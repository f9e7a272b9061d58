"""Record the small classical TPU trace that test_unstructured_spmv reads.

    python3 -m benchmark.tests.record_classical_trace <out.xplane.pb.gz>

Runs the poisson7_classical cell on a TPU at 24^3 (level 1's Galerkin
operator and the transfers go through the unstructured SpMV path) for a
single traced solve, through the harness's own path, and writes the
profiler's ``.xplane.pb`` gzipped to ``out``.  The hierarchy is built by
the host builder, which gives the device pipeline's levels without
compiling its programs at these shapes; the trace holds only the solve.
"""

import copy
import glob
import gzip
import os
import shutil
import sys
import tempfile

from benchmark import run


def main(out: str) -> int:
    config = run.load_json(os.path.join(run.BENCH, "configs",
                                        "poisson7_classical.json"))
    amgx_config = copy.deepcopy(config["amgx_config"])
    amgx_config["solver"]["preconditioner"]["setup_location"] = "HOST"
    keep = tempfile.mkdtemp(prefix="bench_record_")
    try:
        line = run.run("poisson7_classical.solve", 1, 0.001, True,
                       config_overrides={"nx": 24, "ny": 24, "nz": 24,
                                         "amgx_config": amgx_config},
                       keep_trace=keep)
        (path,) = glob.glob(os.path.join(keep, "**", "*.xplane.pb"),
                            recursive=True)
        with open(path, "rb") as src, gzip.open(out, "wb") as dst:
            shutil.copyfileobj(src, dst)
    finally:
        shutil.rmtree(keep, ignore_errors=True)
    print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
