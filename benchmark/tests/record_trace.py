"""Record the small TPU trace that the trace tests read.

    python3 -m benchmark.tests.record_trace <out.xplane.pb.gz>

Runs the poisson7_agg cell on a TPU at 32^3 (the Pallas DIA kernel runs
from 8,192 rows) for a single traced solve, through the harness's own
path, and writes the profiler's ``.xplane.pb`` gzipped to ``out``.
"""

import glob
import gzip
import os
import shutil
import sys
import tempfile

from benchmark import run


def main(out: str) -> int:
    keep = tempfile.mkdtemp(prefix="bench_record_")
    try:
        line = run.run("poisson7_agg.solve", 1, 0.001, True,
                       config_overrides={"nx": 32, "ny": 32, "nz": 32},
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
