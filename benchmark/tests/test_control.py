"""The check's control, at a size a test run holds: the plain reference
solver in bfloat16, one precision below the configurations' float32,
has to come out not correct; in float32 it passes."""

import pytest

from benchmark import control, run
from benchmark.tests.conftest import SMALL

SPEC = run.load_json(run.os.path.join(run.ROOT, "BENCHMARK.json"))


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_bfloat16_control_fails_the_check(cell):
    out = control.control(cell, 5, "bfloat16", config_overrides=SMALL)
    assert not out["correct"]
    c = out["checks"]["residual"]
    assert c["value"] > 10 * c["limit"]


def test_float32_reference_passes_the_check():
    cell = SPEC["workloads"][0]["name"]
    out = control.control(cell, 5, "float32", config_overrides=SMALL)
    assert out["correct"]
