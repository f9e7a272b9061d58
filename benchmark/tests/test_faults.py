"""A whole run with the timed path broken underneath must come out not
correct: the harness's look for a chip is skipped and every cell runs on
the CPU at 16^3, with one fault planted in the program at a time."""

import dataclasses

import pytest

from benchmark import run
from benchmark.tests.conftest import SMALL

SPEC = run.load_json(run.os.path.join(run.ROOT, "BENCHMARK.json"))
CELLS = [w["name"] for w in SPEC["workloads"]]


def _run(cell):
    return run.run(cell, 2**35 + 11, 0.3, False, require_tpu=False,
                   config_overrides=SMALL)


def _alter_x(monkeypatch, change):
    """Apply ``change`` to each solution where the program returns it."""
    from amgx_tpu.solvers.base import Solver

    orig = Solver.solve

    def solve(self, *a, **kw):
        res = orig(self, *a, **kw)
        return dataclasses.replace(res, x=change(res.x))

    monkeypatch.setattr(Solver, "solve", solve)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    line = _run(cell)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] >= 1
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("cell", CELLS)
def test_state_unchanged(monkeypatch, cell):
    """The preconditioner's step returns its state unchanged."""
    from amgx_tpu.amg.hierarchy import AMGSolver

    monkeypatch.setattr(AMGSolver, "make_step",
                        lambda self: (lambda params, b, x: x))
    line = _run(cell)
    assert not line["correct"] and line["failed"] == line["attempted"]


@pytest.mark.parametrize("cell", CELLS)
def test_half_the_rows_left_out(monkeypatch, cell):
    _alter_x(monkeypatch, lambda x: x.at[x.shape[0] // 2:].set(0))
    line = _run(cell)
    assert not line["correct"] and line["failed"] == line["attempted"]


@pytest.mark.parametrize("cell", CELLS)
def test_answer_altered(monkeypatch, cell):
    _alter_x(monkeypatch, lambda x: x.at[7].add(1.0))
    line = _run(cell)
    assert not line["correct"] and line["failed"] == line["attempted"]
