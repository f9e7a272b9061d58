"""Entry ``amg_solver``: the program's single-chip solver API,
``create_solver(cfg).setup(A)`` and then ``solve(b)`` per right-hand side
(the sequence of AmgX's ``AMGX_solver_setup`` / ``AMGX_solver_solve``)."""

from __future__ import annotations

import json

import numpy as np


def _format(A) -> str:
    return ("MATRIX_FREE" if A.has_matrix_free else
            "DIA" if A.has_dia else
            "dense" if A.has_dense else
            "ELLw" if A.ell_wcols is not None else
            "ELL" if A.has_ell else "CSR")


class Program:
    def __init__(self, config: dict, A, rhs: np.ndarray, devices):
        import amgx_tpu

        amgx_tpu.initialize()
        self._config = config
        self._A = A
        self._rhs = rhs
        self._device = devices[0]
        self.solver = None
        self._b = []

    def setup(self):
        """The program's set-up: the CSR upload and the hierarchy."""
        from amgx_tpu.config.amg_config import AMGConfig
        from amgx_tpu.core.matrix import SparseMatrix
        from amgx_tpu.solvers import create_solver

        A = self._A
        cfg = AMGConfig.from_string(json.dumps(self._config["amgx_config"]))
        self.matrix = SparseMatrix.from_csr(
            A.indptr, A.indices, A.data, n_cols=A.shape[1])
        self.solver = create_solver(cfg, "default")
        self.solver.setup(self.matrix)

    def stage(self):
        """Hand the right-hand sides to the device, once, before the
        window."""
        import jax

        self._b = [jax.device_put(b, self._device) for b in self._rhs]
        for b in self._b:
            b.block_until_ready()

    def solve(self, k: int):
        return self.solver.solve(self._b[k], block=False)

    @staticmethod
    def block(handle):
        handle.x.block_until_ready()

    @staticmethod
    def result(handle):
        """``(x, iterations, converged)`` of a finished solve."""
        return np.asarray(handle.x), int(handle.iters), bool(handle.converged)

    def describe(self) -> dict:
        precond = getattr(self.solver, "precond", None)
        levels = [lvl.A for lvl in getattr(precond, "levels", [])] or [
            self.solver.A]
        fine = levels[0]
        return {
            "levels": [{"format": _format(A), "rows": int(A.n_rows)}
                       for A in levels],
            "fine": {"format": _format(fine), "rows": int(fine.n_rows)},
        }

    def close(self):
        self.solver = self.matrix = None
        self._b = []
