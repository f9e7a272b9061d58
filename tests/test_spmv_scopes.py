"""Named scopes of the unstructured SpMV branches (ops/spmv.py).

A device trace attributes each op to its scope path; the benchmark's
unstructured-SpMV readers key on ``spmv_ell``, ``spmv_well`` and
``spmv_csr``.  Each jitted SpMV here is compiled on the CPU and every op
that carries source metadata must sit inside its branch's scope, while
the structured branches (DIA, grid transfers) carry none of the three.
"""

import functools
import re

import jax
import numpy as np
import pytest
import scipy.sparse as sps

from amgx_tpu.amg.aggregation import build_aggregation_level
from amgx_tpu.config.amg_config import AMGConfig
from amgx_tpu.core.matrix import SparseMatrix
from amgx_tpu.io.poisson import poisson_scipy
from amgx_tpu.ops import pallas_well
from amgx_tpu.ops.spmv import spmv

SCOPES = ("spmv_ell", "spmv_well", "spmv_csr")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _op_names(A, x):
    """Scope paths of the compiled program's ops (the reducers nested
    in a reduce carry the bare primitive name and are left out).  A
    fresh function each time: JAX would reuse an earlier trace."""
    text = jax.jit(lambda A, x: spmv(A, x)).lower(A, x).compile().as_text()
    return [n for n in _OP_NAME.findall(text)
            if n.startswith("jit(<lambda>)/")]


def _in_scope(name, scope):
    return scope in name.split("/")


def _banded(n, w, bw, seed=3):
    """Random matrix with ``w`` entries a row within +-bw of the
    diagonal: above the dense cutoff (4096 rows), so ELL is built."""
    rng = np.random.default_rng(seed)
    r = np.repeat(np.arange(n), w)
    c = np.clip(r + rng.integers(-bw, bw + 1, r.shape), 0, n - 1)
    m = sps.csr_matrix((rng.standard_normal(r.shape), (r, c)), shape=(n, n))
    m.sum_duplicates()
    m.sort_indices()
    return m


def _irregular(n, seed=4):
    """One long row among short ones: ELL padding would pass 4x, so the
    matrix keeps CSR."""
    m = _banded(n, 2, 50, seed).tolil()
    m[0, : 120] = 1.0
    return m.tocsr()


def _ell(block_size):
    A = SparseMatrix.from_scipy(_banded(4608, 5, 200), block_size=block_size)
    assert A.has_ell and A.ell_wcols is None
    return A


def _csr(block_size):
    A = SparseMatrix.from_scipy(_irregular(4608), block_size=block_size)
    assert not (A.has_ell or A.has_dia or A.has_dense)
    return A


@pytest.mark.parametrize("block_size", [1, 2])
@pytest.mark.parametrize("fmt,scope", [("ell", "spmv_ell"),
                                       ("csr", "spmv_csr")])
def test_gather_branches_carry_their_scope(fmt, scope, block_size):
    A = (_ell if fmt == "ell" else _csr)(block_size)
    x = np.random.default_rng(0).standard_normal(A.n_cols * block_size)
    names = _op_names(A, x)
    assert names
    assert all(_in_scope(n, scope) for n in names), names
    others = set(SCOPES) - {scope}
    assert not any(_in_scope(n, s) for n in names for s in others)


def test_windowed_kernel_carries_spmv_well(monkeypatch):
    """The windowed-ELL branch, taken as on a TPU, with the kernel in
    interpret mode (the CPU's gate picks the XLA gather instead)."""
    monkeypatch.setenv("AMGX_TPU_TILED_ELL", "1")
    A = SparseMatrix.from_scipy(_banded(6000, 5, 300), dtype=np.float32)
    assert A.ell_wcols is not None
    x = np.random.default_rng(1).standard_normal(A.n_cols).astype(
        np.float32)
    # the CPU's own gate: windowed arrays ride the XLA gather
    assert all(_in_scope(n, "spmv_ell") for n in _op_names(A, x))
    monkeypatch.setattr(pallas_well, "well_kernel_selected", lambda A: True)
    monkeypatch.setattr(pallas_well, "pallas_well_spmv", functools.partial(
        pallas_well.pallas_well_spmv, interpret=True))
    names = _op_names(A, x)
    assert names and all(_in_scope(n, "spmv_well") for n in names), names
    y = np.asarray(jax.jit(lambda A, x: spmv(A, x))(A, x))
    ref = _banded(6000, 5, 300).astype(np.float32) @ x
    np.testing.assert_allclose(y, ref, rtol=1e-4, atol=1e-4)


def _geo_transfers():
    cfg = AMGConfig.from_string(
        '{"config_version": 2, "solver": {"scope": "amg", "solver": "AMG",'
        ' "algorithm": "AGGREGATION", "selector": "SIZE_8"}}')
    A = poisson_scipy((8, 8, 8))
    P, R, _, geo = build_aggregation_level(A, cfg, "amg")
    assert geo is not None
    return [SparseMatrix.from_scipy(M, geo_transfer=geo) for M in (P, R)]


def test_structured_branches_carry_none():
    A = SparseMatrix.from_scipy(poisson_scipy((16, 16, 16)))
    assert A.has_dia
    mats = [A] + _geo_transfers()
    assert all(M.has_geo_transfer for M in mats[1:])
    for M in mats:
        x = np.ones(M.n_cols)
        names = _op_names(M, x)
        assert names
        assert not any(_in_scope(n, s) for n in names for s in SCOPES)
