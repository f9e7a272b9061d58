import numpy as np
import pytest
import scipy.sparse as sps

from benchmark import generate


def _kron_poisson7(nx, ny, nz):
    """The Kronecker assembly of the same operator, row x + nx (y + ny z)."""
    def t(n):
        return sps.diags_array([-np.ones(n - 1), 2 * np.ones(n),
                                -np.ones(n - 1)], offsets=[-1, 0, 1])

    def eye(n):
        return sps.eye_array(n)

    return (sps.kron(sps.kron(eye(nz), eye(ny)), t(nx))
            + sps.kron(sps.kron(eye(nz), t(ny)), eye(nx))
            + sps.kron(sps.kron(t(nz), eye(ny)), eye(nx))).tocsr()


def test_poisson7_8cubed():
    A = generate.operator({"operator": "poisson7", "nx": 8, "ny": 8,
                           "nz": 8})
    n = 8 ** 3
    assert A.shape == (n, n)
    assert A.nnz == 7 * n - 6 * 8 * 8
    assert np.all(A.diagonal() == 6.0)
    assert A.has_sorted_indices
    # a row sums to the number of its missing neighbours
    idx = np.arange(n)
    x, y, z = idx % 8, (idx // 8) % 8, idx // 64
    missing = sum((c == 0).astype(int) + (c == 7) for c in (x, y, z))
    np.testing.assert_array_equal(np.asarray(A.sum(axis=1)).ravel(), missing)
    assert abs(A - A.T).max() == 0


@pytest.mark.parametrize("shape", [(8, 8, 8), (5, 7, 9)])
def test_poisson7_matches_kronecker_assembly(shape):
    A = generate.operator({"operator": "poisson7", "nx": shape[0],
                           "ny": shape[1], "nz": shape[2]})
    assert abs(A - _kron_poisson7(*shape)).max() == 0


def test_operator_float32_is_exact():
    cfg = {"operator": "poisson7", "nx": 6, "ny": 6, "nz": 6}
    A32 = generate.operator(cfg, np.float32)
    assert A32.dtype == np.float32
    assert abs(A32.astype(np.float64) - generate.operator(cfg)).max() == 0


def test_unknown_operator_refused():
    with pytest.raises(ValueError):
        generate.operator({"operator": "poisson27", "nx": 4, "ny": 4,
                           "nz": 4})


def test_rhs_fresh_from_seed():
    traffic = {"rhs": "normal", "rhs_count": 8}
    seed = 2**40 + 3
    a = generate.rhs_ring(1000, traffic, seed)
    assert a.shape == (8, 1000) and a.dtype == np.float32
    np.testing.assert_array_equal(a, generate.rhs_ring(1000, traffic, seed))
    # every field of the ring is a fresh one
    for i in range(8):
        for j in range(i):
            assert not np.array_equal(np.abs(a[i]), np.abs(a[j]))
    assert abs(a.mean()) < 0.05 and abs(a.std() - 1) < 0.05
    # another seed brings other fields, also for large and negative seeds
    for other in (seed + 1, 3, -seed, 2**64 + seed):
        b = generate.rhs_ring(1000, traffic, other)
        assert not any(np.array_equal(np.abs(b[k]), np.abs(a[k]))
                       for k in range(8))


@pytest.mark.parametrize("k", [0, 5])
def test_rhs_alone_is_the_rings(k):
    traffic = {"rhs": "normal", "rhs_count": 6}
    ring = generate.rhs_ring(300, traffic, 7)
    np.testing.assert_array_equal(generate.rhs(300, traffic, 7, k), ring[k])
    # the reference's float64 copy holds the program's float32 values
    b64 = generate.rhs(300, traffic, 7, k, np.float64)
    assert b64.dtype == np.float64
    np.testing.assert_array_equal(b64, ring[k].astype(np.float64))


def test_unknown_rhs_refused():
    with pytest.raises(ValueError):
        generate.rhs(10, {"rhs": "lognormal", "rhs_count": 1}, 1, 0)
