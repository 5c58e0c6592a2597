"""The CSR kernel's row-pointer expansion (scatter of row-end marks, then a
prefix sum) gives exactly the per-edge row ids of the binary search it
replaced, on every row-pointer shape the program builds, and the kernels
that use it stay exact against the dense matrix on budget-padded
payloads."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import formats
from repro.kernels.csr import _edge_rows, csr_matvec, csr_transform_matvec
from repro.sampling.plan_cache import _pad_csr


def _csr(counts, nnz=None, n_cols=8, seed=0):
    """CSR with the given per-row edge counts; ``nnz`` above their sum
    stores extra entries past ``indptr[-1]``."""
    counts = np.asarray(counts, np.int64)
    indptr = np.zeros(len(counts) + 1, np.int32)
    np.cumsum(counts, out=indptr[1:])
    nnz = int(indptr[-1]) if nnz is None else nnz
    rng = np.random.default_rng(seed)
    return formats.CSR(len(counts), n_cols, indptr,
                       rng.integers(0, n_cols, nnz).astype(np.int32),
                       rng.standard_normal(nnz).astype(np.float32))


def _searchsorted_rows(csr):
    nnz = csr.indices.shape[0]
    return np.asarray(jnp.searchsorted(jnp.asarray(csr.indptr),
                                       jnp.arange(nnz, dtype=jnp.int32),
                                       side="right") - 1)


def _random_counts(n, p_empty, seed):
    rng = np.random.default_rng(seed)
    return np.where(rng.random(n) < p_empty, 0, rng.integers(1, 6, n))


CASES = {
    "no_empty_rows": lambda: _csr([3, 1, 2, 4]),
    "leading_empty": lambda: _csr([0, 0, 0, 2, 3]),
    "middle_empty": lambda: _csr([2, 0, 0, 0, 1, 0, 3]),
    "trailing_empty": lambda: _csr([1, 4, 0, 0, 0]),
    "empty_everywhere": lambda: _csr([0, 2, 0, 0, 5, 0, 1, 0, 0]),
    "all_in_first_row": lambda: _csr([9, 0, 0, 0]),
    "all_in_middle_row": lambda: _csr([0, 0, 9, 0, 0]),
    "all_in_last_row": lambda: _csr([0, 0, 0, 9]),
    "one_row": lambda: _csr([7]),
    "one_row_one_edge": lambda: _csr([1]),
    "random_sparse_rows": lambda: _csr(_random_counts(300, 0.7, 1)),
    "random_dense_rows": lambda: _csr(_random_counts(300, 0.05, 2)),
    "budget_padded": lambda: _pad_csr(_csr([0, 3, 0, 2, 0]), 12),
    "budget_padded_trailing_empty": lambda: _pad_csr(_csr([2, 1, 0, 0]), 9),
    "budget_padded_one_row": lambda: _pad_csr(_csr([4]), 10),
    "budget_padded_random": lambda: _pad_csr(
        _csr(_random_counts(200, 0.5, 3)), 1024),
    "tail_past_last_pointer": lambda: _csr([2, 0, 3], nnz=9),
    "tail_past_last_pointer_trailing_empty": lambda: _csr([1, 2, 0, 0],
                                                          nnz=6),
    "no_edges": lambda: _csr([0, 0, 0]),
    "no_edges_one_row": lambda: _csr([0]),
}


@pytest.mark.parametrize("case", list(CASES))
def test_edge_rows_equals_searchsorted(case):
    csr = CASES[case]()
    got = _edge_rows(csr)
    assert got.dtype == jnp.int32
    assert got.shape == csr.indices.shape
    np.testing.assert_array_equal(np.asarray(got), _searchsorted_rows(csr))
    # the segment sums rely on sorted ids
    assert np.all(np.diff(np.asarray(got)) >= 0)


def _dense(csr):
    a = np.zeros((csr.n_rows, csr.n_cols), np.float32)
    indptr = np.asarray(csr.indptr)
    for i in range(csr.n_rows):
        for e in range(indptr[i], indptr[i + 1]):
            a[i, csr.indices[e]] += csr.vals[e]
    return a


@pytest.mark.parametrize("counts,budget", [
    ([0, 3, 0, 2, 0, 1], 16),
    ([5, 0, 0], 8),
    (_random_counts(64, 0.5, 4), 256),
])
def test_csr_kernels_match_dense_on_padded_payload(rng, counts, budget):
    """``csr_matvec`` and ``csr_transform_matvec``, forward and both
    gradients, against the dense matrix of the unpadded CSR."""
    csr = _csr(counts, n_cols=24, seed=5)
    a = _dense(csr)
    padded = _pad_csr(csr, budget)
    assert padded.indices.shape[0] == budget
    assert int(padded.indptr[-1]) == budget
    tol = dict(atol=1e-4, rtol=1e-4)

    x = jnp.asarray(rng.standard_normal((csr.n_cols, 7)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((7, 5)), jnp.float32)
    g_y = rng.standard_normal((csr.n_rows, 7)).astype(np.float32)
    g_yw = rng.standard_normal((csr.n_rows, 5)).astype(np.float32)

    np.testing.assert_allclose(np.asarray(csr_matvec(padded, x)),
                               a @ np.asarray(x), **tol)
    dx = jax.grad(lambda x: jnp.sum(csr_matvec(padded, x) * g_y))(x)
    np.testing.assert_allclose(np.asarray(dx), a.T @ g_y, **tol)

    np.testing.assert_allclose(
        np.asarray(csr_transform_matvec(padded, x, w)),
        a @ (np.asarray(x) @ np.asarray(w)), **tol)
    dx, dw = jax.grad(
        lambda x, w: jnp.sum(csr_transform_matvec(padded, x, w) * g_yw),
        argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(dx),
                               a.T @ g_yw @ np.asarray(w).T, **tol)
    np.testing.assert_allclose(np.asarray(dw),
                               (a @ np.asarray(x)).T @ g_yw, **tol)
