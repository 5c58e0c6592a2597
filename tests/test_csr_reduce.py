"""The ``csr`` kernel's sorted row-tile reduce (Pallas, interpret mode off
the chip) against ``jax.ops.segment_sum``, and the kernels built on it,
``csr_matvec`` and ``csr_transform_matvec``, forward and gradients, against
the dense matrix.  Float32 messages enter the sum exactly (three bfloat16
pieces against an exact one-hot), so the sums agree at float32 rounding."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import formats
from repro.kernels import csr as csr_mod
from repro.kernels.csr import (_edge_rows, _sorted_reduce, csr_matvec,
                               csr_transform_matvec, sorted_reduce_pallas)
from repro.sampling.plan_cache import _pad_csr

R = csr_mod.ROW_TILE
EPS = float(np.finfo(np.float32).eps)


def _csr(counts, nnz=None, n_cols=None, seed=0):
    """CSR with the given per-row edge counts; ``nnz`` above their sum
    stores extra entries past ``indptr[-1]``."""
    counts = np.asarray(counts, np.int64)
    n_cols = len(counts) if n_cols is None else n_cols
    indptr = np.zeros(len(counts) + 1, np.int32)
    np.cumsum(counts, out=indptr[1:])
    nnz = int(indptr[-1]) if nnz is None else nnz
    rng = np.random.default_rng(seed)
    return formats.CSR(len(counts), n_cols, indptr,
                       rng.integers(0, n_cols, nnz).astype(np.int32),
                       rng.standard_normal(nnz).astype(np.float32))


def _counts(n, p_empty, hi, seed):
    rng = np.random.default_rng(seed)
    return np.where(rng.random(n) < p_empty, 0, rng.integers(1, hi, n))


def _with(counts, at, value):
    counts = np.array(counts)
    counts[at] = value
    return counts


CASES = {
    "leading_empty_rows": lambda: _csr([0] * 300 + [3, 1, 2] * 50),
    "middle_empty_rows": lambda: _csr([2] * 100 + [0] * 400 + [1] * 100),
    "trailing_empty_rows": lambda: _csr([4] * 150 + [0] * 500),
    "empty_tiles_everywhere": lambda: _csr(
        ([0] * 3 * R + [5] * 7) * 3 + [0] * 2 * R),
    # one row holds several chunks' worth of edges
    "row_longer_than_a_chunk": lambda: _csr(_with(_counts(400, 0.3, 6, 1),
                                                  200, 9000)),
    "first_row_longer_than_a_chunk": lambda: _csr(_with([1] * 300, 0, 5000)),
    # one chunk spans many row tiles
    "chunk_spans_many_tiles": lambda: _csr(_counts(20 * R, 0.6, 2, 2)),
    "rows_not_a_tile_multiple": lambda: _csr(_counts(3 * R + 37, 0.2, 30,
                                                     3)),
    "fewer_rows_than_a_tile": lambda: _csr([3, 0, 5, 1, 0, 2, 7]),
    "one_row": lambda: _csr([700]),
    "budget_padded": lambda: _pad_csr(_csr(_counts(500, 0.5, 8, 4)), 4096),
    "budget_padded_trailing_empty": lambda: _pad_csr(
        _csr([3] * 200 + [0] * 300), 2000),
    "edges_past_last_pointer": lambda: _csr(_counts(300, 0.3, 9, 5),
                                            nnz=3000),
    "edges_past_last_pointer_no_rows": lambda: _csr([0] * 40, nnz=500),
    "dense_random": lambda: _csr(_counts(2 * R + 5, 0.05, 60, 6)),
}


def _assert_sums(got, msgs, csr):
    """``got`` is the per-row sum of ``msgs`` at float32 rounding: within
    a few float32 epsilons of the summed magnitudes of the exact (float64)
    sum, whatever the order of the additions; ``jax.ops.segment_sum``
    (float32) meets the same bound."""
    msgs = np.asarray(msgs, np.float64)
    rows = np.asarray(_edge_rows(csr))
    keep = rows < csr.n_rows
    want = np.zeros((csr.n_rows, msgs.shape[1]))
    mags = np.zeros_like(want)
    np.add.at(want, rows[keep], msgs[keep])
    np.add.at(mags, rows[keep], np.abs(msgs[keep]))
    bound = 8 * EPS * mags
    seg = np.asarray(jax.ops.segment_sum(
        jnp.asarray(msgs, jnp.float32), _edge_rows(csr),
        num_segments=csr.n_rows, indices_are_sorted=True), np.float64)
    assert np.all(np.abs(seg - want) <= bound)
    got = np.asarray(got, np.float64)
    assert got.shape == want.shape
    assert np.all(np.abs(got - want) <= bound), np.max(
        np.abs(got - want) / np.maximum(mags, 1e-30)) / EPS


@pytest.mark.parametrize("case", list(CASES))
def test_sorted_reduce_matches_segment_sum(case):
    csr = CASES[case]()
    rows = _edge_rows(csr)
    msgs = jnp.asarray(np.random.default_rng(7).standard_normal(
        (csr.indices.shape[0], 64)), jnp.float32)
    got = sorted_reduce_pallas(msgs, rows, n_rows=csr.n_rows, interpret=True)
    assert got.shape == (csr.n_rows, 64) and got.dtype == jnp.float32
    _assert_sums(got, msgs, csr)


@pytest.mark.parametrize("f", (22, 39, 50, 64, 256))
def test_sorted_reduce_widths(f):
    csr = _csr(_counts(2 * R + 19, 0.3, 40, f))
    msgs = jnp.asarray(np.random.default_rng(f).standard_normal(
        (csr.indices.shape[0], f)), jnp.float32)
    got = sorted_reduce_pallas(msgs, _edge_rows(csr), n_rows=csr.n_rows,
                               interpret=True)
    _assert_sums(got, msgs, csr)


def test_sorted_reduce_keeps_float32_bits():
    """Messages that bfloat16 would round (24-bit mantissas) sum exactly:
    one edge per row returns each message bit for bit."""
    csr = _csr([1] * (R + 3))
    msgs = (1.0 + jnp.arange(csr.n_rows * 8, dtype=jnp.float32)
            * 2.0 ** -23).reshape(-1, 8) * 3.0
    got = sorted_reduce_pallas(msgs, _edge_rows(csr), n_rows=csr.n_rows,
                               interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(msgs))


def test_sorted_reduce_bfloat16_messages():
    csr = _csr(_counts(R + 50, 0.2, 20, 8))
    msgs = jnp.asarray(np.random.default_rng(8).standard_normal(
        (csr.indices.shape[0], 39)), jnp.bfloat16)
    got = sorted_reduce_pallas(msgs, _edge_rows(csr), n_rows=csr.n_rows,
                               interpret=True)
    _assert_sums(got, msgs.astype(jnp.float32), csr)


def test_no_edges_give_zeros():
    csr = _csr([0, 0, 0])
    x = jnp.ones((3, 5), jnp.float32)
    np.testing.assert_array_equal(np.asarray(csr_matvec(csr, x)),
                                  np.zeros((3, 5), np.float32))
    dx = jax.grad(lambda x: jnp.sum(csr_matvec(csr, x)))(x)
    np.testing.assert_array_equal(np.asarray(dx), np.zeros((3, 5)))
    y = _sorted_reduce(jnp.zeros((0, 4), jnp.float32),
                       jnp.zeros((0,), jnp.int32), 6)
    np.testing.assert_array_equal(np.asarray(y), np.zeros((6, 4)))


def _dense(csr):
    a = np.zeros((csr.n_rows, csr.n_cols), np.float64)
    indptr = np.asarray(csr.indptr)
    for i in range(csr.n_rows):
        for e in range(indptr[i], indptr[i + 1]):
            a[i, csr.indices[e]] += csr.vals[e]
    return a


KERNEL_CASES = {
    "rows_not_a_tile_multiple": lambda: _csr(_counts(R + 61, 0.3, 12, 9),
                                             n_cols=170),
    "budget_padded": lambda: _pad_csr(_csr(_counts(150, 0.5, 6, 10),
                                           n_cols=150), 1024),
    "edges_past_last_pointer": lambda: _csr(_counts(90, 0.4, 5, 11),
                                            nnz=700, n_cols=90),
}


@pytest.mark.parametrize("case", list(KERNEL_CASES))
def test_csr_kernels_match_dense(case):
    """``csr_matvec`` (custom VJP: the transposed sorted reduce) and
    ``csr_transform_matvec`` (the reduce's own VJP), forward and the
    gradients for ``x`` and ``w``, against the dense matrix in float64."""
    csr = KERNEL_CASES[case]()
    a = _dense(csr)
    rng = np.random.default_rng(12)
    x = rng.standard_normal((csr.n_cols, 50)).astype(np.float32)
    w = rng.standard_normal((50, 22)).astype(np.float32)
    g_y = rng.standard_normal((csr.n_rows, 50))
    g_yw = rng.standard_normal((csr.n_rows, 22))
    xd, wd = x.astype(np.float64), w.astype(np.float64)
    tol = dict(rtol=1e-5, atol=1e-5)

    np.testing.assert_allclose(np.asarray(csr_matvec(csr, x)), a @ xd, **tol)
    dx = jax.grad(lambda x: jnp.sum(csr_matvec(csr, x) * g_y))(x)
    np.testing.assert_allclose(np.asarray(dx), a.T @ g_y, **tol)

    np.testing.assert_allclose(np.asarray(csr_transform_matvec(csr, x, w)),
                               a @ (xd @ wd), rtol=1e-4, atol=1e-4)
    dx, dw = jax.grad(
        lambda x, w: jnp.sum(csr_transform_matvec(csr, x, w) * g_yw),
        argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(dx), a.T @ g_yw @ wd.T,
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(dw), (a @ xd).T @ g_yw,
                               rtol=1e-4, atol=1e-4)
