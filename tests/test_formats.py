"""Format containers + converters: every format must represent the same
matrix as the dense ground truth."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import formats
from repro.kernels import ref


def random_coo(rng, n, nnz, block_diag_only=False, B=8):
    if block_diag_only:
        nb = n // B
        b = rng.integers(0, nb, nnz)
        r = b * B + rng.integers(0, B, nnz)
        c = b * B + rng.integers(0, B, nnz)
    else:
        r = rng.integers(0, n, nnz)
        c = rng.integers(0, n, nnz)
    # dedup (r, c)
    key = r.astype(np.int64) * n + c
    _, keep = np.unique(key, return_index=True)
    r, c = r[keep], c[keep]
    v = rng.standard_normal(len(r)).astype(np.float32)
    return formats.coo_from_edges(n, n, r, c, v)


def dense_of(coo: formats.COO) -> np.ndarray:
    a = np.zeros((coo.n_rows, coo.n_cols), np.float32)
    a[np.asarray(coo.rows), np.asarray(coo.cols)] = np.asarray(coo.vals)
    return a


@pytest.mark.parametrize("n,nnz", [(16, 5), (64, 100), (128, 500)])
def test_coo_csr_ell_agree(rng, n, nnz):
    coo = random_coo(rng, n, nnz)
    dense = dense_of(coo)
    x = rng.standard_normal((n, 7)).astype(np.float32)
    y_ref = dense @ x
    y_coo = ref.coo_spmm(coo.rows, coo.cols, coo.vals, jnp.asarray(x), n)
    np.testing.assert_allclose(y_coo, y_ref, atol=1e-4)
    ell = formats.coo_to_ell(coo)
    y_ell = ref.ell_spmm(ell.indices, ell.vals, jnp.asarray(x))
    np.testing.assert_allclose(y_ell, y_ref, atol=1e-4)
    csr = formats.coo_to_csr(coo)
    assert csr.nnz == coo.nnz
    indptr = np.asarray(csr.indptr)
    assert indptr[0] == 0 and indptr[-1] == coo.nnz
    assert np.all(np.diff(indptr) >= 0)


@pytest.mark.parametrize("B", [4, 8, 16])
def test_blockdiag_roundtrip(rng, B):
    n = 8 * B
    coo = random_coo(rng, n, 3 * n, block_diag_only=True, B=B)
    bd = formats.coo_to_blockdiag(coo, B)
    dense = dense_of(coo)
    x = rng.standard_normal((bd.n, 5)).astype(np.float32)
    y = ref.block_diag_spmm(bd.blocks, jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(y)[:n], dense @ x[:n], atol=1e-4)


@pytest.mark.parametrize("B", [4, 8])
def test_bell_roundtrip(rng, B):
    n = 6 * B
    coo = random_coo(rng, n, 4 * n)
    bell = formats.coo_to_bell(coo, B)
    dense = dense_of(coo)
    x = rng.standard_normal((bell.n_cols, 9)).astype(np.float32)
    y = ref.bell_spmm(bell.blocks, bell.col_idx, jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(y)[:n], dense @ x[:bell.n_cols][:n],
                               atol=1e-4)
    # padding blocks must be all-zero
    nv = np.asarray(bell.n_valid)
    blocks = np.asarray(bell.blocks)
    for i in range(bell.n_brow):
        for k in range(nv[i], bell.max_blocks):
            assert not blocks[i, k].any()


@settings(max_examples=25, deadline=None)
@given(n=st.integers(8, 64), nnz=st.integers(1, 200), f=st.integers(1, 16),
       seed=st.integers(0, 2**31 - 1))
def test_property_all_formats_agree(n, nnz, f, seed):
    """Property: COO/ELL/BELL/dense all compute the same SpMM."""
    rng = np.random.default_rng(seed)
    coo = random_coo(rng, n, nnz)
    if coo.nnz == 0:
        return
    dense = dense_of(coo)
    x = rng.standard_normal((max(coo.n_cols, ((n + 7) // 8) * 8), f)).astype(np.float32)
    y_ref = dense @ x[:n]
    y_coo = np.asarray(ref.coo_spmm(coo.rows, coo.cols, coo.vals,
                                    jnp.asarray(x[:n]), n))
    np.testing.assert_allclose(y_coo, y_ref, atol=1e-3, rtol=1e-3)
    ell = formats.coo_to_ell(coo)
    y_ell = np.asarray(ref.ell_spmm(ell.indices, ell.vals, jnp.asarray(x[:n])))
    np.testing.assert_allclose(y_ell, y_ref, atol=1e-3, rtol=1e-3)
    bell = formats.coo_to_bell(coo, 8)
    xp = np.zeros((bell.n_cols, f), np.float32)
    xp[:n] = x[:n]
    y_bell = np.asarray(ref.bell_spmm(bell.blocks, bell.col_idx,
                                      jnp.asarray(xp)))[:n]
    np.testing.assert_allclose(y_bell, y_ref, atol=1e-3, rtol=1e-3)


@pytest.mark.parametrize("budget", [None, 512])
@pytest.mark.parametrize("kind", ["diag", "offdiag"])
def test_repeated_edges_count_in_every_format(rng, kind, budget):
    """An edge listed twice (a graph's own self-loop plus the one GCN adds)
    counts twice in every kernel's payload, as in the COO sum."""
    from repro.core import adaptgear, decompose
    from repro.kernels.registry import REGISTRY
    n, B = 64, 16
    coo = random_coo(rng, n, 300, block_diag_only=(kind == "diag"), B=B)
    r, c, v = (np.asarray(a) for a in (coo.rows, coo.cols, coo.vals))
    keep = ((r // B) == (c // B)) == (kind == "diag")
    r, c, v = (np.concatenate([a[keep], a[keep][:20]]) for a in (r, c, v))
    order = np.argsort(r, kind="stable")
    sub = decompose.build_subgraph("t", kind, n, B, r[order], c[order],
                                   v[order], edge_budget=budget)
    x = rng.standard_normal((n, 8)).astype(np.float32)
    want = np.zeros((n, 8), np.float32)
    np.add.at(want, r, v[:, None] * x[c])
    for spec in REGISTRY.candidates_for(sub):
        got = adaptgear.aggregate_sub(sub, jnp.asarray(x), spec.name)
        np.testing.assert_allclose(got, want, atol=1e-5, err_msg=spec.name)
