"""The kernels the benchmark cells' plans dispatch, at the widths those
cells run, against the plain references of ``kernels/ref.py``.

The committed plans put ``block_diag`` / ``block_diag_fused`` on the
diagonal tier and ``csr`` / ``csr_fused`` on the inter tiers (``csr``'s
reduce has its own width sweep in ``test_csr_reduce.py``); SAGE's layers
run ``block_diag_fused``'s dual-weight hook.  The cells' widths: GCN
256 -> 256 -> 256 -> 39 over 128 inputs (blogcatalog), GIN 50 -> 64 x 5
(PPI), SAGE 96 -> 256 -> 22 (amazon0505) -- narrow, odd and below the 128
lanes that ``kernels.ops`` pads every width to.  Forward and VJP are
compared densely, in float32 and in bfloat16 (the kernels accumulate in
float32; the reference computes from the same bfloat16 inputs in
float32 at ``Precision.HIGHEST``), each as the largest error over the
reference's largest magnitude.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import formats
from repro.kernels import ops, ref
from repro.kernels.registry import REGISTRY

NB, B = 16, 16          # 16 diagonal blocks of the cells' community size
N = NB * B
DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16}
# float32: a few ulps of the largest entry.  bfloat16: the output and the
# in-kernel transform round to 8 significant bits (2^-9 relative), summed
# over a block row's 16 terms.
RTOL = {"f32": 1e-5, "bf16": 2e-2}


def _rel(u, v) -> float:
    u, v = np.asarray(u, np.float32), np.asarray(v, np.float32)
    return float(np.max(np.abs(u - v)) / np.max(np.abs(v)))


def _check(run, reference, args, dt, seed):
    """Forward and VJP of ``run`` against ``reference`` (which sees the
    same inputs upcast to float32), under one random cotangent."""
    up = [a.astype(jnp.float32) for a in args]
    with jax.default_matmul_precision("highest"):
        y_ref, vjp_ref = jax.vjp(reference, *up)
    y, vjp = jax.vjp(run, *args)
    assert y.dtype == args[0].dtype and y.shape == y_ref.shape
    cot = jnp.asarray(np.random.default_rng(seed).standard_normal(y.shape),
                      args[0].dtype)
    with jax.default_matmul_precision("highest"):
        g_ref = vjp_ref(cot.astype(jnp.float32))
    g = vjp(cot)
    gaps = dict(y=_rel(y, y_ref))
    gaps.update({f"d{i}": _rel(p, q) for i, (p, q) in
                 enumerate(zip(g, g_ref))})
    bad = {k: v for k, v in gaps.items() if not v <= RTOL[dt]}
    assert not bad, bad


def _arrays(seed, dt, *shapes):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.standard_normal(s) / np.sqrt(s[0]), DTYPES[dt])
            for s in shapes]


def _blocks(dt):
    rng = np.random.default_rng(1)
    mask = rng.random((NB, B, B)) < 0.4
    return jnp.asarray(np.where(mask, rng.standard_normal((NB, B, B)), 0.0),
                       DTYPES[dt])


@pytest.mark.parametrize("dt", list(DTYPES))
@pytest.mark.parametrize("f", [22, 39, 50, 64, 256])
def test_block_diag_at_cell_widths(f, dt):
    blocks = _blocks(dt)
    x, = _arrays(f, dt, (N, f))
    _check(lambda x: ops.block_diag_matvec(blocks, x),
           lambda x: ref.block_diag_spmm(blocks.astype(jnp.float32), x),
           [x], dt, f)


FUSED_WIDTHS = [(128, 256), (256, 256), (256, 39), (96, 256), (256, 22)]


@pytest.mark.parametrize("dt", list(DTYPES))
@pytest.mark.parametrize("fin,fout", FUSED_WIDTHS)
def test_block_diag_fused_at_cell_widths(fin, fout, dt):
    blocks = _blocks(dt)
    x, w = _arrays(fin + fout, dt, (N, fin), (fin, fout))
    _check(lambda x, w: ops.block_diag_fused_matvec(blocks, x, w),
           lambda x, w: ref.block_diag_spmm(blocks.astype(jnp.float32),
                                            x @ w),
           [x, w], dt, fin)


def _csr():
    rng = np.random.default_rng(2)
    e = 6 * N
    rows = rng.integers(0, N, e).astype(np.int32)
    cols = rng.integers(0, N, e).astype(np.int32)
    vals = rng.standard_normal(e).astype(np.float32)
    coo = formats.coo_from_edges(N, N, rows, cols, vals)
    return formats.coo_to_csr(coo), coo


@pytest.mark.parametrize("dt", list(DTYPES))
@pytest.mark.parametrize("fin,fout", FUSED_WIDTHS)
def test_csr_fused_at_cell_widths(fin, fout, dt):
    csr, coo = _csr()
    spec = REGISTRY.get("csr_fused")
    x, w = _arrays(fin * fout, dt, (N, fin), (fin, fout))
    _check(lambda x, w: spec.fused_matvec(csr, x, w),
           lambda x, w: ref.coo_spmm_dense_ref(coo.rows, coo.cols, coo.vals,
                                               x @ w, N),
           [x, w], dt, fout)


@pytest.mark.parametrize("dt", list(DTYPES))
@pytest.mark.parametrize("fin,fout", [(96, 256), (256, 22)])
def test_block_diag_dual_at_cell_widths(fin, fout, dt):
    """SAGE's layer on the diagonal tier: ``X W_self + A (X W_neigh)``."""
    blocks = _blocks(dt)
    x, w, ws = _arrays(fin + 2 * fout, dt, (N, fin), (fin, fout), (fin, fout))
    _check(lambda x, w, ws: ops.block_diag_dual_matvec(blocks, x, w, ws),
           lambda x, w, ws: (ref.block_diag_spmm(blocks.astype(jnp.float32),
                                                 x @ w) + x @ ws),
           [x, w, ws], dt, fout)
