"""Pallas TPU kernels: fused transform+aggregate over blocked-ELL
(inter-community subgraph), plus the shared dW reduction kernel.

``bell_spmm_fused`` computes Y = A_bell @ (X @ W) (+ Y_in) in one pass: the
(Fi, Ft) weight stripe lives in VMEM and each gathered (B, Fi) source-feature
block is transformed and immediately contracted against its stored (B, B)
adjacency block — the transformed feature matrix H never round-trips HBM.
Unlike the diagonal tier, the in-kernel transform re-runs per stored block
(a source block referenced by k stored blocks is transformed k times), so
fusion trades recompute FLOPs for the H write+read; the registry cost model
prices both and lets the selector decide per bucket.

``bell_spmm_dw`` is the backward weight kernel: dW = X^T (A^T dY), expressed
as a single blocked reduction sum_{i,k} x_i^T (A^T[i,k] @ dy[col_idx[i,k]])
over the materialized transpose payload — no (n, F) intermediate is ever
written.  The block-diagonal kernel reuses it with K=1 and identity block
columns (ops.py), so both fused VJPs share one Pallas reduction.

Under the mini-batch edge budget both kernels run on the budget-padded
payload (stored-block count capped at a budget-derived K, masked
zero-blocks padding, overflow edges spilled to an in-payload COO): the
grid shape is then batch-invariant, and the spilled edges transform their
gathered source rows per edge (ops.coo_transform_matvec) instead of
forcing an H = X W materialization.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(idx_ref, a_ref, x_ref, w_ref, o_ref, acc_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    h = jnp.dot(x_ref[...], w_ref[...], preferred_element_type=jnp.float32)
    acc_ref[...] += jnp.dot(a_ref[...].astype(jnp.float32), h,
                            preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _kernel_acc(idx_ref, a_ref, x_ref, w_ref, y_ref, o_ref, acc_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        # seed the VMEM scratch from the threaded-through partial instead of
        # zeros: the downstream "+" that would re-read both operands vanishes
        acc_ref[...] = y_ref[...].astype(jnp.float32)

    h = jnp.dot(x_ref[...], w_ref[...], preferred_element_type=jnp.float32)
    acc_ref[...] += jnp.dot(a_ref[...].astype(jnp.float32), h,
                            preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("f_tile", "interpret"))
def bell_spmm_fused(blocks: jax.Array, col_idx: jax.Array, x: jax.Array,
                    w: jax.Array, y_in: jax.Array | None = None, *,
                    f_tile: int = 512, interpret: bool) -> jax.Array:
    """Y = A_bell @ (x @ w) (+ y_in).

    blocks: (nbr, K, B, B); col_idx: (nbr, K) int32; x: (nbc*B, Fi);
    w: (Fi, Fo) with Fo % f_tile == 0; y_in: optional (nbr*B, Fo).
    Returns (nbr*B, Fo).
    """
    nbr, K, B, _ = blocks.shape
    Fi = x.shape[-1]
    Fo = w.shape[-1]
    f_tile = min(f_tile, Fo)
    assert Fo % f_tile == 0, (Fo, f_tile)
    xb = x.reshape(-1, B, Fi)
    grid = (nbr, Fo // f_tile, K)
    in_specs = [
        pl.BlockSpec((None, None, B, B), lambda i, j, k, idx: (i, k, 0, 0)),
        pl.BlockSpec((None, B, Fi), lambda i, j, k, idx: (idx[i, k], 0, 0)),
        pl.BlockSpec((Fi, f_tile), lambda i, j, k, idx: (0, j)),
    ]
    operands = [col_idx, blocks, xb, w]
    kernel = _kernel
    if y_in is not None:
        yb = y_in.reshape(nbr, B, Fo)
        in_specs.append(
            pl.BlockSpec((None, B, f_tile), lambda i, j, k, idx: (i, 0, j)))
        operands.append(yb)
        kernel = _kernel_acc
    gs = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, B, f_tile), lambda i, j, k, idx: (i, 0, j)),
        scratch_shapes=[pltpu.VMEM((B, f_tile), jnp.float32)],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=gs,
        out_shape=jax.ShapeDtypeStruct((nbr, B, Fo), x.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(*operands)
    return out.reshape(nbr * B, Fo)


# ---------------------------------------------------------------------------
# dW reduction
# ---------------------------------------------------------------------------

def _dw_kernel(idx_ref, a_ref, x_ref, g_ref, o_ref, acc_ref):
    i = pl.program_id(2)
    k = pl.program_id(3)

    @pl.when((i == 0) & (k == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    z = jnp.dot(a_ref[...].astype(jnp.float32), g_ref[...],
                preferred_element_type=jnp.float32)          # (B, fo_tile)
    # x_i^T @ z without materializing the transpose: contract the B dims
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), z,
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                  # (fi_tile, fo_tile)

    @pl.when((i == pl.num_programs(2) - 1) & (k == pl.num_programs(3) - 1))
    def _flush():
        o_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("fi_tile", "fo_tile", "interpret"))
def bell_spmm_dw(blocks_t: jax.Array, col_idx_t: jax.Array, x: jax.Array,
                 g: jax.Array, *, fi_tile: int = 512, fo_tile: int = 512,
                 interpret: bool) -> jax.Array:
    """dW = X^T @ (A^T @ G), A^T given in blocked-ELL (transpose payload).

    blocks_t: (nbr, K, B, B); col_idx_t: (nbr, K) int32; x: (nbr*B, Fi);
    g: (nbc*B, Fo).  Returns (Fi, Fo) float32.
    """
    nbr, K, B, _ = blocks_t.shape
    Fi = x.shape[-1]
    Fo = g.shape[-1]
    fi_tile = min(fi_tile, Fi)
    fo_tile = min(fo_tile, Fo)
    assert Fi % fi_tile == 0 and Fo % fo_tile == 0, (Fi, fi_tile, Fo, fo_tile)
    xb = x.reshape(nbr, B, Fi)
    gb = g.reshape(-1, B, Fo)
    grid = (Fi // fi_tile, Fo // fo_tile, nbr, K)
    gs = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, B, B),
                         lambda fi, fo, i, k, idx: (i, k, 0, 0)),
            pl.BlockSpec((None, B, fi_tile),
                         lambda fi, fo, i, k, idx: (i, 0, fi)),
            pl.BlockSpec((None, B, fo_tile),
                         lambda fi, fo, i, k, idx: (idx[i, k], 0, fo)),
        ],
        out_specs=pl.BlockSpec((fi_tile, fo_tile),
                               lambda fi, fo, i, k, idx: (fi, fo)),
        scratch_shapes=[pltpu.VMEM((fi_tile, fo_tile), jnp.float32)],
    )
    return pl.pallas_call(
        _dw_kernel,
        grid_spec=gs,
        out_shape=jax.ShapeDtypeStruct((Fi, Fo), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel",
                                 "arbitrary", "arbitrary")),
    )(col_idx_t, blocks_t, xb, gb)
