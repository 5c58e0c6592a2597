"""Pallas TPU kernel: fused transform+aggregate for the block-diagonal
(intra-community) subgraph: Y = blockdiag(blocks) @ (X @ W) [+ Y_in].

The unfused GCN path pays an HBM round-trip for H = X @ W: XLA writes H out,
the aggregation kernel reads it back.  Here the weight tile lives in VMEM and
the (B, Fi) @ (Fi, Ft) transform product is consumed immediately by the
(B, B) @ (B, Ft) block contraction — H never touches HBM (TC-GNN / MaxK-GNN's
fusion argument, mapped to the MXU).

Grid = (block, out-feature-tile).  Each step loads the (B, B) adjacency
block, the block's full-width (B, Fi) feature rows, and the (Fi, Ft) weight
stripe, then issues two chained MXU matmuls.  For the diagonal tier the
in-kernel transform does exactly the same FLOPs as the standalone X @ W
(every row transformed once), so fusion is a pure bandwidth/launch win.

The optional ``y_in`` operand turns the kernel into an accumulator
(o = y_in + A (X W)): aggregate() threads one output buffer through the
subgraph list instead of materializing one partial per density bucket.

``block_diag_spmm_dual`` is the dual-weight epilogue variant (SAGE:
Y = X W_self + A (X W_neigh) [+ Y_in]): a *second* weight stripe rides in
VMEM next to the neighbor stripe and the block's rows are transformed by
both — the self term never materializes as a separate (n, Fo) tensor.
Only the diagonal tier gets this (its row block *is* its source block);
off-diagonal tiers accumulate their neighbor terms on top via y_in.

VMEM working set per step: B*B + B*Fi + Fi*Ft + 2*B*Ft floats — with
B=128, Fi=1536, Ft=512 that is ~4.5 MB, inside the ~16 MB budget (the
dual variant adds one more Fi*Ft stripe).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, x_ref, w_ref, o_ref):
    h = jnp.dot(x_ref[...], w_ref[...], preferred_element_type=jnp.float32)
    o_ref[...] = jnp.dot(a_ref[...].astype(jnp.float32), h,
                         preferred_element_type=jnp.float32).astype(o_ref.dtype)


def _kernel_acc(a_ref, x_ref, w_ref, y_ref, o_ref):
    h = jnp.dot(x_ref[...], w_ref[...], preferred_element_type=jnp.float32)
    y = jnp.dot(a_ref[...].astype(jnp.float32), h,
                preferred_element_type=jnp.float32)
    o_ref[...] = (y_ref[...].astype(jnp.float32) + y).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("f_tile", "interpret"))
def block_diag_spmm_fused(blocks: jax.Array, x: jax.Array, w: jax.Array,
                          y_in: jax.Array | None = None, *,
                          f_tile: int = 512, interpret: bool
                          ) -> jax.Array:
    """Y = blockdiag(blocks) @ (x @ w) (+ y_in).

    blocks: (nb, B, B); x: (nb*B, Fi); w: (Fi, Fo) with Fo % f_tile == 0
    (ops.py pads); y_in: optional (nb*B, Fo) accumulator input.
    """
    nb, B, _ = blocks.shape
    n, Fi = x.shape
    assert n == nb * B, (n, nb, B)
    Fo = w.shape[-1]
    f_tile = min(f_tile, Fo)
    assert Fo % f_tile == 0, (Fo, f_tile)
    xb = x.reshape(nb, B, Fi)
    grid = (nb, Fo // f_tile)
    in_specs = [
        pl.BlockSpec((None, B, B), lambda i, j: (i, 0, 0)),
        pl.BlockSpec((None, B, Fi), lambda i, j: (i, 0, 0)),
        pl.BlockSpec((Fi, f_tile), lambda i, j: (0, j)),
    ]
    operands = [blocks, xb, w]
    kernel = _kernel
    if y_in is not None:
        yb = y_in.reshape(nb, B, Fo)
        in_specs.append(pl.BlockSpec((None, B, f_tile), lambda i, j: (i, 0, j)))
        operands.append(yb)
        kernel = _kernel_acc
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, B, f_tile), lambda i, j: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((nb, B, Fo), x.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
    )(*operands)
    return out.reshape(n, Fo)


# ---------------------------------------------------------------------------
# Dual-weight epilogue variant (SAGE): Y = X W_self + A (X W_neigh) [+ Y_in]
# ---------------------------------------------------------------------------

def _kernel_dual(a_ref, x_ref, w_ref, ws_ref, o_ref):
    h = jnp.dot(x_ref[...], w_ref[...], preferred_element_type=jnp.float32)
    y = jnp.dot(a_ref[...].astype(jnp.float32), h,
                preferred_element_type=jnp.float32)
    y += jnp.dot(x_ref[...], ws_ref[...], preferred_element_type=jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)


def _kernel_dual_acc(a_ref, x_ref, w_ref, ws_ref, y_ref, o_ref):
    h = jnp.dot(x_ref[...], w_ref[...], preferred_element_type=jnp.float32)
    y = jnp.dot(a_ref[...].astype(jnp.float32), h,
                preferred_element_type=jnp.float32)
    y += jnp.dot(x_ref[...], ws_ref[...], preferred_element_type=jnp.float32)
    o_ref[...] = (y_ref[...].astype(jnp.float32) + y).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("f_tile", "interpret"))
def block_diag_spmm_dual(blocks: jax.Array, x: jax.Array, w: jax.Array,
                         w_self: jax.Array, y_in: jax.Array | None = None, *,
                         f_tile: int = 512, interpret: bool
                         ) -> jax.Array:
    """Y = blockdiag(blocks) @ (x @ w) + x @ w_self (+ y_in).

    Same grid/tiling as :func:`block_diag_spmm_fused`; ``w_self`` is a
    second (Fi, Fo) stripe sharing ``w``'s BlockSpec.  The diagonal tier's
    row block is its own source block, so the self transform consumes the
    already-resident (B, Fi) feature rows — the dual epilogue costs one
    extra MXU matmul per step and zero extra HBM feature traffic.
    """
    nb, B, _ = blocks.shape
    n, Fi = x.shape
    assert n == nb * B, (n, nb, B)
    Fo = w.shape[-1]
    assert w_self.shape == w.shape, (w_self.shape, w.shape)
    f_tile = min(f_tile, Fo)
    assert Fo % f_tile == 0, (Fo, f_tile)
    xb = x.reshape(nb, B, Fi)
    grid = (nb, Fo // f_tile)
    w_spec = pl.BlockSpec((Fi, f_tile), lambda i, j: (0, j))
    in_specs = [
        pl.BlockSpec((None, B, B), lambda i, j: (i, 0, 0)),
        pl.BlockSpec((None, B, Fi), lambda i, j: (i, 0, 0)),
        w_spec,
        w_spec,
    ]
    operands = [blocks, xb, w, w_self]
    kernel = _kernel_dual
    if y_in is not None:
        yb = y_in.reshape(nb, B, Fo)
        in_specs.append(pl.BlockSpec((None, B, f_tile), lambda i, j: (i, 0, j)))
        operands.append(yb)
        kernel = _kernel_dual_acc
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, B, f_tile), lambda i, j: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((nb, B, Fo), x.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
    )(*operands)
    return out.reshape(n, Fo)
