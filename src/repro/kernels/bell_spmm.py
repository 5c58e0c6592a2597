"""Pallas TPU kernel: blocked-ELL SpMM with scalar-prefetch block indices
(inter-community subgraph).

Paper mapping (§3.2 'CSR-based kernel' for low-density subgraphs): on CUDA a
CTA covers several destination vertices, threads walk the CSR neighbor lists
and gather source features from global memory.  A TPU has no per-thread
gather; the idiomatic equivalent is *block-level* indirection: store the
inter-community adjacency as a CSR over (B, B) tiles, pad each block-row to K
tiles (blocked-ELL), and let the BlockSpec index_map -- fed by scalar-prefetch
-- DMA exactly the (B, Ft) feature tile named by each stored block.

Grid = (block-rows, feature-tiles, K); K is the innermost reduction
("arbitrary") dimension accumulated in a VMEM scratch and flushed at k==K-1.
Padding tiles are all-zero and point at block-column 0, so no masking is
needed inside the kernel (no data-dependent control flow on TPU).

That zero-padding contract is what makes the *budget-padded* variant free
at kernel level: the mini-batch path caps K from the sampler's edge budget
(formats.bell_budget_k) and pads every block-row to exactly that many
slots, so this kernel runs an identical grid for every sampled batch — the
jitted step never retraces — while executing the masked zero-blocks as
ordinary (correct, zero-contributing) MXU tiles.  Overflow edges never
reach this kernel; they ride the COO spill tier of the payload.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(idx_ref, a_ref, x_ref, o_ref, acc_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], x_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _kernel_acc(idx_ref, a_ref, x_ref, y_ref, o_ref, acc_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        # accumulation mode: seed the VMEM scratch from the threaded-through
        # partial output instead of zeros — the separate partial-sum pass
        # (and its full-width HBM tensor) disappears
        acc_ref[...] = y_ref[...].astype(jnp.float32)

    acc_ref[...] += jnp.dot(a_ref[...], x_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("f_tile", "interpret"))
def bell_spmm(blocks: jax.Array, col_idx: jax.Array, x: jax.Array,
              y_in: jax.Array | None = None, *,
              f_tile: int = 512, interpret: bool) -> jax.Array:
    """Y = A_bell @ x (+ y_in).

    blocks: (nbr, K, B, B); col_idx: (nbr, K) int32; x: (nbc*B, F);
    y_in: optional (nbr*B, F) accumulator input.  Returns (nbr*B, F).
    """
    nbr, K, B, _ = blocks.shape
    F = x.shape[-1]
    f_tile = min(f_tile, F)
    assert F % f_tile == 0, (F, f_tile)
    xb = x.reshape(-1, B, F)
    grid = (nbr, F // f_tile, K)
    in_specs = [
        pl.BlockSpec((None, None, B, B), lambda i, j, k, idx: (i, k, 0, 0)),
        pl.BlockSpec((None, B, f_tile), lambda i, j, k, idx: (idx[i, k], 0, j)),
    ]
    operands = [col_idx, blocks, xb]
    kernel = _kernel
    if y_in is not None:
        in_specs.append(
            pl.BlockSpec((None, B, f_tile), lambda i, j, k, idx: (i, 0, j)))
        operands.append(y_in.reshape(nbr, B, F))
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
        out_shape=jax.ShapeDtypeStruct((nbr, B, F), x.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(*operands)
    return out.reshape(nbr * B, F)
