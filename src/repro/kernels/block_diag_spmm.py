"""Pallas TPU kernel: dense diagonal-block batched SpMM (intra-community).

Paper mapping (§3.2 'Dense-based kernel'): CUDA maps one CTA per community
adjacency block and runs a batched GEMM on Tensor Cores.  On TPU the analogue
is a pallas_call whose grid iterates (block, feature-tile); each step loads a
(B, B) adjacency block and the matching (B, Ft) feature tile into VMEM and
issues one MXU matmul.  B is padded to the 128-lane boundary by ops.py so the
MXU tiles are fully utilized.

VMEM working set per step: B*B + 2*B*Ft floats.  With B=128, Ft=512 that is
~0.6 MB -- far below the ~16 MB VMEM budget, leaving room for the pipelined
double buffering pallas inserts automatically.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, x_ref, o_ref):
    o_ref[...] = jnp.dot(
        a_ref[...], x_ref[...], preferred_element_type=jnp.float32
    ).astype(o_ref.dtype)


def _kernel_acc(a_ref, x_ref, y_ref, o_ref):
    y = jnp.dot(a_ref[...], x_ref[...], preferred_element_type=jnp.float32)
    o_ref[...] = (y_ref[...].astype(jnp.float32) + y).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("f_tile", "interpret"))
def block_diag_spmm(blocks: jax.Array, x: jax.Array,
                    y_in: jax.Array | None = None, *,
                    f_tile: int = 512, interpret: bool) -> jax.Array:
    """Y = blockdiag(blocks) @ x (+ y_in).

    blocks: (nb, B, B); x: (nb*B, F) with F % f_tile == 0 (ops.py pads);
    y_in: optional (nb*B, F) accumulator input (aggregate's threaded output
    buffer, saving the separate partial-sum pass).
    """
    nb, B, _ = blocks.shape
    n, F = x.shape
    assert n == nb * B, (n, nb, B)
    f_tile = min(f_tile, F)
    assert F % f_tile == 0, (F, f_tile)
    xb = x.reshape(nb, B, F)
    grid = (nb, F // f_tile)
    in_specs = [
        pl.BlockSpec((None, B, B), lambda i, j: (i, 0, 0)),
        pl.BlockSpec((None, B, f_tile), lambda i, j: (i, 0, j)),
    ]
    operands = [blocks, xb]
    kernel = _kernel
    if y_in is not None:
        in_specs.append(pl.BlockSpec((None, B, f_tile), lambda i, j: (i, 0, j)))
        operands.append(y_in.reshape(nb, B, F))
        kernel = _kernel_acc
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, B, f_tile), lambda i, j: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((nb, B, F), x.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
    )(*operands)
    return out.reshape(n, F)
