"""Pallas TPU kernel: selective SSM scan (Mamba) with VMEM-resident state.

XLA's associative_scan over the full sequence materializes the (B, T,
d_inner, d_state) hidden tensor in HBM O(log T) times — the §Roofline
baseline shows this makes jamba's train cell memory-bound by a wide margin.
The original CUDA kernel (Gu & Dao, arXiv:2312.00752 'hardware-aware scan')
keeps the recurrent state in SRAM; the TPU analogue keeps the (d_tile,
d_state) state in VMEM scratch across a sequential chunk grid:

  grid = (B, d_inner/d_tile, T/chunk)   -- chunk dim sequential
  per step: within-chunk associative scan over (chunk, d_tile, d_state)
            entirely in VMEM; only x/dt/B/C stream in and y streams out.

HBM traffic drops from O(T * d_inner * d_state * log T) to
O(T * (2 d_inner + 2 d_state * d_tiles) + T * d_inner) — the streaming
floor.  d_tile=512, chunk=128 keeps the working set ~6 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, y_ref, h_ref):
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[...].astype(jnp.float32)       # (C, dt_tile)
    dt = dt_ref[...].astype(jnp.float32)     # (C, dt_tile)
    Bc = b_ref[...].astype(jnp.float32)      # (C, ds)
    Cc = c_ref[...].astype(jnp.float32)      # (C, ds)
    A = a_ref[...].astype(jnp.float32)       # (dt_tile, ds)
    D = d_ref[...].astype(jnp.float32)       # (1, dt_tile)

    dA = jnp.exp(dt[:, :, None] * A[None])               # (C, d, ds)
    dBx = (dt * x)[:, :, None] * Bc[:, None, :]          # (C, d, ds)

    def combine(p, q):
        a1, b1 = p
        a2, b2 = q
        return a2 * a1, a2 * b1 + b2

    aa, bb = jax.lax.associative_scan(combine, (dA, dBx), axis=0)
    h0 = h_ref[...]                                      # (d, ds)
    hs = aa * h0[None] + bb                              # (C, d, ds)
    y = jnp.einsum("cds,cs->cd", hs, Cc) + x * D
    y_ref[...] = y.astype(y_ref.dtype)
    h_ref[...] = hs[-1]


@functools.partial(jax.jit, static_argnames=("chunk", "d_tile", "interpret"))
def mamba_scan(x, dt, Bc, Cc, A, D, *, chunk: int = 128, d_tile: int = 512,
               interpret: bool):
    """x, dt: (B, T, d_inner); Bc, Cc: (B, T, d_state);
    A: (d_inner, d_state); D: (d_inner,) -> y (B, T, d_inner).
    dt is post-softplus.  T % chunk == 0; d_inner % d_tile == 0."""
    B, T, di = x.shape
    ds = A.shape[-1]
    chunk = min(chunk, T)
    d_tile = min(d_tile, di)
    assert T % chunk == 0 and di % d_tile == 0
    grid = (B, di // d_tile, T // chunk)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, chunk, d_tile), lambda b, d, t: (b, t, d)),
            pl.BlockSpec((None, chunk, d_tile), lambda b, d, t: (b, t, d)),
            pl.BlockSpec((None, chunk, ds), lambda b, d, t: (b, t, 0)),
            pl.BlockSpec((None, chunk, ds), lambda b, d, t: (b, t, 0)),
            pl.BlockSpec((d_tile, ds), lambda b, d, t: (d, 0)),
            pl.BlockSpec((1, d_tile), lambda b, d, t: (0, d)),
        ],
        out_specs=pl.BlockSpec((None, chunk, d_tile), lambda b, d, t: (b, t, d)),
        out_shape=jax.ShapeDtypeStruct((B, T, di), x.dtype),
        scratch_shapes=[pltpu.VMEM((d_tile, ds), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(x, dt, Bc, Cc, A, D.reshape(1, di))
    return out


def mamba_scan_hbm_bytes(B, T, di, ds, d_tile: int = 512,
                         bytes_el: int = 4) -> int:
    """Streaming floor: x/dt/y once; B/C rereads per d-tile; A/D once."""
    xy = 3 * B * T * di * bytes_el
    bc = 2 * B * T * ds * (di // d_tile) * bytes_el
    return xy + bc + di * ds * bytes_el


def mamba_scan_flops(B, T, di, ds) -> float:
    """exp + 3 muls + add per (t, d, s) for the recurrence, plus the C
    contraction and D skip: ~8 flops per state element."""
    return 8.0 * B * T * di * ds


# ---------------------------------------------------------------------------
# trainable wrapper: Pallas forward + recompute backward (oracle vjp)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _trainable():
    from repro.kernels import ref

    @jax.custom_vjp
    def f(x, dt, Bc, Cc, A, D):
        interp = jax.default_backend() != "tpu"
        return mamba_scan(x, dt, Bc, Cc, A, D, interpret=interp)

    def fwd(x, dt, Bc, Cc, A, D):
        return f(x, dt, Bc, Cc, A, D), (x, dt, Bc, Cc, A, D)

    def bwd(res, dy):
        x, dt, Bc, Cc, A, D = res
        _, vjp = jax.vjp(
            lambda x, dt, Bc, Cc, A, D: ref.mamba_ssm(x, dt, A, Bc, Cc, D),
            x, dt, Bc, Cc, A, D)
        return vjp(dy)

    f.defvjp(fwd, bwd)
    return f


def mamba_scan_trainable(x, dt, Bc, Cc, A, D):
    """Differentiable selective scan: Pallas forward, recompute backward."""
    return _trainable()(x, dt, Bc, Cc, A, D)
