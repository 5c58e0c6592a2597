"""Pallas TPU kernel: blockwise-softmax (Flash) causal attention.

The roofline baseline (EXPERIMENTS.md §Roofline) shows every *_4k/32k
attention cell is memory- or collective-bound because XLA's unfused
attention writes the (B, H, S, S) score tensor to HBM.  This kernel keeps
the score block in VMEM: HBM traffic drops from O(S^2) to O(S * d) streams
of Q, K, V, O — the classic FlashAttention result (arXiv:2205.14135),
retiled for the TPU MXU (block sizes multiples of 128 lanes).

Grid: (B * Hq, Sq / blk_q, Skv / blk_k) with the KV dim innermost
("arbitrary"); running (max, sum, acc) live in VMEM scratch across KV steps.
Causal masking is handled per-block: fully-masked blocks still execute (no
data-dependent control flow) but contribute zero; a production mosaic build
would skip them via the grid order — we note the 2x causal win in the
analytic model instead.

GQA: the index map sends q-head h to kv-head h // (Hq // Hkv).

Supports forward (serving / prefill).  For training, the wrapper installs a
custom VJP whose backward recomputes attention blockwise through the
pure-jnp path (flash-style recompute; see ops note in DESIGN.md).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, blk_q: int, blk_k: int):
    kv_i = pl.program_id(2)

    @pl.when(kv_i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...].astype(jnp.float32)          # (blk_q, d)
    k = k_ref[...].astype(jnp.float32)          # (blk_k, d)
    v = v_ref[...].astype(jnp.float32)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale

    if causal:
        q_pos = pl.program_id(1) * blk_q + \
            jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
        k_pos = kv_i * blk_k + \
            jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)

    m_prev = m_ref[...]                          # (blk_q, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + \
        jnp.dot(p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(kv_i == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = (acc_ref[...] /
                      jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "blk_q", "blk_k",
                                             "interpret", "scale"))
def flash_attention(q, k, v, *, causal: bool = True, blk_q: int = 128,
                    blk_k: int = 128, interpret: bool,
                    scale: float | None = None):
    """q: (B, Hq, Sq, d); k: (B, Hkv, Skv, d); v: (B, Hkv, Skv, dv)
    -> (B, Hq, Sq, dv).  Sq % blk_q == 0 and Skv % blk_k == 0 (pad
    upstream); dv may differ from d (MLA)."""
    B, Hq, Sq, d = q.shape
    _, Hkv, Skv, _ = k.shape
    dv = v.shape[-1]
    g = Hq // Hkv
    blk_q = min(blk_q, Sq)
    blk_k = min(blk_k, Skv)
    assert Sq % blk_q == 0 and Skv % blk_k == 0
    scale = (d ** -0.5) if scale is None else scale

    qf = q.reshape(B * Hq, Sq, d)
    kf = k.reshape(B * Hkv, Skv, d)
    vf = v.reshape(B * Hkv, Skv, dv)

    def kv_index(i, qi, ki):
        # flat q index i = b * Hq + h  ->  kv index b * Hkv + h // g
        b = i // Hq
        h = i % Hq
        return (b * Hkv + h // g, ki, 0)

    grid = (B * Hq, Sq // blk_q, Skv // blk_k)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal,
                          blk_q=blk_q, blk_k=blk_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, blk_q, d), lambda i, qi, ki: (i, qi, 0)),
            pl.BlockSpec((None, blk_k, d), kv_index),
            pl.BlockSpec((None, blk_k, dv), kv_index),
        ],
        out_specs=pl.BlockSpec((None, blk_q, dv), lambda i, qi, ki: (i, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hq, Sq, dv), q.dtype),
        scratch_shapes=[pltpu.VMEM((blk_q, 1), jnp.float32),
                        pltpu.VMEM((blk_q, 1), jnp.float32),
                        pltpu.VMEM((blk_q, dv), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(qf, kf, vf)
    return out.reshape(B, Hq, Sq, dv)


# ---------------------------------------------------------------------------
# trainable wrapper: flash forward + flash-style recompute backward
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _trainable(causal: bool, scale: float | None):
    from repro.kernels import ref

    @jax.custom_vjp
    def f(q, k, v):
        interp = jax.default_backend() != "tpu"
        return flash_attention(q, k, v, causal=causal, interpret=interp,
                               scale=scale)

    def fwd(q, k, v):
        return f(q, k, v), (q, k, v)

    def bwd(res, do):
        # flash-style recompute: rerun attention under vjp of the oracle
        # (no saved S^2 tensors cross fwd->bwd; the recompute itself is the
        # Pallas bwd kernel on TPU — here the oracle stands in, DESIGN.md)
        q, k, v = res
        _, vjp = jax.vjp(
            lambda q, k, v: ref.mha(q, k, v, causal=causal, scale=scale),
            q, k, v)
        return vjp(do)

    f.defvjp(fwd, bwd)
    return f


def flash_attention_trainable(q, k, v, *, causal: bool = True,
                              scale: float | None = None):
    """Differentiable flash attention: Pallas forward, recompute backward."""
    return _trainable(causal, scale)(q, k, v)


def flash_hbm_bytes(B, Hq, Hkv, Sq, Skv, d, bytes_el=2, blk_q=512) -> int:
    """Analytic HBM traffic of the kernel: Q and O streamed once; K and V
    streamed once per q-block row (the KV loop rereads them).  blk_q=512
    keeps the VMEM working set ~1 MiB while cutting KV rereads 4x vs the
    128 default (a tuning noted in EXPERIMENTS.md §Perf)."""
    q_o = 2 * B * Hq * Sq * d * bytes_el
    n_qblk = max(Sq // blk_q, 1)
    kv = 2 * B * Hkv * Skv * d * bytes_el * n_qblk
    return q_o + kv


def flash_flops(B, Hq, Sq, Skv, d, causal=True) -> float:
    """2 matmuls of S_q x S_kv x d per head; causal halves the live blocks."""
    f = 2.0 * 2.0 * B * Hq * Sq * Skv * d
    return f / 2 if causal else f
