"""Pure-jnp oracles for every Pallas kernel in this package.

These are the correctness references (tests assert_allclose kernels against
these across shape/dtype sweeps) and double as the portable fallback path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


# --- SpMM family (AdaptGear subgraph kernels) ------------------------------

def block_diag_spmm(blocks: jax.Array, x: jax.Array) -> jax.Array:
    """Y[b*B:(b+1)*B] = blocks[b] @ x[b*B:(b+1)*B].

    blocks: (nb, B, B); x: (nb*B, F)  ->  (nb*B, F)
    """
    nb, B, _ = blocks.shape
    xb = x.reshape(nb, B, -1)
    y = jnp.einsum("bij,bjf->bif", blocks, xb,
                   preferred_element_type=jnp.float32)
    return y.reshape(nb * B, -1).astype(x.dtype)


def bell_spmm(blocks: jax.Array, col_idx: jax.Array, x: jax.Array) -> jax.Array:
    """Blocked-ELL SpMM.

    blocks: (nbr, K, B, B), col_idx: (nbr, K) block-column ids,
    x: (n_cols_pad, F) -> (nbr*B, F).  Padding blocks are all-zero so their
    contribution vanishes regardless of col_idx.
    """
    nbr, K, B, _ = blocks.shape
    xb = x.reshape(-1, B, x.shape[-1])            # (nbc, B, F)
    gathered = xb[col_idx]                        # (nbr, K, B, F)
    y = jnp.einsum("rkij,rkjf->rif", blocks, gathered,
                   preferred_element_type=jnp.float32)
    return y.reshape(nbr * B, -1).astype(x.dtype)


def ell_spmm(indices: jax.Array, vals: jax.Array, x: jax.Array) -> jax.Array:
    """Row-padded gather SpMM: Y[i] = sum_k vals[i,k] * x[indices[i,k]].

    indices/vals: (n, K) (vals zero where padded); x: (n_cols, F)."""
    gathered = x[indices]                          # (n, K, F)
    return jnp.einsum("nk,nkf->nf", vals, gathered,
                      preferred_element_type=jnp.float32).astype(x.dtype)


def coo_spmm(rows: jax.Array, cols: jax.Array, vals: jax.Array,
             x: jax.Array, n_rows: int) -> jax.Array:
    """Edge-parallel scatter-add (the atomicAdd analogue)."""
    msgs = x[cols] * vals[:, None]
    return jax.ops.segment_sum(msgs, rows, num_segments=n_rows,
                               indices_are_sorted=True).astype(x.dtype)


def coo_spmm_dense_ref(rows, cols, vals, x, n_rows):
    """O(n^2) dense-materialized oracle (small shapes only)."""
    a = jnp.zeros((n_rows, x.shape[0]), jnp.float32)
    a = a.at[rows, cols].add(vals)
    return (a @ x.astype(jnp.float32)).astype(x.dtype)
