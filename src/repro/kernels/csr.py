"""CSR (row-pointer gather+reduce) aggregation kernel — the registry's
"one-file kernel" validation: everything a kernel needs (matvec, cost model,
format builder binding) lives here as a single ``register()`` call; the
decomposition, both selector modes, dispatch, and the benchmarks pick it up
with no edits elsewhere.

Paper mapping (§2.1/§3.2): CSR is the vertex-parallel format — one worker
per destination row walks ``indices[indptr[i]:indptr[i+1]]``.  The TPU/XLA
analogue expands the row pointer back to per-edge row ids in O(E + n)
(one scatter of a mark at each row end, then a prefix sum over the
static-shape edge range), gathers source features, and reduces with a
sorted segment-sum: gather-efficiency class (like ELL) rather than
scatter class (like COO), but with zero padding — CSR stores exactly nnz
entries where ELL pads every row to max degree.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import formats
from repro.kernels.registry import DIAG, OFFDIAG, REGISTRY, KernelSpec


def _edge_rows(csr: formats.CSR) -> jax.Array:
    """Expand the row pointer back to per-edge destination ids (sorted,
    static shape).  Each row end ``indptr[i+1]`` marks the edge where row
    ``i + 1`` starts; the prefix sum of the marks counts the row ends at
    or before each edge, which is its row.  Empty rows stack their marks
    on one edge; ends at ``nnz`` drop.  Equals ``searchsorted(indptr,
    arange(nnz), side="right") - 1`` for any non-decreasing ``indptr``
    with ``indptr[0] == 0``: budget-padded entries (``indptr[-1]`` raised
    to the budget) land in the last row's segment, and edges past
    ``indptr[-1]`` get ``n_rows``, which the segment sums drop."""
    nnz = csr.indices.shape[0]
    with jax.named_scope("expand"):
        marks = jnp.zeros((nnz,), jnp.int32).at[csr.indptr[1:]].add(
            1, mode="drop", indices_are_sorted=True)
        return jnp.cumsum(marks, dtype=jnp.int32)


def csr_matvec(csr: formats.CSR, x: jax.Array) -> jax.Array:
    """Y = A_csr @ x via row-pointer expansion + sorted segment reduce.
    Natively differentiable (gather transposes to scatter-add)."""
    msgs = x[csr.indices] * csr.vals[:, None]
    return jax.ops.segment_sum(msgs, _edge_rows(csr),
                               num_segments=csr.n_rows,
                               indices_are_sorted=True).astype(x.dtype)


def _csr_cost(sub, feat_dim, dtype, hw) -> float:
    be = np.dtype(dtype).itemsize
    nnz = sub.stats["nnz"]
    flops = 2.0 * nnz * feat_dim
    # exact-nnz gather (no ELL padding) + row-pointer stream + output
    bytes_ = nnz * (feat_dim * be + 4) + sub.n_rows * (feat_dim * be + 4)
    return max(flops / hw.peak_flops,
               bytes_ / (hw.hbm_bw * hw.gather_eff)) + hw.launch_overhead_s


REGISTRY.register(KernelSpec(
    name="csr",
    kinds=frozenset({DIAG, OFFDIAG}),
    build=lambda coo, coo_t, B, stats: formats.coo_to_csr(coo),
    matvec=csr_matvec,
    cost=_csr_cost,
    doc="row-pointer gather+reduce (vertex-parallel, exact-nnz storage)",
))


# ---------------------------------------------------------------------------
# Fused epilogue path: Y = A_csr @ (x @ w) without materializing H = x @ w
# ---------------------------------------------------------------------------

def csr_transform_matvec(csr: formats.CSR, x: jax.Array,
                         w: jax.Array) -> jax.Array:
    """Per-edge gathered transform: each edge transforms only its gathered
    source row, ``(E, Fi) @ (Fi, Fo)``, then the sorted segment reduce — the
    (n, Fo)-wide ``H`` never round-trips HBM.  Wins exactly on sparse tiers
    (E below ~n/n_sub, where the per-edge recompute undercuts the unfused
    candidates' share of the shared transform).  Natively differentiable."""
    h_e = (x[csr.indices] @ w) * csr.vals[:, None]
    return jax.ops.segment_sum(h_e, _edge_rows(csr), num_segments=csr.n_rows,
                               indices_are_sorted=True).astype(x.dtype)


def _csr_fused_cost(sub, feat_dims, dtype, hw) -> float:
    fin, fout = feat_dims
    be = np.dtype(dtype).itemsize
    nnz = sub.stats["nnz"]
    # transform recompute per edge (a source row referenced k times is
    # transformed k times) + gather-class traffic on the narrow input side
    flops = 2.0 * nnz * (fin * fout + fout)
    bytes_ = (nnz * (fin * be + fout * be + 8)
              + sub.n_rows * (fout * be + 4))
    return max(flops / hw.peak_flops,
               bytes_ / (hw.hbm_bw * hw.gather_eff)) + hw.launch_overhead_s


REGISTRY.register(KernelSpec(
    name="csr_fused",
    kinds=frozenset({DIAG, OFFDIAG}),
    build=None,
    payload_of="csr",
    matvec=None,
    fused_matvec=csr_transform_matvec,
    cost=_csr_fused_cost,
    doc="fused CSR A @ (X W): per-edge gathered transform, no (n, F) "
        "intermediate; trades per-edge recompute for the H round-trip",
))
