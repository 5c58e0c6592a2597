"""CSR (row-pointer gather+reduce) aggregation kernel — the registry's
"one-file kernel" validation: everything a kernel needs (matvec, cost model,
format builder binding) lives here as a single ``register()`` call; the
decomposition, both selector modes, dispatch, and the benchmarks pick it up
with no edits elsewhere.

Paper mapping (§2.1/§3.2): CSR is the vertex-parallel format — one worker
per destination row walks ``indices[indptr[i]:indptr[i+1]]``.  On the TPU
the walk is done per row tile: a tile of R destination rows owns the one
contiguous edge range ``[indptr[r0], indptr[r0+R])`` (edges are sorted by
destination), and the Pallas reduce streams that range in chunks of T
gathered messages, adding ``onehot(rows - r0) @ chunk`` into the tile's
(R, F) float32 accumulator on the MXU.  The row pointer is expanded back
to per-edge row ids in O(E + n) (one scatter of a mark at each row end,
then a prefix sum over the static-shape edge range); XLA gathers the
source features.  Gather-efficiency class (like ELL) rather than scatter
class (like COO), with zero padding — CSR stores exactly nnz entries where
ELL pads every row to max degree.

The backward pass is the same reduce over the transpose, built on the
device: one sort of the edges by source column, carrying each edge's row
and value, gives the transposed walk; its tile bounds come from a binary
search of the sorted columns at the tile starts, and the gathered output
gradient streams through the kernel as the forward's messages do.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import formats
from repro.kernels import ops
from repro.kernels.registry import DIAG, OFFDIAG, REGISTRY, KernelSpec


def _expand(ptr: jax.Array, length: int) -> jax.Array:
    """Segment id of each of ``length`` positions under the non-decreasing
    pointer ``ptr`` (``ptr[0] == 0``): each segment end ``ptr[i+1]`` marks
    the position where segment ``i + 1`` starts, and the prefix sum of the
    marks counts the ends at or before each position.  Empty segments
    stack their marks on one position; ends at ``length`` drop, so
    positions past ``ptr[-1]`` get ``len(ptr) - 1``."""
    marks = jnp.zeros((length,), jnp.int32).at[ptr[1:]].add(
        1, mode="drop", indices_are_sorted=True)
    return jnp.cumsum(marks, dtype=jnp.int32)


def _edge_rows(csr: formats.CSR) -> jax.Array:
    """Expand the row pointer back to per-edge destination ids (sorted,
    static shape).  Equals ``searchsorted(indptr, arange(nnz),
    side="right") - 1`` for any non-decreasing ``indptr`` with
    ``indptr[0] == 0``: budget-padded entries (``indptr[-1]`` raised to
    the budget) land in the last row's segment, and edges past
    ``indptr[-1]`` get ``n_rows``, which the reduce drops."""
    with jax.named_scope("expand"):
        return _expand(csr.indptr, csr.indices.shape[0])


# ---------------------------------------------------------------------------
# Sorted row-tile reduce (Pallas)
# ---------------------------------------------------------------------------

ROW_TILE = 128          # R: one MXU pass of one-hot rows per tile


def _chunk(f: int, itemsize: int) -> int:
    """T: edges per streamed chunk, at most 1 MiB of lane-padded messages
    and at most 1024 edges (on one v5e, 512 and 2048 ran slower at widths
    39 to 256), at least 256."""
    lanes = -(-f // ops.LANE) * ops.LANE
    t = (1 << 20) // (lanes * itemsize)
    return int(min(1024, max(256, t // 256 * 256)))


def _splits(dtype) -> int:
    """bfloat16 pieces that carry a message exactly: the one-hot is exact
    in bfloat16, so three pieces give float32 messages exact products and
    a float32 sum; a bfloat16 message is one piece."""
    return 1 if jnp.dtype(dtype) == jnp.bfloat16 else 3


def _work_items(tile_ptr: jax.Array, n_chunks: int, chunk: int):
    """(tile, chunk) work items in tile order, built on the device from
    the tiles' edge bounds ``tile_ptr`` (n_tiles + 1): one item per chunk
    that a tile's edge range touches, one for an empty tile (it zeroes its
    output).  Consecutive tiles share at most their boundary chunk, so
    there are at most ``n_chunks + n_tiles - 1`` items; the static grid
    has ``n_chunks + n_tiles`` and repeats the last item, flagged by the
    count, so its blocks are not fetched again."""
    n_tiles = tile_ptr.shape[0] - 1
    grid = n_chunks + n_tiles
    lo, hi = tile_ptr[:-1], tile_ptr[1:]
    first = jnp.minimum(lo // chunk, n_chunks - 1)
    last = jnp.where(hi > lo, (hi - 1) // chunk, first)
    ends = jnp.cumsum(last - first + 1, dtype=jnp.int32)
    n_items = ends[-1]
    tile = jnp.minimum(_expand(jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), ends]), grid), n_tiles - 1)
    item = jnp.arange(grid, dtype=jnp.int32)
    chunk_of = first[tile] + item - (ends - (last - first + 1))[tile]
    chunk_of = jnp.where(item < n_items, chunk_of, chunk_of[n_items - 1])
    return tile, chunk_of, n_items[None]


def _reduce_kernel(tile_ref, chunk_ref, count_ref, rows_ref, m_ref, o_ref, *,
                   n_rows: int, n_edges: int, chunk: int, splits: int):
    i = pl.program_id(0)
    tile = tile_ref[i]

    @pl.when((i == 0) | (tile != tile_ref[jnp.maximum(i - 1, 0)]))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    def accumulate(m):
        r = o_ref.shape[0]
        rows = rows_ref[...]                                  # (1, T)
        local = jnp.where(rows < n_rows, rows - tile * r, -1)
        onehot = (jax.lax.broadcasted_iota(jnp.int32, (r, chunk), 0)
                  == local).astype(jnp.bfloat16)
        acc = o_ref[...]
        for _ in range(splits):
            piece = m.astype(jnp.bfloat16)
            acc += jnp.dot(onehot, piece, preferred_element_type=jnp.float32)
            m = m - piece.astype(m.dtype)
        o_ref[...] = acc

    valid = i < count_ref[0]
    if n_edges % chunk:
        # the last chunk's block runs past the edges: zero what lies there
        tail = chunk_ref[i] == n_edges // chunk

        @pl.when(valid & tail)
        def _tail():
            e = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
            m = m_ref[...]
            accumulate(jnp.where(e < n_edges % chunk, m, jnp.zeros_like(m)))

        valid = valid & jnp.logical_not(tail)

    @pl.when(valid)
    def _body():
        accumulate(m_ref[...])


@functools.partial(jax.jit, static_argnames=("n_rows", "interpret"))
def sorted_reduce_pallas(msgs: jax.Array, rows: jax.Array, *, n_rows: int,
                         interpret: bool) -> jax.Array:
    """``out[r] = sum of msgs[e] over rows[e] == r`` for ``rows`` sorted
    (non-decreasing), as float32 ``(n_rows, F)``; rows outside
    ``[0, n_rows)`` drop.  ``msgs`` is ``(E, F)`` with ``E > 0``."""
    n_edges, f = msgs.shape
    r = ROW_TILE if n_rows >= ROW_TILE else -(-n_rows // 8) * 8
    chunk = _chunk(f, msgs.dtype.itemsize)
    if n_edges < chunk:                       # one whole-array chunk
        chunk = -(-n_edges // 128) * 128
        msgs = jnp.pad(msgs, ((0, chunk - n_edges), (0, 0)))
        rows = jnp.pad(rows, (0, chunk - n_edges), constant_values=n_rows)
    n_chunks = -(-n_edges // chunk)
    n_tiles = -(-n_rows // r)
    starts = jnp.minimum(jnp.arange(n_tiles + 1, dtype=jnp.int32) * r,
                         n_rows)
    tile_ptr = jnp.searchsorted(rows, starts, side="left",
                                method="scan_unrolled").astype(jnp.int32)
    tile, chunk_of, count = _work_items(tile_ptr, n_chunks, chunk)
    kernel = functools.partial(_reduce_kernel, n_rows=n_rows,
                               n_edges=msgs.shape[0], chunk=chunk,
                               splits=_splits(msgs.dtype))
    gs = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n_chunks + n_tiles,),
        in_specs=[
            pl.BlockSpec((1, chunk), lambda i, t, c, n: (0, c[i])),
            pl.BlockSpec((chunk, f), lambda i, t, c, n: (c[i], 0)),
        ],
        out_specs=pl.BlockSpec((r, f), lambda i, t, c, n: (t[i], 0)),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=gs,
        out_shape=jax.ShapeDtypeStruct((n_rows, f), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="csr_sorted_reduce",
    )(tile, chunk_of, count, rows.reshape(1, -1), msgs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _sorted_reduce(msgs: jax.Array, rows: jax.Array,
                   n_rows: int) -> jax.Array:
    """Sorted segment sum of ``msgs`` by ``rows`` into ``n_rows`` float32
    rows; its VJP is the sorted gather ``g[rows]`` (zero where a row
    drops)."""
    if msgs.shape[0] == 0:
        return jnp.zeros((n_rows, msgs.shape[1]), jnp.float32)
    return sorted_reduce_pallas(msgs, rows, n_rows=n_rows,
                                interpret=ops._interpret())


def _sorted_reduce_fwd(msgs, rows, n_rows):
    # an empty array carries the messages' dtype to the backward pass
    return _sorted_reduce(msgs, rows, n_rows), (rows, msgs[:0])


def _sorted_reduce_bwd(n_rows, res, g):
    rows, like = res
    d = jnp.take(g, rows, axis=0, mode="fill", fill_value=0)
    return d.astype(like.dtype), None


_sorted_reduce.defvjp(_sorted_reduce_fwd, _sorted_reduce_bwd)


# ---------------------------------------------------------------------------
# Y = A_csr @ x, and its transpose as the same reduce
# ---------------------------------------------------------------------------

@jax.custom_vjp
def csr_matvec(csr: formats.CSR, x: jax.Array) -> jax.Array:
    """Y = A_csr @ x: row-pointer expansion, the XLA gather-and-scale of
    the messages, then the sorted row-tile reduce.  Its VJP,
    ``dx = A^T dY``, is the same reduce over the column-sorted edges."""
    return _csr_fwd(csr, x)[0]


def _csr_fwd(csr, x):
    rows = _edge_rows(csr)
    msgs = x[csr.indices] * csr.vals[:, None]
    with jax.named_scope("reduce"):
        y = _sorted_reduce(msgs, rows, csr.n_rows)
    # a zero-width array carries x's rows and dtype to the backward pass
    return y.astype(x.dtype), (csr.indices, csr.vals, rows, x[:, :0])


def _csr_bwd(res, g):
    indices, vals, rows, x = res
    with jax.named_scope("reduce_t"):
        # rows and values ride the sort as payloads: gathering them by a
        # sorted permutation costs a random scalar gather of edge length
        # each; a column's order is free, as the reduce sums it in one pass
        cols, src, v = jax.lax.sort((indices, rows, vals), num_keys=1,
                                    is_stable=False)
        msgs = jnp.take(g, src, axis=0, mode="fill",
                        fill_value=0) * v[:, None]
        dx = _sorted_reduce(msgs, cols, x.shape[0])
    return None, dx.astype(x.dtype)


csr_matvec.defvjp(_csr_fwd, _csr_bwd)


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
    pallas=True,
    doc="row-pointer gather+reduce (vertex-parallel, exact-nnz storage)",
))


# ---------------------------------------------------------------------------
# Fused epilogue path: Y = A_csr @ (x @ w) without materializing H = x @ w
# ---------------------------------------------------------------------------

def csr_transform_matvec(csr: formats.CSR, x: jax.Array,
                         w: jax.Array) -> jax.Array:
    """Per-edge gathered transform: each edge transforms only its gathered
    source row, ``(E, Fi) @ (Fi, Fo)``, then the sorted row-tile reduce —
    the (n, Fo)-wide ``H`` never round-trips HBM.  Wins exactly on sparse
    tiers (E below ~n/n_sub, where the per-edge recompute undercuts the
    unfused candidates' share of the shared transform).  Differentiable
    for ``x`` and ``w`` through the reduce's VJP, a sorted gather."""
    h_e = (x[csr.indices] @ w) * csr.vals[:, None]
    with jax.named_scope("reduce"):
        y = _sorted_reduce(h_e, _edge_rows(csr), csr.n_rows)
    return y.astype(x.dtype)


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
    pallas=True,
    doc="fused CSR A @ (X W): per-edge gathered transform, no (n, F) "
        "intermediate; trades per-edge recompute for the H round-trip",
))
