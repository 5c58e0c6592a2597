"""Sparse/dense graph storage formats used by AdaptGear's subgraph kernels.

The paper (AdaptGear, CF'23 §2.1/§3.2) uses Dense / CSR / COO formats and a
block-diagonal dense layout for intra-community subgraphs.  On TPU we keep the
same taxonomy and add two block-structured variants that map onto the MXU and
scalar-prefetch DMA:

  COO       -- edge list (edge-parallel; TPU analogue = segment_sum)
  CSR       -- row-compressed (vertex-parallel; TPU analogue = gather+reduce)
  ELL       -- per-row padded neighbor lists (regular gather, XLA-friendly)
  BlockDiag -- dense (B,B) diagonal blocks (intra-community; Pallas MXU kernel)
  BlockELL  -- blocked-ELL: CSR over (B,B) blocks, padded to K blocks per block
               row (inter-community; Pallas scalar-prefetch kernel)

All containers are registered pytrees so they can cross jit boundaries.
Conversion happens on host in numpy during preprocessing (paper §3.3: one
pass over the edges).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any

import jax
import numpy as np

Array = Any



def _np(arr) -> np.ndarray:
    """Host view of a (possibly device) array.  The numpy short-circuit
    matters: converters run per batch on the mini-batch hot path, where
    payload leaves are host numpy until the jit boundary, and
    jax.device_get's tree dispatch costs more than the work itself."""
    if isinstance(arr, np.ndarray):
        return arr
    return np.asarray(jax.device_get(arr))


def _register(cls, data_fields, meta_fields):
    jax.tree_util.register_dataclass(cls, data_fields, meta_fields)
    return cls


@dataclass(frozen=True)
class COO:
    """Edge-list format. rows = destination, cols = source (paper §2.1)."""
    n_rows: int = dataclasses.field(metadata=dict(static=True))
    n_cols: int = dataclasses.field(metadata=dict(static=True))
    rows: Array = None   # (E,) int32, destination vertex per edge
    cols: Array = None   # (E,) int32, source vertex per edge
    vals: Array = None   # (E,) float, edge weight (e.g. GCN normalization)

    @property
    def nnz(self) -> int:
        return int(self.rows.shape[0])

    @property
    def density(self) -> float:
        denom = max(self.n_rows * self.n_cols, 1)
        return self.nnz / denom


@dataclass(frozen=True)
class CSR:
    n_rows: int = dataclasses.field(metadata=dict(static=True))
    n_cols: int = dataclasses.field(metadata=dict(static=True))
    indptr: Array = None   # (n_rows+1,) int32
    indices: Array = None  # (E,) int32 column (source) indices
    vals: Array = None     # (E,) float

    @property
    def nnz(self) -> int:
        return int(self.indices.shape[0])


@dataclass(frozen=True)
class ELL:
    """Per-row padded neighbor lists.  indices[i, k] is the k-th source
    neighbor of row i (0 where padded, masked by ``mask``)."""
    n_rows: int = dataclasses.field(metadata=dict(static=True))
    n_cols: int = dataclasses.field(metadata=dict(static=True))
    max_deg: int = dataclasses.field(metadata=dict(static=True))
    indices: Array = None  # (n_rows, max_deg) int32
    vals: Array = None     # (n_rows, max_deg) float, 0 where padded
    mask: Array = None     # (n_rows, max_deg) bool

    @property
    def nnz(self) -> int:
        return int(_np(self.mask).sum())


@dataclass(frozen=True)
class BlockDiag:
    """Dense diagonal blocks: the intra-community subgraph after community
    reordering (paper Fig. 3a / §3.2 'Dense-based kernel')."""
    n: int = dataclasses.field(metadata=dict(static=True))            # padded node count
    block_size: int = dataclasses.field(metadata=dict(static=True))   # community size B
    blocks: Array = None   # (n // B, B, B) float dense adjacency blocks

    @property
    def n_blocks(self) -> int:
        return self.n // self.block_size

    @property
    def nnz(self) -> int:
        return int((_np(self.blocks) != 0).sum())

    @property
    def density(self) -> float:
        return self.nnz / max(self.blocks.size, 1)


@dataclass(frozen=True)
class BlockELL:
    """CSR-of-blocks padded to K non-empty (B,B) blocks per block-row.

    ``col_idx[i, k]`` names the block column of the k-th stored block in block
    row i; padding entries point at block column 0 with an all-zero block so
    the accumulation stays correct without a mask (TPU-friendly: no
    data-dependent control flow inside the kernel)."""
    n_rows: int = dataclasses.field(metadata=dict(static=True))
    n_cols: int = dataclasses.field(metadata=dict(static=True))
    block_size: int = dataclasses.field(metadata=dict(static=True))
    max_blocks: int = dataclasses.field(metadata=dict(static=True))   # K
    # per-bucket feature-tile cap chosen from the bucket's density stats at
    # build time (VMEM working-set budget); ops._f_tile clamps to a divisor
    f_tile_cap: int = dataclasses.field(default=512, metadata=dict(static=True))
    # True when K came from an edge *budget* rather than from this input's
    # max stored-block count: every array dim is then a function of
    # (budget, n_pad, B) alone — the contract the mini-batch no-retrace
    # path requires (sampling.plan_cache admits only budgeted payloads)
    budgeted: bool = dataclasses.field(default=False,
                                       metadata=dict(static=True))
    blocks: Array = None    # (n_brow, K, B, B) float
    col_idx: Array = None   # (n_brow, K) int32 block-column ids
    n_valid: Array = None   # (n_brow,) int32 number of real blocks per row

    @property
    def n_brow(self) -> int:
        return self.n_rows // self.block_size

    @property
    def nnz(self) -> int:
        return int((_np(self.blocks) != 0).sum())


for _cls, _data, _meta in [
    (COO, ("rows", "cols", "vals"), ("n_rows", "n_cols")),
    (CSR, ("indptr", "indices", "vals"), ("n_rows", "n_cols")),
    (ELL, ("indices", "vals", "mask"), ("n_rows", "n_cols", "max_deg")),
    (BlockDiag, ("blocks",), ("n", "block_size")),
    (BlockELL, ("blocks", "col_idx", "n_valid"),
     ("n_rows", "n_cols", "block_size", "max_blocks", "f_tile_cap",
      "budgeted")),
]:
    _register(_cls, list(_data), list(_meta))


# ---------------------------------------------------------------------------
# Host-side (numpy) constructors.  Preprocessing is a single pass over the
# edge list, matching the paper's §3.3 decomposition procedure.
# ---------------------------------------------------------------------------

def coo_from_edges(n_rows: int, n_cols: int, rows: np.ndarray, cols: np.ndarray,
                   vals: np.ndarray | None = None) -> COO:
    rows = np.asarray(rows, np.int32)
    cols = np.asarray(cols, np.int32)
    if vals is None:
        vals = np.ones(rows.shape[0], np.float32)
    # Sort by destination row: makes segment_sum use sorted (cheap) mode and
    # makes CSR conversion a cumsum.  Skipped when the caller already sorted
    # (the decompose skeleton row-sorts each tier once, so every per-batch
    # materialization takes the O(E) check instead of the O(E log E) sort).
    if rows.size and np.any(rows[1:] < rows[:-1]):
        order = np.argsort(rows, kind="stable")
        rows, cols = rows[order], cols[order]
        vals = np.asarray(vals, np.float32)[order]
    return COO(n_rows, n_cols, rows, cols, np.asarray(vals, np.float32))


def coo_to_csr(coo: COO) -> CSR:
    rows = _np(coo.rows)
    counts = np.bincount(rows, minlength=coo.n_rows)
    indptr = np.zeros(coo.n_rows + 1, np.int32)
    np.cumsum(counts, out=indptr[1:])
    return CSR(coo.n_rows, coo.n_cols, indptr, coo.cols, coo.vals)


def coo_to_ell(coo: COO, max_deg: int | None = None) -> ELL:
    rows = _np(coo.rows)
    cols = _np(coo.cols)
    vals = _np(coo.vals)
    counts = np.bincount(rows, minlength=coo.n_rows)
    K = int(counts.max()) if counts.size and max_deg is None else int(max_deg or 1)
    K = max(K, 1)
    idx = np.zeros((coo.n_rows, K), np.int32)
    v = np.zeros((coo.n_rows, K), np.float32)
    m = np.zeros((coo.n_rows, K), bool)
    slot = np.zeros(coo.n_rows, np.int32)
    for r, c, w in zip(rows, cols, vals):
        s = slot[r]
        if s < K:
            idx[r, s] = c
            v[r, s] = w
            m[r, s] = True
            slot[r] = s + 1
    return ELL(coo.n_rows, coo.n_cols, K, idx, v, m)


def coo_to_blockdiag(coo: COO, block_size: int) -> BlockDiag:
    """Densify assuming every edge lies on the diagonal blocks (caller must
    have already filtered to the intra-community subgraph)."""
    B = block_size
    n_pad = ((coo.n_rows + B - 1) // B) * B
    nb = n_pad // B
    rows = _np(coo.rows)
    cols = _np(coo.cols)
    vals = _np(coo.vals)
    blocks = np.zeros((nb, B, B), np.float32)
    b = rows // B
    assert np.all(b == cols // B), "coo_to_blockdiag: edge off the block diagonal"
    # add, not assign: a repeated edge (a graph's own self-loop plus the
    # one GCN adds) counts as often as it occurs, as in every other format
    np.add.at(blocks, (b, rows % B, cols % B), vals)
    return BlockDiag(n_pad, B, blocks)


def coo_to_bell(coo: COO, block_size: int, n_cols_pad: int | None = None,
                f_tile_cap: int = 512) -> BlockELL:
    """Blocked-ELL over (B,B) tiles; K = max non-empty blocks per block row."""
    B = block_size
    n_rpad = ((coo.n_rows + B - 1) // B) * B
    n_cpad = n_cols_pad or ((coo.n_cols + B - 1) // B) * B
    nbr = n_rpad // B
    rows = _np(coo.rows)
    cols = _np(coo.cols)
    vals = _np(coo.vals)
    brow, bcol = rows // B, cols // B
    # group edges per (brow, bcol)
    blk_of: dict[tuple[int, int], int] = {}
    per_row: list[list[int]] = [[] for _ in range(nbr)]
    for r in range(len(rows)):
        key = (int(brow[r]), int(bcol[r]))
        if key not in blk_of:
            blk_of[key] = len(per_row[key[0]])
            per_row[key[0]].append(key[1])
    K = max((len(p) for p in per_row), default=1)
    K = max(K, 1)
    blocks = np.zeros((nbr, K, B, B), np.float32)
    col_idx = np.zeros((nbr, K), np.int32)
    n_valid = np.zeros((nbr,), np.int32)
    for (i, j), slot in blk_of.items():
        col_idx[i, slot] = j
    for i, p in enumerate(per_row):
        n_valid[i] = len(p)
    for r in range(len(rows)):
        i, j = int(brow[r]), int(bcol[r])
        blocks[i, blk_of[(i, j)], rows[r] % B, cols[r] % B] += vals[r]
    return BlockELL(n_rpad, n_cpad, B, K, f_tile_cap,
                    blocks=blocks, col_idx=col_idx, n_valid=n_valid)


# ---------------------------------------------------------------------------
# Budget-padded blocked-ELL (the mini-batch fixed-shape variant)
# ---------------------------------------------------------------------------

def bell_budget_k(edge_budget: int, n_pad: int, block_size: int,
                  slack: float = 2.0) -> int:
    """Stored-block cap K for the budget-padded blocked-ELL.

    Derived from the sampler's *edge budget* alone — never from a batch's
    actual edges — so every batch's payload shares one (n_brow, K, B, B)
    shape.  K covers ``slack``x the per-block-row average stored-block
    count under dense packing (each stored block absorbing ~B edges); the
    block-column count bounds it above (a row cannot store more distinct
    blocks than exist — at that bound the cap is vacuous and nothing ever
    spills)."""
    nbr = max(n_pad // block_size, 1)
    k = -(-int(slack * edge_budget) // max(nbr * block_size, 1))
    return int(max(1, min(k, nbr)))


def coo_to_bell_capped(coo: COO, block_size: int, k_max: int,
                       n_cols_pad: int | None = None,
                       f_tile_cap: int = 512, build_blocks: bool = True
                       ) -> tuple[BlockELL | None, COO, COO]:
    """Blocked-ELL with exactly ``k_max`` stored-block slots per block row.

    Rows needing more keep their *densest* ``k_max`` blocks (ties broken
    toward the lower block column); the remaining edges come back as a
    row-sorted *spill* COO, and the stored edges as a third COO (what the
    transpose pass caps again — see the registry's capped builder).  Slots
    past a row's real block count stay all-zero pointing at block column 0,
    so the kernel needs no mask.  Returns ``(bell, spill, stored)`` with
    ``bell.budgeted=True``: all three shapes are functions of
    ``(k_max, n_pad, B)`` and the edge count only.

    ``build_blocks=False`` skips the (n_brow, K, B, B) scatter and returns
    ``bell=None`` — for callers that only need the stored/spill edge split
    (the capped builder's first partition pass discards its bell and
    rebuilds from the transpose-capped survivors)."""
    B = block_size
    n_rpad = ((coo.n_rows + B - 1) // B) * B
    n_cpad = n_cols_pad or ((coo.n_cols + B - 1) // B) * B
    nbr = n_rpad // B
    nbc = n_cpad // B
    K = int(max(1, min(k_max, nbc)))
    rows = _np(coo.rows)
    cols = _np(coo.cols)
    vals = _np(coo.vals)
    if build_blocks:
        blocks = np.zeros((nbr, K, B, B), np.float32)
        col_idx = np.zeros((nbr, K), np.int32)
        n_valid = np.zeros((nbr,), np.int32)

    if len(rows):
        brow = (rows // B).astype(np.int64)
        bcol = (cols // B).astype(np.int64)
        key = brow * nbc + bcol
        uniq, inv, counts = np.unique(key, return_inverse=True,
                                      return_counts=True)
        ubrow, ubcol = uniq // nbc, uniq % nbc
        # rank each block-row's blocks densest-first; the slot of a block is
        # its rank within its row (vectorized segmented rank: after the
        # lexsort rows are contiguous, so rank = index - first-in-group)
        order = np.lexsort((ubcol, -counts, ubrow))
        sorted_brow = ubrow[order]
        rank_sorted = (np.arange(len(uniq))
                       - np.searchsorted(sorted_brow, sorted_brow))
        slot = np.empty(len(uniq), np.int64)
        slot[order] = rank_sorted

        edge_slot = slot[inv]
        stored_m = edge_slot < K
        if build_blocks:
            sb = np.flatnonzero(slot < K)
            col_idx[ubrow[sb], slot[sb]] = ubcol[sb]
            n_valid[:] = np.minimum(np.bincount(ubrow, minlength=nbr), K)
            np.add.at(blocks, (brow[stored_m], edge_slot[stored_m],
                               rows[stored_m] % B, cols[stored_m] % B),
                      vals[stored_m])
    else:
        stored_m = np.zeros(0, bool)

    bell = (BlockELL(n_rpad, n_cpad, B, K, f_tile_cap, budgeted=True,
                     blocks=blocks, col_idx=col_idx, n_valid=n_valid)
            if build_blocks else None)
    spill = coo_from_edges(n_rpad, n_cpad, rows[~stored_m], cols[~stored_m],
                           vals[~stored_m])
    stored = coo_from_edges(n_rpad, n_cpad, rows[stored_m], cols[stored_m],
                            vals[stored_m])
    return bell, spill, stored


def format_stats(fmt) -> dict:
    """Size/density statistics the selector's cost model consumes."""
    if isinstance(fmt, COO):
        return dict(kind="coo", nnz=fmt.nnz, n=fmt.n_rows, density=fmt.density)
    if isinstance(fmt, CSR):
        return dict(kind="csr", nnz=fmt.nnz, n=fmt.n_rows)
    if isinstance(fmt, ELL):
        return dict(kind="ell", n=fmt.n_rows, max_deg=fmt.max_deg,
                    padded=fmt.n_rows * fmt.max_deg)
    if isinstance(fmt, BlockDiag):
        return dict(kind="block_diag", n_blocks=fmt.n_blocks,
                    block_size=fmt.block_size, density=fmt.density)
    if isinstance(fmt, BlockELL):
        return dict(kind="bell", n_brow=fmt.n_brow, max_blocks=fmt.max_blocks,
                    block_size=fmt.block_size)
    raise TypeError(type(fmt))
