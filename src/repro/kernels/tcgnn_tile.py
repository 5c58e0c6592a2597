"""TC-GNN-style column-condensed MXU tiles — one-file registration
following kernels/csr.py and kernels/sell_cs.py.

TC-GNN (Wang et al., PAPERS.md) condenses the *non-zero columns* of each
sparse block row into a contiguous dense tile and runs it on tensor cores;
Balog et al. make the same bet for MXU-class hardware.  This is the
registry's mid-density tier: blocked-ELL pays (B, B) padding per stored
block (waste grows as blocks get sparser), while dense materializes the
whole row.  Column condensation pays only per *distinct source column* of
a block row — between those two regimes it stores, gathers, and multiplies
strictly less.

Format: per block row ``i`` the builder ranks the distinct source columns,
packs their edge values into a dense ``(B, C)`` tile (``tiles[i, r, s]`` is
the weight of edge ``(i*B + r, gather_idx[i, s])``) and records the column
ids in ``gather_idx``.  ``C`` is lane-aligned (the "8x128" tile contract:
``B`` on the sublane axis, ``C`` a multiple of 128 on the lane axis); slots
past a row's real column count stay all-zero pointing at column 0, so the
kernel needs no mask.  The device pass is then a *row-level* XLA gather
``x[gather_idx] -> (nbr, C, F)`` followed by a batched dense contraction
``tiles @ x_g`` that the Pallas kernel runs through the MXU — block-level
BlockSpec indirection (bell's trick) cannot express a per-column gather,
so the gather stays in XLA and the FLOPs stay on the MXU.

Under the mini-batch edge budget the payload is the budget-capped triple
``(tc, tc_t, spill)`` — C capped by :func:`tcgnn_budget_c` from the edge
budget alone, each block row keeping its densest columns and the overflow
riding the COO spill tier — the same fixed-pytree-shape contract as the
capped blocked-ELL (``MB_KERNELS``).  The transpose of the *stored* edges
is capped again and the forward payload rebuilt from the survivors, so
``tc_t`` is exactly the transpose of ``tc`` and the custom VJPs stay
correct while every spilled edge flows through the natively-differentiable
segment-sum path in both directions.
"""
from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import formats
from repro.kernels import ops
from repro.kernels.registry import (OFFDIAG, REGISTRY, KernelSpec,
                                    _bell_spill_cost, _bytes_el, _lane_pad)

LANE = ops.LANE
C_TILE_CAP = 512     # condensed-column tile per grid step (lane multiple)


@dataclass(frozen=True)
class TcgnnTile:
    """Column-condensed dense tiles + per-block-row gather index."""
    n_rows: int = dataclasses.field(metadata=dict(static=True))
    n_cols: int = dataclasses.field(metadata=dict(static=True))
    block_size: int = dataclasses.field(metadata=dict(static=True))
    n_cond: int = dataclasses.field(metadata=dict(static=True))  # C, lane-pad
    f_tile_cap: int = dataclasses.field(default=512,
                                        metadata=dict(static=True))
    budgeted: bool = dataclasses.field(default=False,
                                       metadata=dict(static=True))
    tiles: Any = None        # (n_brow, B, C) float32 condensed adjacency
    gather_idx: Any = None   # (n_brow, C) int32 source ids, 0 where padded

    @property
    def n_brow(self) -> int:
        return self.n_rows // self.block_size


jax.tree_util.register_dataclass(
    TcgnnTile, ["tiles", "gather_idx"],
    ["n_rows", "n_cols", "block_size", "n_cond", "f_tile_cap", "budgeted"])


# ---------------------------------------------------------------------------
# Host-side builders
# ---------------------------------------------------------------------------

def _np_edges(coo):
    return (formats._np(coo.rows), formats._np(coo.cols),
            formats._np(coo.vals))


def _cond_rank(rows: np.ndarray, cols: np.ndarray, n_cols: int,
               block_size: int):
    """Rank each block row's distinct source columns densest-first (ties
    toward the lower column id) — the column-granular twin of
    formats.coo_to_bell_capped's vectorized segmented block rank."""
    brow = (rows // block_size).astype(np.int64)
    key = brow * np.int64(n_cols) + cols.astype(np.int64)
    uniq, inv, counts = np.unique(key, return_inverse=True,
                                  return_counts=True)
    ubrow, ucol = uniq // n_cols, uniq % n_cols
    order = np.lexsort((ucol, -counts, ubrow))
    sorted_brow = ubrow[order]
    rank_sorted = (np.arange(len(uniq))
                   - np.searchsorted(sorted_brow, sorted_brow))
    slot = np.empty(len(uniq), np.int64)
    slot[order] = rank_sorted
    return brow, ubrow, ucol, slot, slot[inv]


def coo_to_tcgnn(coo: formats.COO, block_size: int,
                 f_tile_cap: int = 512) -> TcgnnTile:
    """Full-batch condensation: C = lane-rounded max distinct-column count
    over block rows (data-dependent; the budget-capped variant below pins
    it for the mini-batch path)."""
    B = block_size
    n_rpad = ((coo.n_rows + B - 1) // B) * B
    nbr = max(n_rpad // B, 1)
    rows, cols, vals = _np_edges(coo)
    if len(rows):
        brow, ubrow, ucol, slot, edge_slot = _cond_rank(
            rows, cols, coo.n_cols, B)
        C = _lane_pad(int(slot.max()) + 1)
    else:
        C = LANE
    tiles = np.zeros((nbr, B, C), np.float32)
    gather_idx = np.zeros((nbr, C), np.int32)
    if len(rows):
        gather_idx[ubrow, slot] = ucol
        np.add.at(tiles, (brow, rows % B, edge_slot), vals)
    return TcgnnTile(n_rpad, coo.n_cols, B, C, f_tile_cap,
                     tiles=jnp.asarray(tiles),
                     gather_idx=jnp.asarray(gather_idx))


def tcgnn_budget_c(edge_budget: int, n_pad: int, block_size: int,
                   slack: float = 2.0) -> int:
    """Condensed-column cap C for the budget-padded payload.

    Derived from the sampler's *edge budget* alone — never a batch's
    actual edges — so every batch shares one (n_brow, B, C) shape.  The
    worst case is every stored edge owning its own distinct column, so C
    covers ``slack``x the per-block-row average edge count, lane-rounded;
    the (lane-padded) column count bounds it above — at that bound the cap
    is vacuous and nothing ever spills."""
    nbr = max(n_pad // block_size, 1)
    c = -(-int(slack * edge_budget) // nbr)
    c = -(-max(c, 1) // LANE) * LANE
    return int(max(LANE, min(c, _lane_pad(n_pad))))


def coo_to_tcgnn_capped(coo: formats.COO, block_size: int, c_max: int,
                        f_tile_cap: int = 512, build_tiles: bool = True
                        ) -> tuple[TcgnnTile | None, formats.COO,
                                   formats.COO]:
    """Condensed tiles with exactly ``c_max`` column slots per block row.

    Rows with more distinct columns keep their *densest* ``c_max`` (ties
    toward the lower column id); the remaining edges come back as a
    row-sorted *spill* COO and the stored edges as a third COO (what the
    transpose pass caps again — see :func:`_tcgnn_build_capped`).  Returns
    ``(tc, spill, stored)`` with ``tc.budgeted=True``; all three shapes
    are functions of ``(c_max, n_pad, B)`` and the edge count only.

    ``build_tiles=False`` skips the (n_brow, B, C) scatter and returns
    ``tc=None`` — for the capped builder's first partition pass, which
    only needs the stored/spill edge split."""
    B = block_size
    n_rpad = ((coo.n_rows + B - 1) // B) * B
    nbr = max(n_rpad // B, 1)
    C = int(max(LANE, -(-int(c_max) // LANE) * LANE))
    rows, cols, vals = _np_edges(coo)
    if build_tiles:
        tiles = np.zeros((nbr, B, C), np.float32)
        gather_idx = np.zeros((nbr, C), np.int32)
    if len(rows):
        brow, ubrow, ucol, slot, edge_slot = _cond_rank(
            rows, cols, coo.n_cols, B)
        stored_m = edge_slot < C
        if build_tiles:
            sb = np.flatnonzero(slot < C)
            gather_idx[ubrow[sb], slot[sb]] = ucol[sb]
            np.add.at(tiles, (brow[stored_m], rows[stored_m] % B,
                              edge_slot[stored_m]), vals[stored_m])
    else:
        stored_m = np.zeros(0, bool)
    tc = (TcgnnTile(n_rpad, coo.n_cols, B, C, f_tile_cap, budgeted=True,
                    tiles=jnp.asarray(tiles),
                    gather_idx=jnp.asarray(gather_idx))
          if build_tiles else None)
    spill = formats.coo_from_edges(n_rpad, coo.n_cols, rows[~stored_m],
                                   cols[~stored_m], vals[~stored_m])
    stored = formats.coo_from_edges(n_rpad, coo.n_cols, rows[stored_m],
                                    cols[stored_m], vals[stored_m])
    return tc, spill, stored


def _tcgnn_f_cap(block_size: int) -> int:
    """Feature-tile cap keeping one grid step's VMEM working set (tile +
    gathered-feature stripe + accumulator + output) near the same ~4 MB
    double-buffered budget the blocked-ELL kernels target."""
    budget_floats = (4 << 20) // 4 // 2
    cap = ((budget_floats - block_size * C_TILE_CAP)
           // (C_TILE_CAP + 2 * block_size))
    return int(max(LANE, min(1024, (cap // LANE) * LANE)))


def _tcgnn_build(coo, coo_t, block_size, stats):
    """Condensed-tile payload; two variants keyed by the subgraph stats.

    With ``stats['edge_budget']`` set (the mini-batch path) the payload is
    the budget-capped triple ``(tc, tc_t, spill)``; otherwise the classic
    ``(tc, tc_t)`` pair with the data-dependent C.  The budget slack is
    shared with blocked-ELL (``stats['bell_slack']``): both caps answer
    "how much padding buys how little spill", so the PlanCache's budget-K
    autotuner steers them together."""
    budget = (stats or {}).get("edge_budget")
    if budget:
        return _tcgnn_build_capped(coo, block_size, int(budget),
                                   slack=(stats or {}).get("bell_slack"))
    cap = _tcgnn_f_cap(block_size)
    return (coo_to_tcgnn(coo, block_size, f_tile_cap=cap),
            coo_to_tcgnn(coo_t, block_size, f_tile_cap=cap))


def _tcgnn_build_capped(coo, block_size, edge_budget, slack=None):
    """Budget-capped payload ``(tc, tc_t, spill)``.

    Same dance as the registry's capped blocked-ELL builder, at column
    granularity: cap the forward edges, cap the *transpose of the stored
    subset*, then rebuild the forward payload from the transpose-capped
    survivors — a subset of a C-fitting column set still fits C, so the
    rebuild never spills and ``tc_t`` is exactly ``tc`` transposed."""
    C = tcgnn_budget_c(edge_budget, coo.n_rows, block_size,
                       **({} if slack is None else dict(slack=slack)))
    cap = _tcgnn_f_cap(block_size)
    _, spill_fwd, stored = coo_to_tcgnn_capped(
        coo, block_size, C, build_tiles=False)
    sr, sc, sv = _np_edges(stored)
    coo_st = formats.coo_from_edges(stored.n_cols, stored.n_rows, sc, sr, sv)
    tc_t, spill_t, stored_t = coo_to_tcgnn_capped(
        coo_st, block_size, C, f_tile_cap=cap)
    tr, tcc, tv = _np_edges(stored_t)
    tc, leftover, _ = coo_to_tcgnn_capped(
        formats.coo_from_edges(coo.n_rows, coo.n_cols, tcc, tr, tv),
        block_size, C, f_tile_cap=cap)
    assert leftover.nnz == 0  # a subset of a C-fitting column set fits C
    fr, fc, fv = _np_edges(spill_fwd)
    xr, xc, xv = _np_edges(spill_t)      # transpose orientation: swap back
    spill = formats.coo_from_edges(
        coo.n_rows, coo.n_cols, np.concatenate([fr, xc]),
        np.concatenate([fc, xr]), np.concatenate([fv, xv]))
    return (tc, tc_t, spill)


# ---------------------------------------------------------------------------
# Pallas kernels: batched dense contraction over the condensed tiles.
# The per-row gather runs in XLA before the call (BlockSpec indirection is
# block-granular; a per-column gather needs row granularity), so the grid
# is plain (block-rows, feature-tiles, column-tiles) with no scalar
# prefetch — C is the innermost reduction accumulated in VMEM scratch.
# ---------------------------------------------------------------------------

def _mv_kernel(a_ref, xg_ref, o_ref, acc_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], xg_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _mv_kernel_acc(a_ref, xg_ref, y_ref, o_ref, acc_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        # accumulation mode: seed from the threaded-through partial output
        acc_ref[...] = y_ref[...].astype(jnp.float32)

    acc_ref[...] += jnp.dot(a_ref[...], xg_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("f_tile", "c_tile", "interpret"))
def tcgnn_spmm(tiles: jax.Array, xg: jax.Array,
               y_in: jax.Array | None = None, *, f_tile: int = 512,
               c_tile: int = C_TILE_CAP, interpret: bool
               ) -> jax.Array:
    """Y = condensed contraction tiles @ xg (+ y_in).

    tiles: (nbr, B, C); xg: (nbr, C, F) gathered features; y_in: optional
    (nbr*B, F) accumulator input.  Returns (nbr*B, F).
    """
    nbr, B, C = tiles.shape
    F = xg.shape[-1]
    f_tile = min(f_tile, F)
    c_tile = min(c_tile, C)
    assert F % f_tile == 0 and C % c_tile == 0, (F, f_tile, C, c_tile)
    grid = (nbr, F // f_tile, C // c_tile)
    in_specs = [
        pl.BlockSpec((None, B, c_tile), lambda i, j, k: (i, 0, k)),
        pl.BlockSpec((None, c_tile, f_tile), lambda i, j, k: (i, k, j)),
    ]
    operands = [tiles, xg]
    kernel = _mv_kernel
    if y_in is not None:
        in_specs.append(
            pl.BlockSpec((None, B, f_tile), lambda i, j, k: (i, 0, j)))
        operands.append(y_in.reshape(nbr, B, F))
        kernel = _mv_kernel_acc
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, B, f_tile), lambda i, j, k: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((nbr, B, F), xg.dtype),
        scratch_shapes=[pltpu.VMEM((B, f_tile), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(*operands)
    return out.reshape(nbr * B, F)


def _fmv_kernel(a_ref, xg_ref, w_ref, o_ref, acc_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    h = jnp.dot(xg_ref[...], w_ref[...], preferred_element_type=jnp.float32)
    acc_ref[...] += jnp.dot(a_ref[...].astype(jnp.float32), h,
                            preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _fmv_kernel_acc(a_ref, xg_ref, w_ref, y_ref, o_ref, acc_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = y_ref[...].astype(jnp.float32)

    h = jnp.dot(xg_ref[...], w_ref[...], preferred_element_type=jnp.float32)
    acc_ref[...] += jnp.dot(a_ref[...].astype(jnp.float32), h,
                            preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("f_tile", "c_tile", "interpret"))
def tcgnn_spmm_fused(tiles: jax.Array, xg: jax.Array, w: jax.Array,
                     y_in: jax.Array | None = None, *, f_tile: int = 512,
                     c_tile: int = C_TILE_CAP, interpret: bool
                     ) -> jax.Array:
    """Y = tiles @ (xg @ w) (+ y_in): the gathered (c_tile, Fi) feature
    stripe is transformed in VMEM and immediately contracted — H never
    round-trips HBM.  Unlike bell's fused kernel the transform runs once
    per *condensed column slot* (a source row gathered by k block rows is
    transformed k times; the cost model prices that recompute).

    tiles: (nbr, B, C); xg: (nbr, C, Fi); w: (Fi, Fo) with Fo % f_tile
    == 0; y_in: optional (nbr*B, Fo).  Returns (nbr*B, Fo).
    """
    nbr, B, C = tiles.shape
    Fi = xg.shape[-1]
    Fo = w.shape[-1]
    f_tile = min(f_tile, Fo)
    c_tile = min(c_tile, C)
    assert Fo % f_tile == 0 and C % c_tile == 0, (Fo, f_tile, C, c_tile)
    grid = (nbr, Fo // f_tile, C // c_tile)
    in_specs = [
        pl.BlockSpec((None, B, c_tile), lambda i, j, k: (i, 0, k)),
        pl.BlockSpec((None, c_tile, Fi), lambda i, j, k: (i, k, 0)),
        pl.BlockSpec((Fi, f_tile), lambda i, j, k: (0, j)),
    ]
    operands = [tiles, xg, w]
    kernel = _fmv_kernel
    if y_in is not None:
        in_specs.append(
            pl.BlockSpec((None, B, f_tile), lambda i, j, k: (i, 0, j)))
        operands.append(y_in.reshape(nbr, B, Fo))
        kernel = _fmv_kernel_acc
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, B, f_tile), lambda i, j, k: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((nbr, B, Fo), xg.dtype),
        scratch_shapes=[pltpu.VMEM((B, f_tile), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(*operands)
    return out.reshape(nbr * B, Fo)


def _dw_kernel(a_ref, g_ref, x_ref, o_ref, acc_ref):
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
        preferred_element_type=jnp.float32)                  # (fi, fo)

    @pl.when((i == pl.num_programs(2) - 1) & (k == pl.num_programs(3) - 1))
    def _flush():
        o_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("fi_tile", "fo_tile",
                                             "c_tile", "interpret"))
def tcgnn_spmm_dw(tiles_t: jax.Array, gg: jax.Array, x: jax.Array, *,
                  fi_tile: int = 512, fo_tile: int = 512,
                  c_tile: int = C_TILE_CAP, interpret: bool
                  ) -> jax.Array:
    """dW = X^T @ (A^T @ G), A^T given as the condensed transpose payload,
    as a single blocked reduction sum_{i,k} x_i^T (tiles_t[i,k] @ gg[i,k])
    — no (n, F) intermediate is ever written.

    tiles_t: (nbr, B, C); gg: (nbr, C, Fo) gathered dY; x: (nbr*B, Fi).
    Returns (Fi, Fo) float32.
    """
    nbr, B, C = tiles_t.shape
    Fi = x.shape[-1]
    Fo = gg.shape[-1]
    fi_tile = min(fi_tile, Fi)
    fo_tile = min(fo_tile, Fo)
    c_tile = min(c_tile, C)
    assert Fi % fi_tile == 0 and Fo % fo_tile == 0 and C % c_tile == 0, (
        Fi, fi_tile, Fo, fo_tile, C, c_tile)
    xb = x.reshape(nbr, B, Fi)
    grid = (Fi // fi_tile, Fo // fo_tile, nbr, C // c_tile)
    return pl.pallas_call(
        _dw_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, B, c_tile), lambda fi, fo, i, k: (i, 0, k)),
            pl.BlockSpec((None, c_tile, fo_tile),
                         lambda fi, fo, i, k: (i, k, fo)),
            pl.BlockSpec((None, B, fi_tile),
                         lambda fi, fo, i, k: (i, 0, fi)),
        ],
        out_specs=pl.BlockSpec((fi_tile, fo_tile),
                               lambda fi, fo, i, k: (fi, fo)),
        out_shape=jax.ShapeDtypeStruct((Fi, Fo), jnp.float32),
        scratch_shapes=[pltpu.VMEM((fi_tile, fo_tile), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel",
                                 "arbitrary", "arbitrary")),
    )(tiles_t, gg, xb)


# ---------------------------------------------------------------------------
# jit wrappers + custom VJPs (ops.py idiom: Y = A @ X is linear in X, so
# dX = A^T @ dY over the materialized transpose payload)
# ---------------------------------------------------------------------------

def _c_tile_of(C: int) -> int:
    return ops._f_tile(C, cap=C_TILE_CAP)


def _tc_gather(tc: TcgnnTile, xp: jax.Array) -> jax.Array:
    """Row-level XLA gather: (nbr, C, F) dense stripes the kernel streams.
    Padded slots gather row 0 against zero tile values — correct, unmasked."""
    return xp[tc.gather_idx]


def _tc_fwd_impl(tc: TcgnnTile, x, y_in=None):
    t = ops._f_tile(x.shape[-1], cap=tc.f_tile_cap)
    xp, F = ops._pad_feat(x, t)
    xp = ops._pad_rows(xp, tc.n_cols)
    yp = ops._pad_feat(y_in, t)[0] if y_in is not None else None
    y = tcgnn_spmm(tc.tiles, _tc_gather(tc, xp), yp, f_tile=t,
                   c_tile=_c_tile_of(tc.n_cond), interpret=ops._interpret())
    return y[:, :F]


@jax.custom_vjp
def tcgnn_matvec(tc: TcgnnTile, tc_t: TcgnnTile, x: jax.Array) -> jax.Array:
    return _tc_fwd_impl(tc, x)


def _tc_fwd(tc, tc_t, x):
    return _tc_fwd_impl(tc, x), (tc_t, x.shape[0])


def _tc_bwd(res, dy):
    tc_t, n = res
    dx = _tc_fwd_impl(tc_t, dy)[:n]
    return None, None, dx


tcgnn_matvec.defvjp(_tc_fwd, _tc_bwd)


@jax.custom_vjp
def tcgnn_matvec_acc(tc: TcgnnTile, tc_t: TcgnnTile, x: jax.Array,
                     y_in: jax.Array) -> jax.Array:
    """Y = A_tc @ x + y_in (accumulating dispatch mode)."""
    return _tc_fwd_impl(tc, x, y_in)


def _tc_acc_fwd(tc, tc_t, x, y_in):
    return _tc_fwd_impl(tc, x, y_in), (tc_t, x.shape[0])


def _tc_acc_bwd(res, dy):
    tc_t, n = res
    dx = _tc_fwd_impl(tc_t, dy)[:n]
    return None, None, dx, dy


tcgnn_matvec_acc.defvjp(_tc_acc_fwd, _tc_acc_bwd)


def _tc_fused_f_cap(block_size: int, c_tile: int, fin_padded: int) -> int:
    """Output-tile cap for the fused kernel from the VMEM budget: per grid
    step the working set is B*c (tile) + c*Fi (gathered stripe) + Fi*Ft
    (weight stripe) + 2*B*Ft (accumulator + output)."""
    budget_floats = (4 << 20) // 4 // 2
    cap = ((budget_floats - block_size * c_tile - c_tile * fin_padded)
           // (fin_padded + 2 * block_size))
    return int(max(LANE, min(1024, (cap // LANE) * LANE)))


def _tcf_impl(tc: TcgnnTile, x, w, y_in=None):
    xp, _ = ops._pad_feat(x, LANE)
    xp = ops._pad_rows(xp, tc.n_cols)
    Fo = w.shape[-1]
    ct = _c_tile_of(tc.n_cond)
    t = ops._f_tile(Fo, cap=min(tc.f_tile_cap,
                                _tc_fused_f_cap(tc.block_size, ct,
                                                xp.shape[-1])))
    wp = ops._pad_feat(w, t)[0]
    wp = jnp.pad(wp, ((0, xp.shape[-1] - wp.shape[0]), (0, 0)))
    yp = ops._pad_feat(y_in, t)[0] if y_in is not None else None
    y = tcgnn_spmm_fused(tc.tiles, _tc_gather(tc, xp), wp, yp, f_tile=t,
                         c_tile=ct, interpret=ops._interpret())
    return y[:, :Fo]


def _tc_dw_impl(tc_t: TcgnnTile, x, dy):
    """dW = X^T (A^T dY) over the condensed transpose payload."""
    xp, Fi = ops._pad_feat(x, LANE)
    xp = ops._pad_rows(xp, tc_t.n_rows)
    gp, Fo = ops._pad_feat(dy, LANE)
    gp = ops._pad_rows(gp, tc_t.n_cols)
    dw = tcgnn_spmm_dw(tc_t.tiles, _tc_gather(tc_t, gp), xp,
                       fi_tile=ops._f_tile(Fi), fo_tile=ops._f_tile(Fo),
                       c_tile=_c_tile_of(tc_t.n_cond),
                       interpret=ops._interpret())
    return dw[:Fi, :Fo]


@jax.custom_vjp
def tcgnn_fused_matvec(tc: TcgnnTile, tc_t: TcgnnTile, x: jax.Array,
                       w: jax.Array) -> jax.Array:
    """Y = A_tc @ (x @ w), one fused Pallas pass."""
    return _tcf_impl(tc, x, w)


def _tcf_fwd(tc, tc_t, x, w):
    return _tcf_impl(tc, x, w), (tc_t, x, w)


def _tcf_bwd(res, dy):
    tc_t, x, w = res
    dx = _tcf_impl(tc_t, dy, w.T)[: x.shape[0]].astype(x.dtype)
    dw = _tc_dw_impl(tc_t, x, dy).astype(w.dtype)
    return None, None, dx, dw


tcgnn_fused_matvec.defvjp(_tcf_fwd, _tcf_bwd)


@jax.custom_vjp
def tcgnn_fused_matvec_acc(tc: TcgnnTile, tc_t: TcgnnTile, x: jax.Array,
                           w: jax.Array, y_in: jax.Array) -> jax.Array:
    """Y = A_tc @ (x @ w) + y_in, one fused Pallas pass."""
    return _tcf_impl(tc, x, w, y_in)


def _tcf_acc_fwd(tc, tc_t, x, w, y_in):
    return _tcf_impl(tc, x, w, y_in), (tc_t, x, w)


def _tcf_acc_bwd(res, dy):
    tc_t, x, w = res
    dx = _tcf_impl(tc_t, dy, w.T)[: x.shape[0]].astype(x.dtype)
    dw = _tc_dw_impl(tc_t, x, dy).astype(w.dtype)
    return None, None, dx, dw, dy


tcgnn_fused_matvec_acc.defvjp(_tcf_acc_fwd, _tcf_acc_bwd)


# ---------------------------------------------------------------------------
# Dispatch shims shared by the two payload layouts: the classic (tc, tc_t)
# pair and the budget-capped (tc, tc_t, spill) triple (spill rides the COO
# segment-sum / per-edge gathered-transform paths, like bell's)
# ---------------------------------------------------------------------------

def _tc_mv(p, x):
    y = tcgnn_matvec(p[0], p[1], x)
    return y + ops.coo_matvec(p[2], x) if len(p) > 2 else y


def _tc_mv_acc(p, x, y_in):
    y = tcgnn_matvec_acc(p[0], p[1], x, y_in)
    return y + ops.coo_matvec(p[2], x) if len(p) > 2 else y


def _tc_fmv(p, x, w):
    y = tcgnn_fused_matvec(p[0], p[1], x, w)
    return y + ops.coo_transform_matvec(p[2], x, w) if len(p) > 2 else y


def _tc_fmv_acc(p, x, w, y_in):
    y = tcgnn_fused_matvec_acc(p[0], p[1], x, w, y_in)
    return y + ops.coo_transform_matvec(p[2], x, w) if len(p) > 2 else y


# ---------------------------------------------------------------------------
# Cost model: condensation occupancy vs. padding waste.  The kernel
# executes all n_brow * C slots, so a sparse tier whose distinct-column
# count sits far below the lane-rounded C prices its padding here, while a
# dense tier prices the (column-granular, not block-granular) volume that
# makes it beat bell exactly at mid densities.  The XLA row gather that
# feeds the kernel is priced gather-class at full feature width.
# ---------------------------------------------------------------------------

def _tcgnn_cost(sub, feat_dim, dtype, hw) -> float:
    be = _bytes_el(dtype)
    p = sub.formats["tcgnn_tile"]
    tc = p[0]
    B = tc.block_size
    nbr = tc.n_brow
    C = tc.n_cond
    flops = 2.0 * nbr * B * C * feat_dim
    gather_bytes = nbr * C * feat_dim * be     # (nbr, C, F) stripe volume
    bytes_ = (nbr * B * C * 4                  # condensed tiles (f32)
              + gather_bytes                   # kernel streams the stripes
              + sub.n_rows * feat_dim * be)    # output
    t = max(flops / (hw.peak_flops * hw.mxu_eff(B)), bytes_ / hw.hbm_bw)
    # row-level gather materializing the stripes: gather-class read + write
    t += gather_bytes / (hw.hbm_bw * hw.gather_eff)
    if len(p) > 2 and p[2].nnz:                # budget-capped: spill term
        t += _bell_spill_cost(p[2].nnz, sub.n_rows, feat_dim, dtype, hw)
    return t + hw.launch_overhead_s


def _tcgnn_fused_cost(sub, feat_dims, dtype, hw) -> float:
    fin, fout = feat_dims
    be = _bytes_el(dtype)
    p = sub.formats["tcgnn_tile"]
    tc = p[0]
    B = tc.block_size
    nbr = tc.n_brow
    C = tc.n_cond
    ct = _c_tile_of(C)
    ft = min(tc.f_tile_cap, _tc_fused_f_cap(B, ct, _lane_pad(fin)),
             _lane_pad(fout))
    njt = max(1, -(-_lane_pad(fout) // ft))
    # the transform runs once per condensed slot (C per block row) — less
    # recompute than bell's per-stored-block K*B rows at equal coverage
    flops = 2.0 * nbr * C * (fin * fout + B * fout)
    gather_bytes = nbr * C * fin * be
    bytes_ = (nbr * B * C * 4
              + gather_bytes * njt             # stripe re-read per out tile
              + nbr * fin * fout * be          # weight stripe per block row
              + sub.n_rows * fout * be)
    t = max(flops / (hw.peak_flops * hw.mxu_eff(B)), bytes_ / hw.hbm_bw)
    t += gather_bytes / (hw.hbm_bw * hw.gather_eff)
    if len(p) > 2 and p[2].nnz:
        # spilled edges transform their gathered source rows one-by-one
        E = p[2].nnz
        flops_s = 2.0 * E * (fin * fout + fout)
        bytes_s = E * (fin * be + fout * be + 8) + sub.n_rows * fout * be
        t += max(flops_s / hw.peak_flops,
                 bytes_s / (hw.hbm_bw * hw.scatter_eff))
    return t + hw.launch_overhead_s


REGISTRY.register(KernelSpec(
    name="tcgnn_tile",
    kinds=frozenset({OFFDIAG}),
    build=_tcgnn_build,
    matvec=_tc_mv,
    matvec_acc=_tc_mv_acc,
    cost=_tcgnn_cost,
    # full-batch builds consume coo_t; the budget-capped build re-derives
    # its transpose from the stored-edge subset, so no coo_t is needed
    needs_transpose=lambda stats: not stats.get("edge_budget"),
    pallas=True,
    doc="TC-GNN-style column condensation: each block row's non-zero "
        "columns packed into dense 8x128-aligned MXU tiles + a gather "
        "index; budget-capped C + COO spill under an edge budget",
))

REGISTRY.register(KernelSpec(
    name="tcgnn_tile_fused",
    kinds=frozenset({OFFDIAG}),
    build=None,
    payload_of="tcgnn_tile",
    matvec=None,
    fused_matvec=_tc_fmv,
    fused_matvec_acc=_tc_fmv_acc,
    cost=_tcgnn_fused_cost,
    pallas=True,
    doc="fused column-condensed A @ (X W): gathered stripe transformed in "
        "VMEM and contracted immediately, no (n, F) intermediate",
))
