"""Kernel registry: the single place an aggregation kernel is defined.

Every sparse/dense aggregation kernel registers one :class:`KernelSpec`
bundling everything the rest of the system needs to use it:

  name       -- dispatch key (stored in KernelPlans, printed by benchmarks)
  kinds      -- which subgraph kinds the kernel applies to: ``"diag"`` (the
                block-diagonal intra-community subgraph) and/or ``"offdiag"``
                (inter-community density buckets)
  build      -- host-side format materializer run once during decomposition:
                ``build(coo, coo_t, block_size, stats) -> payload``.  The
                payload is an arbitrary pytree (a single format container, or
                a tuple such as blocked-ELL forward + transpose for the VJP).
                ``coo_t`` is only constructed (and non-None) when
                ``needs_transpose`` is set.  ``stats`` carries the subgraph's
                density statistics so a builder can pick per-bucket tiling
                (the blocked-ELL builder chooses its block size and
                feature-tile cap from them).
  matvec     -- device function ``matvec(payload, x) -> A @ x``
  matvec_acc -- optional accumulating variant ``matvec_acc(payload, x, y_in)
                -> y_in + A @ x``; aggregate() threads one output buffer
                through the subgraph list instead of materializing a partial
                per density bucket (the Pallas kernels seed their VMEM
                scratch from y_in).
  fused_matvec / fused_matvec_acc
             -- fused transform+aggregate entry points
                ``(payload, x, w[, y_in]) -> A @ (x @ w) [+ y_in]``.  A spec
                providing these is a *fused* kernel: it is selected through
                the same KernelPlan machinery but dispatched by
                ``aggregate_transform`` with the raw features and weight.
  payload_of -- name of another kernel whose format payload this spec reuses
                (fused kernels alias their unfused counterpart's payload, so
                no extra device memory is materialized).
  cost       -- analytic roofline estimate consumed by the cost-model
                selector: ``cost(sub, feat_dim, dtype, hw) -> seconds`` for
                unfused kernels, where ``feat_dim`` is the aggregated width;
                for fused kernels ``feat_dim`` is the ``(in_dim, out_dim)``
                pair since the in-kernel transform prices both.  ``hw`` is
                any object with ``peak_flops / hbm_bw / launch_overhead_s /
                gather_eff / scatter_eff / mxu_eff(B)`` (core/selector.HwModel).

Adding a kernel (CSR, sell-C-sigma, another fused variant, ...) is one
``register()`` call in one file — see kernels/csr.py for the one-file
template; decomposition, both selector modes, aggregation dispatch, and the
benchmarks pick it up automatically.  Registration order is meaningful:
``candidates()`` preserves it, and the selectors break cost ties in favor of
earlier registrations.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable

import jax
import numpy as np

from repro.core import formats
from repro.kernels import ops

DIAG = "diag"          # intra-community subgraph (block-diagonal)
OFFDIAG = "offdiag"    # inter-community subgraph / density bucket


@dataclass(frozen=True)
class KernelSpec:
    name: str
    kinds: frozenset
    build: Callable[[formats.COO, formats.COO, int, dict], Any] | None
    matvec: Callable[[Any, jax.Array], jax.Array] | None
    cost: Callable[[Any, Any, Any, Any], float]
    # build consumes coo_t (for the VJP); a callable form decides from the
    # tier stats so budget-capped builds (which derive their own transpose
    # from the stored-edge subset) don't force a full transpose COO
    needs_transpose: Any = False    # bool | Callable[[dict], bool]

    def wants_transpose(self, stats: dict | None) -> bool:
        if callable(self.needs_transpose):
            return bool(self.needs_transpose(stats or {}))
        return bool(self.needs_transpose)
    matvec_acc: Callable[[Any, jax.Array, jax.Array], jax.Array] | None = None
    fused_matvec: Callable[..., jax.Array] | None = None
    fused_matvec_acc: Callable[..., jax.Array] | None = None
    # dual-weight epilogue hooks (SAGE): ``(payload, x, w_neigh, w_self
    # [, y_in]) -> x @ w_self + A @ (x @ w_neigh) [+ y_in]``.  Optional even
    # for fused specs; aggregate_transform_dual uses them on the tier that
    # owns the self term (the diagonal tier, whose row block is its own
    # source block) and falls back to seeding the accumulator with the
    # dense self term otherwise.
    fused_dual_matvec: Callable[..., jax.Array] | None = None
    fused_dual_matvec_acc: Callable[..., jax.Array] | None = None
    payload_of: str | None = None   # alias another kernel's format payload
    # Pallas-compiled device code (vs. XLA-native gather/segment ops).  The
    # quarantine path (sampling.plan_cache / train.gnn_steps) uses this to
    # attribute a compile/execute failure it cannot pin to one kernel: the
    # XLA reference kernel (coo) always succeeds, so only pallas specs
    # are quarantine candidates by default.
    pallas: bool = False
    doc: str = ""

    def applies_to(self, kind: str) -> bool:
        return kind in self.kinds

    @property
    def fused(self) -> bool:
        return self.fused_matvec is not None

    @property
    def payload_key(self) -> str:
        """Key into Subgraph.formats holding this kernel's payload."""
        return self.payload_of or self.name


class KernelRegistry:
    """Ordered name -> KernelSpec mapping with per-subgraph-kind views."""

    def __init__(self):
        self._specs: dict[str, KernelSpec] = {}

    def register(self, spec: KernelSpec) -> KernelSpec:
        if spec.name in self._specs:
            raise ValueError(f"kernel {spec.name!r} already registered")
        if spec.payload_of is not None and spec.payload_of not in self._specs:
            raise ValueError(
                f"kernel {spec.name!r} aliases unregistered payload "
                f"{spec.payload_of!r}")
        self._specs[spec.name] = spec
        return spec

    def get(self, name: str) -> KernelSpec:
        try:
            return self._specs[name]
        except KeyError:
            raise KeyError(
                f"unknown kernel {name!r}; registered: {self.names()}"
            ) from None

    def names(self) -> tuple[str, ...]:
        return tuple(self._specs)

    def candidates(self, kind: str, include_fused: bool = False
                   ) -> tuple[KernelSpec, ...]:
        """Specs applicable to a subgraph kind, in registration order.

        Fused specs are opt-in: they require the transform operand ``w`` at
        dispatch time, so only transform-first call sites (GCN) enumerate
        them."""
        return tuple(s for s in self._specs.values()
                     if s.applies_to(kind) and (include_fused or not s.fused))

    def candidates_for(self, sub, include_fused: bool = False
                       ) -> tuple[KernelSpec, ...]:
        """Specs whose format payload is materialized on this subgraph."""
        return tuple(s for s in self.candidates(sub.kind, include_fused)
                     if s.payload_key in sub.formats)

    def __contains__(self, name: str) -> bool:
        return name in self._specs

    def __iter__(self):
        return iter(self._specs.values())


REGISTRY = KernelRegistry()


def payload_nbytes(payload) -> int:
    """Device bytes of a format payload (any pytree of arrays)."""
    return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(payload)
               if hasattr(a, "size"))


def _bytes_el(dtype) -> int:
    return np.dtype(dtype).itemsize


def _lane_pad(F: int) -> int:
    return ((F + 127) // 128) * 128


# ---------------------------------------------------------------------------
# Per-bucket blocked-ELL tiling (chosen at build time from density stats)
# ---------------------------------------------------------------------------

def _bell_pick_block(coo: formats.COO, base_block: int) -> int:
    """Blocked-ELL block size for one density bucket.

    Candidates are multiples of the community size that still divide the
    padded node count (so every bucket's output stays row-aligned with the
    rest of the decomposition).  Score per candidate: ``K * sqrt(Bb)`` —
    the geometric mean of the memory proxy (padded tile volume
    ``nbr * K * Bb^2 = n * K * Bb``) and the MXU-efficiency proxy (the same
    volume de-rated by the ``Bb/128`` sublane utilization, i.e. ``n*K*128``).
    Merging neighbors into a fatter tile wins exactly when the bucket's
    stored-block count collapses with it (dense block neighborhoods);
    scattered buckets keep the small base block since K barely drops while
    padding quadruples."""
    n_pad = coo.n_rows
    rows = formats._np(coo.rows)
    cols = formats._np(coo.cols)
    if len(rows) == 0:
        return base_block
    best, best_score = base_block, None
    for mult in (1, 2, 4):
        Bb = base_block * mult
        if n_pad % Bb:
            continue
        nbc = n_pad // Bb
        brow = rows // Bb
        keys = np.unique(brow.astype(np.int64) * nbc + cols // Bb)
        per_row = np.bincount(keys // nbc, minlength=n_pad // Bb)
        K = max(int(per_row.max()), 1)
        score = K * float(np.sqrt(Bb))
        if best_score is None or score < best_score:
            best, best_score = Bb, score
    return best


def _bell_f_cap(block_size: int) -> int:
    """Feature-tile cap keeping the kernel's double-buffered VMEM working
    set (adjacency tile + x tile + accumulator + output tile) near 4 MB."""
    budget_floats = (4 << 20) // 4 // 2
    cap = (budget_floats - block_size * block_size) // (3 * block_size)
    return int(max(128, min(1024, (cap // 128) * 128)))


def _bell_build(coo, coo_t, block_size, stats):
    """Blocked-ELL payload; two variants keyed by the subgraph stats.

    With ``stats['edge_budget']`` set (the mini-batch path) the payload is
    the *budget-padded* triple ``(bell, bell_t, spill)`` whose every array
    dim is a function of (budget, n_pad, B) — see :func:`_bell_build_capped`.
    Otherwise (full batch) it is the classic ``(bell, bell_t)`` pair with
    the data-dependent per-bucket block size and K."""
    budget = (stats or {}).get("edge_budget")
    if budget:
        return _bell_build_capped(coo, block_size, int(budget),
                                  slack=(stats or {}).get("bell_slack"))
    Bb = _bell_pick_block(coo, block_size)
    cap = _bell_f_cap(Bb)
    return (formats.coo_to_bell(coo, Bb, f_tile_cap=cap),
            formats.coo_to_bell(coo_t, Bb, f_tile_cap=cap))


def _np_edges(coo):
    return (formats._np(coo.rows), formats._np(coo.cols),
            formats._np(coo.vals))


def _bell_build_capped(coo, block_size, edge_budget, slack=None):
    """Budget-padded blocked-ELL payload ``(bell, bell_t, spill)``.

    The block size is pinned to the community size and K to
    :func:`formats.bell_budget_k` (a data-dependent block merge or K would
    change the pytree shape per batch and retrace the jitted step).
    ``slack`` overrides the budget cap's slack factor: the PlanCache's
    budget-K autotuner feeds observed spill rates back through the tier
    stats (``stats['bell_slack']``) so hub-heavy samplers trade padding
    waste against spill volume per workload.  The
    forward cap keeps each block-row's densest blocks; the transpose of the
    *stored* edges is capped again, and stored edges whose transposed block
    did not fit move to the spill alongside the forward overflow.  That
    makes ``bell_t`` exactly the transpose of ``bell``, so the existing
    blocked-ELL custom VJPs stay correct as-is, while every spilled edge
    flows through the natively-differentiable segment-sum path in both
    directions."""
    K = formats.bell_budget_k(edge_budget, coo.n_rows, block_size,
                              **({} if slack is None else dict(slack=slack)))
    cap = _bell_f_cap(block_size)
    _, spill_fwd, stored = formats.coo_to_bell_capped(
        coo, block_size, K, f_tile_cap=cap, build_blocks=False)
    sr, sc, sv = _np_edges(stored)
    coo_st = formats.coo_from_edges(stored.n_cols, stored.n_rows, sc, sr, sv)
    bell_t, spill_t, stored_t = formats.coo_to_bell_capped(
        coo_st, block_size, K, f_tile_cap=cap)
    # forward payload = exactly the transpose-capped survivors
    tr, tc, tv = _np_edges(stored_t)
    bell, leftover, _ = formats.coo_to_bell_capped(
        formats.coo_from_edges(coo.n_rows, coo.n_cols, tc, tr, tv),
        block_size, K, f_tile_cap=cap)
    assert leftover.nnz == 0  # a subset of a K-fitting edge set fits K
    fr, fc, fv = _np_edges(spill_fwd)
    xr, xc, xv = _np_edges(spill_t)      # transpose orientation: swap back
    spill = formats.coo_from_edges(
        coo.n_rows, coo.n_cols, np.concatenate([fr, xc]),
        np.concatenate([fc, xr]), np.concatenate([fv, xv]))
    return (bell, bell_t, spill)


# Dispatch shims shared by the two blocked-ELL payload layouts: the classic
# (bell, bell_t) pair and the budget-padded (bell, bell_t, spill) triple.
# The spill aggregates through the COO segment-sum path (unfused) or the
# per-edge gathered transform (fused — H is never materialized for it).

def _bell_mv(p, x):
    y = ops.bell_matvec(p[0], p[1], x)
    return y + ops.coo_matvec(p[2], x) if len(p) > 2 else y


def _bell_mv_acc(p, x, y_in):
    y = ops.bell_matvec_acc(p[0], p[1], x, y_in)
    return y + ops.coo_matvec(p[2], x) if len(p) > 2 else y


def _bell_fmv(p, x, w):
    y = ops.bell_fused_matvec(p[0], p[1], x, w)
    return y + ops.coo_transform_matvec(p[2], x, w) if len(p) > 2 else y


def _bell_fmv_acc(p, x, w, y_in):
    y = ops.bell_fused_matvec_acc(p[0], p[1], x, w, y_in)
    return y + ops.coo_transform_matvec(p[2], x, w) if len(p) > 2 else y


# ---------------------------------------------------------------------------
# Built-in kernels.  Cost formulae are the two-term roofline estimates that
# used to live inline in core/selector.candidate_cost (paper §3.3's analytic
# alternative to feedback probing).
# ---------------------------------------------------------------------------

def _block_diag_cost(sub, feat_dim, dtype, hw) -> float:
    be = _bytes_el(dtype)
    B = sub.block_size
    nb = sub.n_rows // B
    flops = 2.0 * nb * B * B * feat_dim
    bytes_ = nb * B * B * be + 2.0 * sub.n_rows * feat_dim * be
    t = max(flops / (hw.peak_flops * hw.mxu_eff(B)), bytes_ / hw.hbm_bw)
    return t + hw.launch_overhead_s


def _bell_spill_cost(nnz, n_rows, feat_dim, dtype, hw) -> float:
    """Scatter-class seconds for the capped payload's spilled edges (same
    shape as the COO term; no extra launch — the spill rides the same
    dispatch).  Priced at the *real* spill nnz, matching the convention of
    the COO/CSR cost fns (padding to the edge budget executes zero-valued
    edges for every candidate alike)."""
    be = _bytes_el(dtype)
    flops = 2.0 * nnz * feat_dim
    bytes_ = nnz * (2 * feat_dim * be + 8) + n_rows * feat_dim * be
    return max(flops / hw.peak_flops, bytes_ / (hw.hbm_bw * hw.scatter_eff))


def _bell_cost(sub, feat_dim, dtype, hw) -> float:
    be = _bytes_el(dtype)
    p = sub.formats["bell"]
    bl = p[0]
    B = bl.block_size
    # padding-waste term is inherent: the kernel executes all n_brow * K
    # slots, so a budget-capped K prices its masked zero-blocks here
    nblk = bl.n_brow * bl.max_blocks
    flops = 2.0 * nblk * B * B * feat_dim
    bytes_ = nblk * (B * B * be + B * feat_dim * be) + sub.n_rows * feat_dim * be
    t = max(flops / (hw.peak_flops * hw.mxu_eff(B)), bytes_ / hw.hbm_bw)
    if len(p) > 2 and p[2].nnz:          # budget-capped: spill-cost term
        t += _bell_spill_cost(p[2].nnz, sub.n_rows, feat_dim, dtype, hw)
    return t + hw.launch_overhead_s


def _ell_cost(sub, feat_dim, dtype, hw) -> float:
    be = _bytes_el(dtype)
    n = sub.n_rows
    K = sub.formats["ell"].max_deg
    flops = 2.0 * n * K * feat_dim
    bytes_ = n * K * (feat_dim * be + 4) + n * feat_dim * be
    return max(flops / hw.peak_flops,
               bytes_ / (hw.hbm_bw * hw.gather_eff)) + hw.launch_overhead_s


def _coo_cost(sub, feat_dim, dtype, hw) -> float:
    be = _bytes_el(dtype)
    nnz = sub.stats["nnz"]
    flops = 2.0 * nnz * feat_dim
    bytes_ = nnz * (2 * feat_dim * be + 8) + sub.n_rows * feat_dim * be
    return max(flops / hw.peak_flops,
               bytes_ / (hw.hbm_bw * hw.scatter_eff)) + hw.launch_overhead_s


# Fused transform+aggregate costs.  ``feat_dim`` is the (in_dim, out_dim)
# pair: the in-kernel transform prices the input width (the unfused
# aggregation only ever sees out_dim); the selector adds the shared dense
# transform's cost to *unfused* candidates when comparing (selector.py).

def _block_diag_fused_cost(sub, feat_dims, dtype, hw) -> float:
    fin, fout = feat_dims
    be = _bytes_el(dtype)
    B = sub.block_size
    nb = sub.n_rows // B
    ft = min(ops._fused_f_cap(B, _lane_pad(fin)), _lane_pad(fout))
    njt = max(1, -(-_lane_pad(fout) // ft))
    # transform runs once per row (same FLOPs as the standalone X @ W) plus
    # the block contraction; H never round-trips HBM
    flops = 2.0 * nb * B * (fin * fout + B * fout)
    bytes_ = (nb * B * B * be                     # adjacency blocks
              + sub.n_rows * fin * be * njt      # x re-read per output tile
              + nb * fin * fout * be             # weight stripe per block
              + sub.n_rows * fout * be)          # output
    t = max(flops / (hw.peak_flops * hw.mxu_eff(B)), bytes_ / hw.hbm_bw)
    return t + hw.launch_overhead_s


def _bell_fused_cost(sub, feat_dims, dtype, hw) -> float:
    fin, fout = feat_dims
    be = _bytes_el(dtype)
    p = sub.formats["bell"]
    bl = p[0]
    B = bl.block_size
    nblk = bl.n_brow * bl.max_blocks     # includes budget-cap padding waste
    ft = min(bl.f_tile_cap, ops._fused_f_cap(B, _lane_pad(fin)),
             _lane_pad(fout))
    njt = max(1, -(-_lane_pad(fout) // ft))
    # the transform re-runs per *stored block* (recompute vs H round-trip
    # trade: a source block referenced k times is transformed k times)
    flops = 2.0 * nblk * B * (fin * fout + B * fout)
    bytes_ = (nblk * B * B * be
              + nblk * B * fin * be * njt        # gathered x per stored block
              + nblk * fin * fout * be           # weight stripe per step
              + sub.n_rows * fout * be)
    t = max(flops / (hw.peak_flops * hw.mxu_eff(B)), bytes_ / hw.hbm_bw)
    if len(p) > 2 and p[2].nnz:
        # spilled edges transform their gathered source rows one-by-one
        # (coo_transform_matvec): E*fin*fout recompute + scatter-class bytes
        E = p[2].nnz
        flops_s = 2.0 * E * (fin * fout + fout)
        bytes_s = (E * (fin * be + fout * be + 8)
                   + sub.n_rows * fout * be)
        t += max(flops_s / hw.peak_flops,
                 bytes_s / (hw.hbm_bw * hw.scatter_eff))
    return t + hw.launch_overhead_s


REGISTRY.register(KernelSpec(
    name="block_diag",
    kinds=frozenset({DIAG}),
    build=lambda coo, coo_t, B, stats: formats.coo_to_blockdiag(coo, B),
    matvec=lambda bd, x: ops.block_diag_matvec(bd.blocks, x),
    matvec_acc=lambda bd, x, y: ops.block_diag_matvec_acc(bd.blocks, x, y),
    cost=_block_diag_cost,
    pallas=True,
    doc="dense (B,B) diagonal blocks on the MXU (paper's dense kernel)",
))

REGISTRY.register(KernelSpec(
    name="bell",
    kinds=frozenset({OFFDIAG}),
    build=_bell_build,
    matvec=_bell_mv,
    matvec_acc=_bell_mv_acc,
    cost=_bell_cost,
    # full-batch builds consume coo_t; the budget-capped build re-derives
    # its transpose from the stored-edge subset, so no coo_t is needed
    needs_transpose=lambda stats: not stats.get("edge_budget"),
    pallas=True,
    doc="blocked-ELL over per-bucket (B,B) tiles; transpose materialized "
        "for the VJP; budget-capped K + COO spill under an edge budget",
))

REGISTRY.register(KernelSpec(
    name="ell",
    kinds=frozenset({DIAG, OFFDIAG}),
    build=lambda coo, coo_t, B, stats: formats.coo_to_ell(coo),
    matvec=lambda ell, x: ops.ell_matvec(ell, x),
    cost=_ell_cost,
    doc="padded-neighbor gather (vertex-parallel CSR analogue)",
))

REGISTRY.register(KernelSpec(
    name="coo",
    kinds=frozenset({DIAG, OFFDIAG}),
    build=lambda coo, coo_t, B, stats: coo,
    matvec=lambda coo, x: ops.coo_matvec(coo, x),
    cost=_coo_cost,
    doc="edge-parallel segment-sum (scatter-add analogue)",
))

REGISTRY.register(KernelSpec(
    name="block_diag_fused",
    kinds=frozenset({DIAG}),
    build=None,
    payload_of="block_diag",
    matvec=None,
    fused_matvec=lambda bd, x, w: ops.block_diag_fused_matvec(bd.blocks, x, w),
    fused_matvec_acc=lambda bd, x, w, y:
        ops.block_diag_fused_matvec_acc(bd.blocks, x, w, y),
    fused_dual_matvec=lambda bd, x, w, ws:
        ops.block_diag_dual_matvec(bd.blocks, x, w, ws),
    fused_dual_matvec_acc=lambda bd, x, w, ws, y:
        ops.block_diag_dual_matvec_acc(bd.blocks, x, w, ws, y),
    cost=_block_diag_fused_cost,
    pallas=True,
    doc="fused A @ (X W): weight stripe in VMEM, transform consumed by the "
        "MXU block contraction without an HBM round-trip; the dual-weight "
        "hook adds a second (self) stripe for the SAGE epilogue",
))

REGISTRY.register(KernelSpec(
    name="bell_fused",
    kinds=frozenset({OFFDIAG}),
    build=None,
    payload_of="bell",
    matvec=None,
    fused_matvec=_bell_fmv,
    fused_matvec_acc=_bell_fmv_acc,
    cost=_bell_fused_cost,
    pallas=True,
    doc="fused blocked-ELL A @ (X W); trades per-stored-block transform "
        "recompute for the H round-trip",
))

# one-file kernel registrations (import side effect registers the spec)
from repro.kernels import csr  # noqa: E402,F401
from repro.kernels import sell_cs  # noqa: E402,F401
from repro.kernels import tcgnn_tile  # noqa: E402,F401
