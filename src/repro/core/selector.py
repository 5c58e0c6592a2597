"""Adaptive kernel selector (paper §3.3).

Two modes, both enumerating candidates from the kernel registry per
subgraph (intra tier + every inter density bucket):

* ``feedback`` (paper-faithful): during the first few training iterations,
  time every candidate kernel on the *actual* decomposed input, then commit
  to the fastest.  GNN training reuses a static graph for hundreds to
  thousands of iterations, so the probe cost amortizes to ~0 (§6.3 measures
  <0.1 s).  On TPU every candidate is compiled once and cached, so probing
  costs K compilations + a few executions -- same amortization argument.

* ``cost_model`` (TPU adaptation, beyond-paper): an analytic two-term
  roofline estimate (compute term = FLOPs/peak, memory term = bytes/bw) per
  candidate, provided by each kernel's registry ``cost`` fn.  Used when
  wall-clock probing is impossible -- inside a traced computation, or during
  the multi-pod dry-run where kernels are only lowered, never run.  The
  model's constants can be calibrated from feedback probes (``calibrate``),
  closing the loop between the two modes.

The selector returns per-subgraph kernel-name tuples (one KernelPlan layer);
dispatch lives in core/adaptgear.py.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field, replace

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.decompose import Decomposed, Subgraph
from repro.core.epilogue import EpilogueSpec, epilogue_cost
from repro.kernels.registry import REGISTRY


@dataclass(frozen=True)
class HwModel:
    """Per-chip hardware constants for the analytic cost model."""
    name: str = "tpu_v5e"
    peak_flops: float = 197e12      # bf16 FLOP/s
    hbm_bw: float = 819e9           # bytes/s
    # fixed per-kernel-invocation overhead (grid setup, DMA warmup) and
    # per-path efficiency de-rates (gather/scatter do not stream at peak).
    launch_overhead_s: float = 2e-6
    gather_eff: float = 0.30        # XLA gather achieves ~30% of streaming bw
    scatter_eff: float = 0.15       # scatter/segment-sum read-modify-write
    mxu_small_block_eff: dict = field(default_factory=dict)

    def mxu_eff(self, b: int) -> float:
        """MXU utilization for (b, b) tiles: a 128x128 systolic array runs
        b<128 tiles at (b/128)^2 of peak in the worst case; padding in ops.py
        lifts the lane dim, leaving a (b/128) sublane de-rate."""
        return min(b / 128.0, 1.0)


CPU_HW = HwModel(name="cpu_interpret", peak_flops=5e10, hbm_bw=2e10,
                 launch_overhead_s=5e-5)

# Cost-model constants keyed by ``jax.Device.device_kind``; a TPU v5e
# reports "TPU v5 lite".
HW_BY_KIND = {"cpu": CPU_HW, "TPU v5 lite": HwModel()}


def default_hw(device_kind: str | None = None) -> HwModel:
    """Constants for ``device_kind`` (default: the first JAX device's).
    A kind missing from :data:`HW_BY_KIND` raises: peak rates assumed for
    a device nobody described would price every kernel wrongly."""
    kind = jax.devices()[0].device_kind if device_kind is None else device_kind
    try:
        return HW_BY_KIND[kind]
    except KeyError:
        raise ValueError(f"no cost-model constants for device kind {kind!r}; "
                         f"known: {sorted(HW_BY_KIND)}") from None


def dense_transform_cost(n: int, fin: int, fout: int, dtype=np.float32,
                         hw: HwModel = HwModel()) -> float:
    """Roofline seconds for the standalone dense transform H = X @ W that
    the unfused GCN path pays before aggregation (and fused kernels fold
    into their pass)."""
    be = np.dtype(dtype).itemsize
    flops = 2.0 * n * fin * fout
    bytes_ = (n * fin + n * fout + fin * fout) * be
    return max(flops / hw.peak_flops, bytes_ / hw.hbm_bw) + hw.launch_overhead_s


def candidate_cost(sub: Subgraph, kernel: str, feat_dim: int,
                   dtype=np.float32, hw: HwModel = HwModel(),
                   in_dim: int | None = None,
                   transform_share: float = 0.0) -> float:
    """Analytic seconds estimate for one (subgraph, kernel) candidate,
    delegated to the kernel's registered cost fn.

    Fused kernels price the ``(in_dim, feat_dim)`` pair (their pass includes
    the transform); unfused kernels aggregate at ``feat_dim`` and carry
    ``transform_share`` — their slice of the shared H = X @ W cost — so the
    fused-vs-unfused comparison stays apples-to-apples."""
    spec = REGISTRY.get(kernel)
    if spec.fused:
        if in_dim is None:
            raise ValueError(
                f"fused kernel {kernel!r} needs in_dim to be costed")
        return spec.cost(sub, (in_dim, feat_dim), dtype, hw)
    return spec.cost(sub, feat_dim, dtype, hw) + transform_share


def select_for_subgraph(sub: Subgraph, feat_dim: int, dtype=np.float32,
                        hw: HwModel = HwModel(),
                        in_dim: int | None = None,
                        transform_share: float = 0.0,
                        exclude: frozenset = frozenset()) -> str:
    """Cost-argmin kernel name for one subgraph.  ``exclude`` removes
    candidates by name — the PlanCache's kernel quarantine: a kernel whose
    compile/execute failed for this signature is struck from the frontier
    and the next-best takes over (the XLA reference path always stays)."""
    specs = [s for s in REGISTRY.candidates_for(
                 sub, include_fused=in_dim is not None)
             if s.name not in exclude]
    if not specs:
        raise ValueError(f"no kernel candidates for subgraph {sub.name!r}"
                         + (f" outside exclusion set {sorted(exclude)}"
                            if exclude else ""))
    return min(specs, key=lambda s: candidate_cost(
        sub, s.name, feat_dim, dtype, hw, in_dim, transform_share)).name


def _transform_share(dec: Decomposed, feat_dim: int, dtype, hw,
                     in_dim: int | None,
                     epilogue: EpilogueSpec | None = None) -> float:
    """Per-subgraph slice of the shared dense-transform cost.

    Approximation: if *some* subgraphs pick unfused kernels the transform is
    paid once in full regardless of how many picked it; dividing by the
    subgraph count under-charges mixed layers slightly, but leaves the
    unfused-vs-unfused ranking untouched and prices the all-fused-vs-
    all-unfused crossover correctly.

    An epilogue with ``free_transform`` (GIN's MLP: the self term computes
    S = X W1 regardless) zeroes the share — unfused candidates aggregate
    the already-paid-for transform, so fused candidates must win on
    bandwidth alone there."""
    if in_dim is None or (epilogue is not None and epilogue.free_transform):
        return 0.0
    return (dense_transform_cost(dec.n_pad, in_dim, feat_dim, dtype, hw)
            / max(len(dec.subgraphs), 1))


def select_by_cost_model(dec: Decomposed, feat_dim: int, dtype=np.float32,
                         hw: HwModel = HwModel(),
                         in_dim: int | None = None,
                         epilogue: EpilogueSpec | None = None,
                         exclude: frozenset = frozenset()
                         ) -> tuple[str, ...]:
    """One KernelPlan layer: the cost-argmin kernel per subgraph.

    With ``in_dim`` set (transform-first layers: GCN, and GIN/SAGE through
    their epilogue rewrite) fused candidates compete: each unfused
    candidate is surcharged its share of the shared H = X @ W cost the
    fused kernels avoid — unless the layer's ``epilogue`` marks that
    transform as free (see :func:`_transform_share`).  ``exclude`` strikes
    quarantined kernel names from every subgraph's candidate set."""
    share = _transform_share(dec, feat_dim, dtype, hw, in_dim, epilogue)
    return tuple(select_for_subgraph(s, feat_dim, dtype, hw, in_dim, share,
                                     exclude=exclude)
                 for s in dec.subgraphs)


def plan_layer_cost(dec: Decomposed, feat_dim: int, dtype=np.float32,
                    hw: HwModel = HwModel(),
                    in_dim: int | None = None,
                    epilogue: EpilogueSpec | None = None) -> float:
    """Total modeled seconds for one layer under the cost-argmin choice —
    the objective the bucket-count autotuner minimizes across k.  The
    layer's dense epilogue terms (the dual self matmul, the MLP's second
    layer) are flat across candidates but enter the total so whole-model
    structures price honestly."""
    share = _transform_share(dec, feat_dim, dtype, hw, in_dim, epilogue)
    total = epilogue_cost(epilogue, dec.n_pad, in_dim, feat_dim, dtype, hw)
    for sub in dec.subgraphs:
        specs = REGISTRY.candidates_for(sub, include_fused=in_dim is not None)
        total += min(candidate_cost(sub, s.name, feat_dim, dtype, hw,
                                    in_dim, share) for s in specs)
    return total


def plan_modeled_costs(dec: Decomposed, layers, pairs, dtype=np.float32,
                       hw: HwModel | None = None,
                       epilogues=None) -> list[list[float]]:
    """Modeled seconds for each *chosen* kernel of a committed plan:
    ``layers`` is the plan's per-layer kernel-name tuples (aligned with
    ``dec.subgraphs``), ``pairs`` the ``(in_dim, agg_dim)`` per layer as
    in PlanCache.  Returns one cost row per layer — the selector audit's
    "modeled" side of the calibration report.  Unfused kernels carry
    their shared-transform share exactly as in selection, so the numbers
    match what ``select_by_cost_model`` compared."""
    hw = hw or default_hw()
    pairs = list(pairs)
    epilogues = epilogues or [None] * len(pairs)
    out = []
    for names, (fin, fout), ep in zip(layers, pairs, epilogues):
        share = _transform_share(dec, fout, dtype, hw, fin, ep)
        out.append([candidate_cost(sub, name, fout, dtype, hw, fin, share)
                    for sub, name in zip(dec.subgraphs, names)])
    return out


def _probe_fn(sub: Subgraph, spec):
    """``(jitted kernel, device payload)`` for timing one candidate.  The
    payload is an argument, not a closed-over constant, so the probe
    compiles the same program the train step runs, without baking the
    subgraph into the executable; it is placed on device once, so no
    timed call re-uploads host payloads."""
    payload = jax.device_put(sub.formats[spec.payload_key])
    return jax.jit(spec.fused_matvec if spec.fused else spec.matvec), payload


def _time_candidate(sub: Subgraph, spec, fin: int | None, fout: int,
                    dtype, iters: int) -> float:
    """Median wall seconds for one candidate on synthetic full-width
    operands (compile excluded) — the measurement unit probe_topk and the
    PlanCache's Nth-miss probe share with the full-batch feedback path.
    The payload is a jit argument (see :func:`_probe_fn`)."""
    fn, payload = _probe_fn(sub, spec)
    if spec.fused:
        args = (payload, jnp.ones((sub.n_rows, fin), dtype),
                jnp.ones((fin, fout), dtype))
    else:
        args = (payload, jnp.ones((sub.n_rows, fout), dtype))
    fn(*args).block_until_ready()          # compile outside the timing
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn(*args).block_until_ready()
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def probe_topk(dec: Decomposed, pairs, dtype=np.float32,
               hw: HwModel | None = None, k: int = 2,
               iters: int = 2, time_dec: Decomposed | None = None,
               epilogues=None, k_max: int | None = None,
               margin: float | None = None,
               time_budget_s: float | None = None,
               errs: list | None = None,
               timings: dict | None = None) -> list[tuple[str, ...]]:
    """Wall-clock probe restricted to the ``k`` cheapest cost-model
    candidates per (layer, subgraph).

    This is the amortized feedback mode the PlanCache runs on every Nth
    miss: instead of timing every registered candidate (the full-batch
    AdaptiveSelector warmup), only the plausible frontier — the top-k by
    modeled cost — is compiled and measured, and the measured argmin is
    pinned.  Unfused candidates carry the *modeled* shared-transform share
    (measuring H = X W per probe would triple the compile bill for a term
    the model prices well); fused candidates are timed end-to-end.
    ``pairs`` are ``(in_dim, agg_dim)`` per layer as in PlanCache;
    ``epilogues`` the aligned per-layer EpilogueSpecs (share freeness).
    Returns one kernel-name tuple per pair.

    Adaptive widening (ROADMAP probe-budget shaping): with ``margin`` set
    — the cost model's observed relative-error band, measured by past
    probes and by ``calibrate_cost_model`` — the frontier widens past the
    top-``k`` to every candidate whose modeled cost sits within
    ``(1 + margin)`` of the modeled best, capped at ``k_max``: when the
    model cannot distinguish candidates to within its own error, the
    wall clock decides among all of them.  ``time_budget_s`` caps the
    probe's total wall time (compiles included): once exhausted, untimed
    candidates are skipped and the argmin runs over whatever was measured
    (falling back to the modeled best when nothing was).

    ``errs``, when given, accrues ``(modeled_seconds, measured_seconds)``
    per timed candidate — the PlanCache folds these into its running
    error band, closing the model-vs-measurement loop.  ``timings``, when
    given, is filled with ``(sub_name, kernel, in_dim, agg_dim) ->
    (modeled_seconds, measured_seconds)`` per timed candidate — the
    attributed form the selector audit records.

    ``time_dec`` optionally supplies the payloads to *time* (aligned with
    ``dec.subgraphs``) while ``dec`` still drives the cost-model ranking:
    the mini-batch probe passes the budget-padded twin, because that —
    not the real-nnz payload — is what the jitted step executes (a COO
    timed at 500 real edges but run at a 10k-slot budget would be pinned
    on the wrong side of the crossover).
    """
    hw = hw or default_hw()
    timed: dict[tuple, float] = {}
    layers = []
    time_subs = (time_dec or dec).subgraphs
    pairs = list(pairs)
    epilogues = epilogues or [None] * len(pairs)
    t_start = time.perf_counter()

    def budget_left() -> bool:
        return (time_budget_s is None
                or time.perf_counter() - t_start < time_budget_s)

    for (fin, fout), ep in zip(pairs, epilogues):
        share = _transform_share(dec, fout, dtype, hw, fin, ep)
        choice = []
        for sub, tsub in zip(dec.subgraphs, time_subs):
            specs = REGISTRY.candidates_for(sub,
                                            include_fused=fin is not None)
            if not specs:
                raise ValueError(
                    f"no kernel candidates for subgraph {sub.name!r}")
            modeled = {s.name: candidate_cost(sub, s.name, fout, dtype, hw,
                                              fin, share) for s in specs}
            ranked = sorted(specs, key=lambda s: modeled[s.name])
            cands = ranked[:max(k, 1)]
            if margin is not None and len(ranked) > len(cands):
                lim = modeled[ranked[0].name] * (1.0 + max(margin, 0.0))
                cands += [s for s in ranked[len(cands):max(k_max or k, k)]
                          if modeled[s.name] <= lim]
            if len(cands) < 2:
                choice.append(cands[0].name)
                continue
            best_name, best_t = None, None
            for spec in cands:
                key = (sub.name, spec.name, fin or 0, fout)
                if key not in timed:
                    if not budget_left():
                        continue        # budget spent: modeled ranking holds
                    timed[key] = _time_candidate(tsub, spec, fin, fout,
                                                 dtype, iters)
                    if errs is not None:
                        errs.append((modeled[spec.name] -
                                     (0.0 if spec.fused else share),
                                     timed[key]))
                    if timings is not None:
                        timings[(sub.name, spec.name, fin or 0, fout)] = (
                            modeled[spec.name] -
                            (0.0 if spec.fused else share),
                            timed[key])
                t = timed[key] + (0.0 if spec.fused else share)
                if best_t is None or t < best_t:
                    best_name, best_t = spec.name, t
            choice.append(best_name or cands[0].name)
        layers.append(tuple(choice))
    return layers


@dataclass
class ProbeResult:
    times: dict            # (subgraph name, kernel) -> median seconds
    choice: tuple          # kernel name per subgraph


class AdaptiveSelector:
    """Feedback-driven selector (paper §3.3).

    ``observe()`` is fed per-candidate wall times collected during the first
    training iterations; ``choice()`` commits to the argmin per subgraph.
    ``probe()`` is a convenience that measures all candidates immediately
    (used by benchmarks; the training loop uses the iteration-interleaved
    variant in core/gnn.py to match the paper's monitor design).
    """

    def __init__(self, dec: Decomposed, warmup_iters: int = 3,
                 include_fused: bool = False):
        self.dec = dec
        self.warmup_iters = warmup_iters
        # fused candidates need the transform operand at probe time; only
        # transform-first call sites (GCN) can supply it, so they opt in
        self.include_fused = include_fused
        # keyed (subgraph, kernel, width key): GNN layers aggregate at
        # different widths (GIN's first layer at the raw feature width, GCN
        # at the hidden width), and the optimal kernel is width-dependent —
        # a beyond-paper refinement of the feedback selector.  The width key
        # is the (in_dim, agg_dim) pair (in_dim 0 when no transform): two
        # GCN layers sharing an output width but differing in input width
        # sit on opposite sides of the fused recompute crossover, so their
        # observations and committed choices must not pool.
        self._times: dict[tuple[str, str, tuple], list[float]] = {}
        self._raw: dict[tuple[str, str, tuple], list[float]] = {}
        self._committed: dict[tuple, tuple] = {}

    def _cands(self, sub: Subgraph):
        return REGISTRY.candidates_for(sub, include_fused=self.include_fused)

    @staticmethod
    def _wkey(width) -> tuple:
        """Normalize a width spec (int or (in_dim, agg_dim)) to a key."""
        if isinstance(width, tuple):
            return (width[0] or 0, width[1])
        return (0, width or 0)

    def observe(self, sub_name: str, kernel: str, seconds: float,
                width=0, raw_seconds: float | None = None) -> None:
        key = (sub_name, kernel, self._wkey(width))
        self._times.setdefault(key, []).append(seconds)
        self._raw.setdefault(key, []).append(
            seconds if raw_seconds is None else raw_seconds)

    def _widths(self) -> set:
        return {w for (_, _, w) in self._times}

    def _need(self, width) -> list[tuple[str, str, tuple]]:
        wk = self._wkey(width)
        return [(s.name, spec.name, wk)
                for s in self.dec.subgraphs
                for spec in self._cands(s)]

    def ready(self, width=0) -> bool:
        width = self._nearest_width(width)
        return all(len(self._times.get(key, [])) >= self.warmup_iters
                   for key in self._need(width))

    def _nearest_width(self, width) -> tuple:
        ws = self._widths()
        wk = self._wkey(width)
        if not ws:
            return wk
        return min(ws, key=lambda w: (abs(w[1] - wk[1]), abs(w[0] - wk[0])))

    def choice(self, feat_dim=None) -> tuple:
        w = self._nearest_width(feat_dim or 0)
        if w in self._committed:
            return self._committed[w]
        if self._times and self.ready(w):
            med = {k: float(np.median(v)) for k, v in self._times.items()}
            self._committed[w] = tuple(
                min(self._cands(s),
                    key=lambda spec: med[(s.name, spec.name, w)]).name
                for s in self.dec.subgraphs)
            return self._committed[w]
        # not enough observations yet: fall back to the cost model
        assert feat_dim is not None, "need feat_dim for cost-model fallback"
        fin, fout = self._wkey(feat_dim)
        return select_by_cost_model(self.dec, fout, hw=default_hw(),
                                    in_dim=fin or None)

    def probe(self, x: jax.Array, iters: int = 3,
              transform: tuple | None = None,
              free_transform: bool = False) -> ProbeResult:
        """Time every candidate on the real decomposed input.

        ``x`` is the aggregated-width operand the unfused kernels consume.
        ``transform`` is the optional ``(x_in, w)`` pair for transform-first
        layers: fused candidates are timed end-to-end on A @ (x_in W), and
        each unfused candidate is charged its per-subgraph share of the
        measured standalone H = X @ W it depends on — keeping the committed
        argmin an honest whole-layer comparison.  ``free_transform`` (GIN's
        MLP epilogue: the self term computes H regardless) keeps the fused
        probes but zeroes that surcharge."""
        share = 0.0
        if transform is not None:
            x_in, w_mat = transform
            width = (x_in.shape[-1], x.shape[-1])
            if not free_transform:
                mm = jax.jit(lambda a, b: a @ b)
                mm(x_in, w_mat).block_until_ready()
                ts = []
                for _ in range(iters):
                    t0 = time.perf_counter()
                    mm(x_in, w_mat).block_until_ready()
                    ts.append(time.perf_counter() - t0)
                share = float(np.median(ts)) / max(len(self.dec.subgraphs), 1)
        else:
            width = x.shape[-1]
        wk = self._wkey(width)
        for sub in self.dec.subgraphs:
            for spec in self._cands(sub):
                if spec.fused and transform is None:
                    continue
                fn, payload = _probe_fn(sub, spec)
                if spec.fused:
                    args = (payload, x_in, w_mat)
                    extra = 0.0
                else:
                    args = (payload, x)
                    extra = share
                fn(*args).block_until_ready()  # compile outside the timing
                for _ in range(iters):
                    t0 = time.perf_counter()
                    fn(*args).block_until_ready()
                    t = time.perf_counter() - t0
                    # selection compares t + transform share; calibration
                    # fits the bare kernel time (raw_seconds)
                    self.observe(sub.name, spec.name, t + extra, width,
                                 raw_seconds=t)
        med = {(s, k): float(np.median(v))
               for (s, k, w), v in self._times.items() if w == wk}
        return ProbeResult(times=med, choice=self.choice(width))

    def calibrate_cost_model(self, feat_dim: int,
                             hw: HwModel | None = None) -> HwModel:
        """Fit a global time-scale from probes so the analytic model's
        *absolute* numbers match this machine (its *ranking* is what the
        dry-run uses).  Fitted against the *raw* kernel times: the selection
        surcharge (shared-transform share) is not part of any kernel's own
        cost fn."""
        hw = hw or default_hw()
        if not self._raw:
            return hw
        by_name = {s.name: s for s in self.dec.subgraphs}
        ratios = []
        for (sub_name, kern, w), ts in self._raw.items():
            if REGISTRY.get(kern).fused:
                continue   # fused probes fold in the transform; skip the fit
            est = candidate_cost(by_name[sub_name], kern, w[1] or feat_dim,
                                 hw=hw)
            ratios.append(np.median(ts) / max(est, 1e-12))
        if not ratios:
            return hw
        scale = float(np.median(ratios))
        return replace(hw, peak_flops=hw.peak_flops / scale,
                       hbm_bw=hw.hbm_bw / scale)
