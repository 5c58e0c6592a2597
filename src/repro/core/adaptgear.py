"""AdaptGear aggregation dispatch + GNN convolution layers (paper §3/§4).

``aggregate`` is the AG-equivalent of the paper's subgraph-level execution:
Y = sum_s A_s @ X over the decomposition's subgraphs (intra tier + one or
more inter density buckets), with an independently selected kernel per
subgraph.  Dispatch goes through the kernel registry — there is no
string-keyed if/elif chain here; a kernel choice is a registry name resolved
to a spec whose ``matvec`` runs on the subgraph's materialized payload.
Layers are pure functions over explicit parameter pytrees (init_* / apply
pattern; no framework dependency).
"""
from __future__ import annotations

import math
from typing import Any, Sequence

import jax
import jax.numpy as jnp

from repro.core import plan as plan_mod
from repro.core.decompose import Decomposed, Subgraph
from repro.kernels.registry import REGISTRY

Params = Any

DEFAULT_KERNELS = ("block_diag", "bell")


# ---------------------------------------------------------------------------
# Aggregation dispatch
# ---------------------------------------------------------------------------

def to_reordered(dec: Decomposed, x: jax.Array) -> jax.Array:
    """Permute node features into community order and pad to n_pad rows."""
    xr = x[dec.inv_perm]
    pad = dec.n_pad - dec.n
    if pad:
        xr = jnp.pad(xr, ((0, pad), (0, 0)))
    return xr


def from_reordered(dec: Decomposed, xr: jax.Array) -> jax.Array:
    return xr[: dec.n][dec.perm]


def aggregate_sub(sub: Subgraph, x: jax.Array, kernel: str) -> jax.Array:
    """Aggregate over a single subgraph with an explicit registry kernel.
    x: (n_pad, F) in reordered space."""
    spec = REGISTRY.get(kernel)
    if spec.fused:
        raise ValueError(
            f"kernel {kernel!r} is fused (needs the weight operand); "
            "dispatch it through aggregate_sub_fused / aggregate_transform")
    return spec.matvec(sub.formats[spec.payload_key], x)


def aggregate_sub_fused(sub: Subgraph, x: jax.Array, w: jax.Array,
                        kernel: str) -> jax.Array:
    """A_s @ (x @ w) over a single subgraph with a fused registry kernel."""
    spec = REGISTRY.get(kernel)
    if not spec.fused:
        raise ValueError(f"kernel {kernel!r} is not fused")
    return spec.fused_matvec(sub.formats[spec.payload_key], x, w)


def tier_scope(sub: Subgraph, kernel: str):
    """``agg/<tier>/<kernel>`` on the name stack of one subgraph's dispatch:
    compile-time metadata only (each op's ``op_name``), read back from a
    compiled step by :func:`repro.obs.op_scopes`.  It changes no op,
    fusion or XLA instruction name; a Pallas call's instruction name,
    which XLA takes from the innermost name-stack component, loses its
    transform prefix under it (``jvp_jit_bell_spmm__.2`` becomes
    ``bell_spmm.2``)."""
    return jax.named_scope(f"agg/{sub.name}/{kernel}")


def aggregate(dec: Decomposed, x: jax.Array,
              kernels: Sequence[str] = DEFAULT_KERNELS) -> jax.Array:
    """Y = A @ X via per-subgraph kernels (x reordered, (n_pad, F)).

    ``kernels`` is one name per subgraph, or the ``(intra, inter)`` pair
    shorthand broadcast over inter buckets.  One output buffer is threaded
    through the subgraph list: kernels exposing ``matvec_acc`` seed their
    accumulator from it instead of zeros, so no per-bucket partial
    (n_pad, F) tensors are materialized; kernels without the hook add
    their partial explicitly."""
    names = plan_mod.normalize_layer(dec, kernels)
    y = None
    for sub, k in zip(dec.subgraphs, names):
        spec = REGISTRY.get(k)
        payload = sub.formats[spec.payload_key]
        with tier_scope(sub, k):
            if y is None:
                y = spec.matvec(payload, x)
            elif spec.matvec_acc is not None:
                y = spec.matvec_acc(payload, x, y)
            else:
                y = y + spec.matvec(payload, x)
    return y


def aggregate_transform(dec: Decomposed, x: jax.Array, w: jax.Array,
                        kernels: Sequence[str] = DEFAULT_KERNELS,
                        bias: jax.Array | None = None, *,
                        seed: jax.Array | None = None,
                        h: jax.Array | None = None) -> jax.Array:
    """Y = A @ (X W) (+ bias / + seed) with per-subgraph fused/unfused
    kernels.

    The transform-first hot path (GCN, and through the epilogue rewrite
    also GIN/SAGE): fused kernels consume the raw features and weight
    directly (H = X W never round-trips HBM); H is materialized once only
    if some subgraph picked an unfused kernel.  The bias seeds the
    accumulator threaded through the subgraph list (``matvec_acc`` /
    ``fused_matvec_acc``, as in :func:`aggregate`), so it rides along for
    free.

    ``seed`` generalizes ``bias`` to a full (n, Fo) accumulator seed — the
    epilogue self terms (GIN's ``(1+eps) X W1 + b1``) enter the threaded
    accumulation through it instead of a separate add.  ``h`` optionally
    supplies a precomputed transform for the unfused candidates (GIN's
    ``S = X W1`` is already materialized for the self term; recomputing it
    here would double the transform)."""
    names = plan_mod.normalize_layer(dec, kernels)
    specs = [REGISTRY.get(k) for k in names]
    if h is None:
        h = x @ w if any(not s.fused for s in specs) else None
    y = None
    if seed is not None:
        if bias is not None:
            raise ValueError("pass either bias or seed, not both")
        y = seed.astype(x.dtype)
    elif bias is not None:
        y = jnp.broadcast_to(bias.astype(x.dtype), (x.shape[0], w.shape[-1]))
    for sub, k, spec in zip(dec.subgraphs, names, specs):
        payload = sub.formats[spec.payload_key]
        with tier_scope(sub, k):
            if spec.fused:
                if y is None:
                    y = spec.fused_matvec(payload, x, w)
                elif spec.fused_matvec_acc is not None:
                    y = spec.fused_matvec_acc(payload, x, w, y)
                else:
                    y = y + spec.fused_matvec(payload, x, w)
            else:
                if y is None:
                    y = spec.matvec(payload, h)
                elif spec.matvec_acc is not None:
                    y = spec.matvec_acc(payload, h, y)
                else:
                    y = y + spec.matvec(payload, h)
    return y


def aggregate_transform_dual(dec: Decomposed, x: jax.Array, w: jax.Array,
                             w_self: jax.Array,
                             kernels: Sequence[str] = DEFAULT_KERNELS,
                             bias: jax.Array | None = None) -> jax.Array:
    """Y = X W_self + A @ (X W) (+ bias): the dual-weight (SAGE) epilogue.

    Mean normalization is baked into the decomposition's edge values
    (``core.gnn.prepare``), so ``A @ (X W)`` *is* the normalized neighbor
    term — no per-row rescale separates the self term from the threaded
    accumulation.  When the first tier's committed kernel provides the
    ``fused_dual_matvec`` hook (the diagonal tier's Pallas kernel), the
    self-weight stripe rides in VMEM next to the neighbor stripe and the
    self term never materializes separately (the bias seeds it through
    ``fused_dual_matvec_acc`` where the kernel has that hook too);
    otherwise it seeds the accumulator as a dense XLA matmul (still only
    (n, Fo)-wide — the (n, Fi) aggregation intermediate of the unfused
    layer is gone either way)."""
    names = plan_mod.normalize_layer(dec, kernels)
    first = REGISTRY.get(names[0])
    if first.fused_dual_matvec is not None:
        payload = dec.subgraphs[0].formats[first.payload_key]
        with tier_scope(dec.subgraphs[0], names[0]):
            if bias is not None and first.fused_dual_matvec_acc is not None:
                y0 = jnp.broadcast_to(bias.astype(x.dtype),
                                      (x.shape[0], w.shape[-1]))
                seed = first.fused_dual_matvec_acc(payload, x, w, w_self, y0)
            else:
                seed = first.fused_dual_matvec(payload, x, w, w_self)
                if bias is not None:
                    seed = seed + bias.astype(x.dtype)
        rest = dec.subgraphs[1:]
        rest_names = names[1:]
    else:
        seed = x @ w_self
        if bias is not None:
            seed = seed + bias.astype(x.dtype)
        rest = dec.subgraphs
        rest_names = names
    sub_dec = Decomposed(n=dec.n, n_pad=dec.n_pad, block_size=dec.block_size,
                         perm=dec.perm, inv_perm=dec.inv_perm,
                         subgraphs=tuple(rest), stats=None)
    return aggregate_transform(sub_dec, x, w, rest_names, seed=seed)


def aggregate_full_static(dec: Decomposed, x: jax.Array,
                          kernel: str = "ell") -> jax.Array:
    """Baseline O1 (paper §6.2): a single static full-graph-level kernel —
    GNNAdvisor/NeuGraph-style.  Every subgraph runs the same format (the
    plan layer validates applicability before anything executes)."""
    return aggregate(dec, x, (kernel,) * len(dec.subgraphs))


# ---------------------------------------------------------------------------
# Convolution layers
# ---------------------------------------------------------------------------

def _glorot(key, shape):
    fan_in, fan_out = shape[-2], shape[-1]
    lim = math.sqrt(6.0 / (fan_in + fan_out))
    return jax.random.uniform(key, shape, jnp.float32, -lim, lim)


def init_gcn_conv(key, in_dim: int, out_dim: int) -> Params:
    kw, = jax.random.split(key, 1)
    return dict(w=_glorot(kw, (in_dim, out_dim)),
                b=jnp.zeros((out_dim,), jnp.float32))


def gcn_conv(params: Params, dec: Decomposed, x: jax.Array,
             kernels: Sequence[str]) -> jax.Array:
    """GCN layer: Y = Â (X W) + b  (Kipf & Welling; Â norm baked into the
    decomposition's edge values).  Transform-first ordering reduces the
    aggregated width when out_dim < in_dim — same trick DGL applies.
    Dispatched through aggregate_transform: subgraphs whose plan entry is a
    fused kernel run A_s @ (X W) in one Pallas pass, and the bias seeds the
    accumulator threaded across the subgraph list."""
    return aggregate_transform(dec, x, params["w"], kernels,
                               bias=params["b"])


def init_gin_conv(key, in_dim: int, hidden: int, out_dim: int) -> Params:
    k1, k2 = jax.random.split(key)
    return dict(eps=jnp.zeros(()),
                w1=_glorot(k1, (in_dim, hidden)), b1=jnp.zeros((hidden,)),
                w2=_glorot(k2, (hidden, out_dim)), b2=jnp.zeros((out_dim,)))


def gin_conv(params: Params, dec: Decomposed, x: jax.Array,
             kernels: Sequence[str],
             structure: str = "transform_first") -> jax.Array:
    """GIN layer: MLP((1+eps) x + sum-agg(x)) (Xu et al.), under the
    structure the selector priced (EpilogueSpec ``structure``):

    transform-first — the MLP's first weight pushed *through* the
    aggregation (linearity):

        h1 = relu((1+eps) S + A (X W1) + b1),   S = X W1
        y  = h1 W2 + b2

    The aggregation runs at the MLP hidden width instead of the raw feature
    width, the (n, Fi) aggregated intermediate is gone, and fused kernels
    compete on ``A (X W1)``.  ``S`` is needed by the self term regardless,
    so it doubles as the unfused candidates' precomputed transform (the
    selector prices their shared-transform share at zero — EpilogueSpec
    ``free_transform``).

    aggregate-first — when the raw input is narrower than the hidden width
    the rewrite would *widen* the sparse pass, so aggregate raw features
    and run the whole MLP after (same result, by the same linearity):

        z  = (1+eps) X + A X
        y  = relu(z W1 + b1) W2 + b2

    Up to the ReLU the layer computes in float32 whatever the storage dtype,
    kernels included (``gnn.train`` prices them so): the ReLU's mask follows
    the sign of the self term plus the tier partials, and ``S`` or a partial
    rounded to bfloat16 flips it wherever the pre-activation sits within
    that rounding of zero; the fused kernels' and the self term's parts of
    dW1 nearly cancel, so they are summed in float32 too.  The self term
    and the MLP sit under ``gin/self`` and ``gin/mlp``; the aggregation
    keeps its own ``agg/<tier>/<kernel>`` scopes outside both.
    """
    dtype = x.dtype
    wide = jnp.promote_types(dtype, jnp.float32)
    x, w1, b1 = (x.astype(wide), params["w1"].astype(wide),
                 params["b1"].astype(wide))
    one_eps = 1.0 + params["eps"].astype(wide)
    if structure == "aggregate_first":
        names = plan_mod.normalize_layer(dec, kernels)
        if not any(REGISTRY.get(k).fused for k in names):
            with jax.named_scope("gin/self"):
                x_self = one_eps * x
            agg = aggregate(dec, x, names)
            with jax.named_scope("gin/mlp"):
                h1 = jax.nn.relu((x_self + agg) @ w1 + b1).astype(dtype)
                return h1 @ params["w2"] + params["b2"]
        # fused kernel names imply transform-first (A (X W1) is the only
        # pass they implement) — a pinned fused plan overrides the spec
    with jax.named_scope("gin/self"):
        s = x @ w1
        seed = one_eps * s + b1
    pre = aggregate_transform(dec, x, w1, kernels, seed=seed, h=s)
    with jax.named_scope("gin/mlp"):
        h1 = jax.nn.relu(pre).astype(dtype)
        return h1 @ params["w2"] + params["b2"]


def init_sage_conv(key, in_dim: int, out_dim: int) -> Params:
    k1, k2 = jax.random.split(key)
    return dict(w_self=_glorot(k1, (in_dim, out_dim)),
                w_neigh=_glorot(k2, (in_dim, out_dim)),
                b=jnp.zeros((out_dim,)))


def sage_conv(params: Params, dec: Decomposed, x: jax.Array,
              kernels: Sequence[str],
              inv_deg: jax.Array | None = None) -> jax.Array:
    """GraphSAGE mean-aggregator: W_self x + W_neigh mean_agg(x) + b.

    With ``inv_deg=None`` (the fused dual-weight epilogue path) the
    decomposition's edge values must already carry the mean normalization
    (``core.gnn.prepare`` / ``train.gnn_steps.prepare_skeleton`` bake
    ``1/deg(dst)`` exactly like GCN's symmetric norm): the neighbor weight
    pushes through the aggregation — ``mean(A@X) W == (D^-1 A)(X W)`` —
    so the aggregation runs at the output width, the (n, Fi) aggregated
    intermediate is gone, and the self term fuses into the diagonal tier's
    dual-stripe kernel when the plan picked it.

    Passing ``inv_deg`` keeps the legacy unbaked form (aggregate raw x,
    rescale, transform after) for callers with unnormalized edge values."""
    if inv_deg is not None:
        agg = aggregate(dec, x, kernels) * inv_deg[:, None]
        return x @ params["w_self"] + agg @ params["w_neigh"] + params["b"]
    return aggregate_transform_dual(dec, x, params["w_neigh"],
                                    params["w_self"], kernels,
                                    bias=params["b"])


def init_gat_conv(key, in_dim: int, out_dim: int) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    return dict(w=_glorot(k1, (in_dim, out_dim)),
                a_dst=_glorot(k2, (out_dim, 1))[:, 0],
                a_src=_glorot(k3, (out_dim, 1))[:, 0],
                b=jnp.zeros((out_dim,)))


def gat_conv(params: Params, dec: Decomposed, x: jax.Array,
             negative_slope: float = 0.2) -> jax.Array:
    """Single-head GAT with subgraph-level execution.

    Attention logits e_ij = LeakyReLU(a_dst.h_i + a_src.h_j) must be
    softmax-normalized over *all* in-neighbors of i — across every subgraph —
    so the partial aggregations share row-max and row-sum statistics.
    The intra part is evaluated as dense masked per-block attention (an MXU
    batched matmul, AdaptGear's dense-kernel path); each inter density bucket
    as COO edge softmax (segment ops, the edge-parallel path)."""
    h = x @ params["w"]                                 # (n_pad, F)
    s_dst = h @ params["a_dst"]                         # (n_pad,)
    s_src = h @ params["a_src"]

    B = dec.block_size
    nb = dec.n_pad // B
    # -- intra: dense per-block logits
    mask = dec.intra.formats["block_diag"].blocks != 0  # (nb, B, B)
    e_in = s_dst.reshape(nb, B)[:, :, None] + s_src.reshape(nb, B)[:, None, :]
    e_in = jax.nn.leaky_relu(e_in, negative_slope)
    e_in = jnp.where(mask, e_in, -jnp.inf)
    # -- inter buckets: per-edge logits (each bucket's COO is row-sorted)
    edge_parts = []
    for sub in dec.inters:
        coo = sub.formats["coo"]
        e_out = jax.nn.leaky_relu(s_dst[coo.rows] + s_src[coo.cols],
                                  negative_slope)
        edge_parts.append((coo.rows, coo.cols, e_out))

    # -- joint row max across all subgraphs
    m = jnp.max(e_in, axis=-1).reshape(-1)              # (n_pad,) -inf if empty
    for rows, _, e_out in edge_parts:
        m_out = jax.ops.segment_max(e_out, rows, num_segments=dec.n_pad,
                                    indices_are_sorted=True)
        m = jnp.maximum(m, m_out)
    m = jnp.where(jnp.isfinite(m), m, 0.0)

    # -- exp + joint row sum
    p_in = jnp.where(mask, jnp.exp(e_in - m.reshape(nb, B)[:, :, None]), 0.0)
    z = jnp.sum(p_in, axis=-1).reshape(-1)
    p_outs = []
    for rows, _, e_out in edge_parts:
        p_out = jnp.exp(e_out - m[rows])
        p_outs.append(p_out)
        z = z + jax.ops.segment_sum(p_out, rows, num_segments=dec.n_pad,
                                    indices_are_sorted=True)
    z = jnp.maximum(z, 1e-9)

    # -- weighted aggregation, subgraph-level kernels
    hb = h.reshape(nb, B, -1)
    y = jnp.einsum("bij,bjf->bif", p_in, hb,
                   preferred_element_type=jnp.float32).reshape(dec.n_pad, -1)
    for (rows, cols, _), p_out in zip(edge_parts, p_outs):
        y = y + jax.ops.segment_sum(h[cols] * p_out[:, None], rows,
                                    num_segments=dec.n_pad,
                                    indices_are_sorted=True)
    return (y / z[:, None]).astype(x.dtype) + params["b"]


# ---------------------------------------------------------------------------
# non-sum aggregation operators (paper §2.1: aggregate-max / aggregate-mean)
# ---------------------------------------------------------------------------

def aggregate_mean(dec: Decomposed, x: jax.Array, inv_deg: jax.Array,
                   kernels: Sequence[str] = DEFAULT_KERNELS) -> jax.Array:
    """mean = sum x (1/deg): reuses the full adaptive sum machinery (the
    dense MXU path stays available)."""
    return aggregate(dec, x, kernels) * inv_deg[:, None]


def aggregate_max(dec: Decomposed, x: jax.Array) -> jax.Array:
    """aggregate-max over in-neighbors of all subgraphs.

    max is not a matmul, so the dense-block MXU candidate does not exist on
    TPU (faithful hardware note: the paper's dense kernel is equivalent to
    aggregation only for sum, §3.2); every subgraph runs the segment/gather
    paths, joined by an elementwise max.  Rows with no neighbors return 0
    (GNN convention)."""
    neg = jnp.float32(-3.4e38)
    # intra via masked ELL gather
    ell = dec.intra.formats["ell"]
    g_in = jnp.where(ell.mask[..., None], x[ell.indices], neg)
    m = jnp.max(g_in, axis=1)                            # (n_pad, F)
    # inter buckets via segment_max over edges
    for sub in dec.inters:
        coo = sub.formats["coo"]
        m_out = jax.ops.segment_max(x[coo.cols], coo.rows,
                                    num_segments=dec.n_pad,
                                    indices_are_sorted=True)
        m = jnp.maximum(m, m_out)
    return jnp.where(m <= neg / 2, 0.0, m).astype(x.dtype)
