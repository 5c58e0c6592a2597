"""Ahead-of-time compiles of every GNN Pallas kernel for a described TPU
v5e, with no chip attached: Mosaic (the TPU Pallas compiler) refuses an
unaligned block, a VMEM overrun or an unknown compiler parameter here, at
no chip time, instead of on the chip.

Widths are the chip smoke's (``chip_smoke.py``): f32, block size B = 16
(the community size), features 256 (hidden) and 512 (pubmed's 500 inputs
lane-padded), and 64 (GIN's hidden width) as one whole block narrower
than the 128 lanes.  ``kernels.ops`` lane-pads GIN's 50 and 64 to 128
before any kernel sees them; the whole GIN step below compiles that path.
The topology is described inside a module fixture — never at import —
because only one process at a time may load the TPU library; under
pytest-xdist only the worker that runs this file loads it.
"""
from __future__ import annotations

import contextlib
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.bell_spmm import bell_spmm
from repro.kernels.bell_spmm_fused import bell_spmm_dw, bell_spmm_fused
from repro.kernels.block_diag_spmm import block_diag_spmm
from repro.kernels.block_diag_spmm_fused import (block_diag_spmm_dual,
                                                 block_diag_spmm_fused)
from repro.kernels.tcgnn_tile import (tcgnn_spmm, tcgnn_spmm_dw,
                                      tcgnn_spmm_fused)

B = 16          # community / block size
NB = 64         # block rows (compile cost does not depend on it)
K = 4           # stored blocks per blocked-ELL block row
C = 256         # condensed tcgnn columns per block row
N = NB * B
F32 = jnp.float32
I32 = jnp.int32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep it out of the cache
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _case(name: str, f: int):
    """(kernel callable, operand shapes) for one kernel at feature width f;
    every call passes interpret=False itself."""
    kw = dict(interpret=False)
    cases = {
        "block_diag": (lambda a, x: block_diag_spmm(a, x, f_tile=f, **kw),
                       [(NB, B, B), (N, f)]),
        "block_diag_acc": (
            lambda a, x, y: block_diag_spmm(a, x, y, f_tile=f, **kw),
            [(NB, B, B), (N, f), (N, f)]),
        "block_diag_fused": (
            lambda a, x, w: block_diag_spmm_fused(a, x, w, f_tile=256, **kw),
            [(NB, B, B), (N, f), (f, 256)]),
        "block_diag_fused_acc": (
            lambda a, x, w, y: block_diag_spmm_fused(a, x, w, y, f_tile=256,
                                                     **kw),
            [(NB, B, B), (N, f), (f, 256), (N, 256)]),
        "block_diag_dual": (
            lambda a, x, w, ws: block_diag_spmm_dual(a, x, w, ws, f_tile=256,
                                                     **kw),
            [(NB, B, B), (N, f), (f, 256), (f, 256)]),
        "block_diag_dual_acc": (
            lambda a, x, w, ws, y: block_diag_spmm_dual(a, x, w, ws, y,
                                                        f_tile=256, **kw),
            [(NB, B, B), (N, f), (f, 256), (f, 256), (N, 256)]),
        "bell": (lambda a, i, x: bell_spmm(a, i, x, f_tile=f, **kw),
                 [(NB, K, B, B), ((NB, K), I32), (N, f)]),
        "bell_acc": (lambda a, i, x, y: bell_spmm(a, i, x, y, f_tile=f, **kw),
                     [(NB, K, B, B), ((NB, K), I32), (N, f), (N, f)]),
        "bell_fused": (
            lambda a, i, x, w: bell_spmm_fused(a, i, x, w, f_tile=256, **kw),
            [(NB, K, B, B), ((NB, K), I32), (N, f), (f, 256)]),
        "bell_fused_acc": (
            lambda a, i, x, w, y: bell_spmm_fused(a, i, x, w, y, f_tile=256,
                                                  **kw),
            [(NB, K, B, B), ((NB, K), I32), (N, f), (f, 256), (N, 256)]),
        "bell_spmm_dw": (
            lambda a, i, x, g: bell_spmm_dw(a, i, x, g, fi_tile=f,
                                            fo_tile=256, **kw),
            [(NB, K, B, B), ((NB, K), I32), (N, f), (N, 256)]),
        "tcgnn_tile": (
            lambda t, xg: tcgnn_spmm(t, xg, f_tile=f, c_tile=C, **kw),
            [(NB, B, C), (NB, C, f)]),
        "tcgnn_tile_acc": (
            lambda t, xg, y: tcgnn_spmm(t, xg, y, f_tile=f, c_tile=C, **kw),
            [(NB, B, C), (NB, C, f), (N, f)]),
        "tcgnn_tile_fused": (
            lambda t, xg, w: tcgnn_spmm_fused(t, xg, w, f_tile=256, c_tile=C,
                                              **kw),
            [(NB, B, C), (NB, C, f), (f, 256)]),
        "tcgnn_tile_fused_acc": (
            lambda t, xg, w, y: tcgnn_spmm_fused(t, xg, w, y, f_tile=256,
                                                 c_tile=C, **kw),
            [(NB, B, C), (NB, C, f), (f, 256), (N, 256)]),
        "tcgnn_spmm_dw": (
            lambda t, gg, x: tcgnn_spmm_dw(t, gg, x, fi_tile=f, fo_tile=256,
                                           c_tile=C, **kw),
            [(NB, B, C), (NB, C, 256), (N, f)]),
    }
    return cases[name]


KERNELS = ("block_diag", "block_diag_acc", "block_diag_fused",
           "block_diag_fused_acc", "block_diag_dual", "block_diag_dual_acc",
           "bell", "bell_acc", "bell_fused", "bell_fused_acc", "bell_spmm_dw",
           "tcgnn_tile", "tcgnn_tile_acc", "tcgnn_tile_fused",
           "tcgnn_tile_fused_acc", "tcgnn_spmm_dw")


# widths below the 128 lanes that the gin and mb cells run: 50 (PPI's
# inputs), 22 and 39 (amazon0505's and blogcatalog's classes)
NARROW = (22, 39, 50)


def _narrow_case(name: str, f: int):
    """(callable, operand shapes) for a ``block_diag`` kernel at a narrow
    width, through the ``kernels.ops`` wrapper that lane-pads it, as the
    chip runs it: ``f`` is the aggregated width, the fused kernels'
    output (their input is 256 wide, as in the cells' last layers)."""
    from repro.kernels import ops
    wide = ("_fused" in name or "_dual" in name)
    fi = 256 if wide else f
    cases = {
        "block_diag": (ops.block_diag_matvec, [(NB, B, B), (N, f)]),
        "block_diag_acc": (ops.block_diag_matvec_acc,
                           [(NB, B, B), (N, f), (N, f)]),
        "block_diag_fused": (ops.block_diag_fused_matvec,
                             [(NB, B, B), (N, fi), (fi, f)]),
        "block_diag_fused_acc": (ops.block_diag_fused_matvec_acc,
                                 [(NB, B, B), (N, fi), (fi, f), (N, f)]),
        "block_diag_dual": (ops.block_diag_dual_matvec,
                            [(NB, B, B), (N, fi), (fi, f), (fi, f)]),
        "block_diag_dual_acc": (ops.block_diag_dual_matvec_acc,
                                [(NB, B, B), (N, fi), (fi, f), (fi, f),
                                 (N, f)]),
    }
    return cases[name]


CASES = ([(name, f) for name in KERNELS for f in (64, 256, 512)]
         + [(name, f) for name in KERNELS[:6] for f in NARROW])


@pytest.mark.parametrize("name,f", CASES)
def test_kernel_compiles_for_v5e(name, f, one_chip, no_persistent_cache,
                                 monkeypatch):
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    fn, specs = (_narrow_case if f in NARROW else _case)(name, f)
    args = [jax.ShapeDtypeStruct(*(s if isinstance(s[0], tuple) else (s, F32)),
                                 sharding=one_chip)
            for s in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _csr_spec(one_chip, n, nnz):
    from repro.core import formats

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return formats.CSR(n, n, spec((n + 1,), I32), spec((nnz,), I32),
                       spec((nnz,), F32))


def test_csr_expansion_has_no_loop_for_v5e(one_chip, no_persistent_cache,
                                           monkeypatch):
    """``csr_matvec`` at the full-batch blogcatalog cell's inter-tier shape
    (88,784 rows, 1,556,811 edges, 256 features) compiles to straight-line
    code: the row-pointer expansion is a scatter and a prefix sum, not a
    binary search's ``while`` loop over every edge."""
    from repro.kernels import ops
    from repro.kernels.csr import csr_matvec

    monkeypatch.setattr(ops, "_interpret", lambda: False)
    n, nnz, f = 88_784, 1_556_811, 256
    x = jax.ShapeDtypeStruct((n, f), F32, sharding=one_chip)
    text = jax.jit(csr_matvec).lower(_csr_spec(one_chip, n, nnz),
                                     x).compile().as_text()
    assert not re.search(r"\bwhile\(", text)


_DEF = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (\w+\[[\d,]*\])", re.M)
_SCATTER = re.compile(r"^\s*(?:ROOT )?%[\w.\-]+ = \S+ scatter\(([^)]*)\)",
                      re.M)


def _scatter_update_shapes(text: str) -> list:
    """The shape of every scatter's updates operand (its last) in an
    optimized HLO module, looked up by the operand's instruction name."""
    shapes = dict(_DEF.findall(text))
    return [shapes[re.findall(r"%([\w.\-]+)", m.group(1))[-1]]
            for m in _SCATTER.finditer(text)]


@pytest.mark.parametrize("n,nnz,f", [
    (88_784, 1_556_811, 256), (88_784, 1_556_811, 39),   # gcn full cell
    (56_944, 652_045, 64), (56_944, 652_045, 50)])       # gin cell
def test_csr_reduce_compiles_for_v5e(n, nnz, f, one_chip,
                                     no_persistent_cache, monkeypatch):
    """``csr_matvec`` and its gradient at the full-batch cells' inter-tier
    sizes: each direction reduces in the Pallas row-tile kernel, and no
    scatter takes an edge-length update (XLA's segment-sum scatter-add and
    the gather's transpose are gone; the row-pointer expansion scatters
    one mark per row)."""
    from repro.kernels import ops
    from repro.kernels.csr import csr_matvec
    from repro.obs import op_scopes

    monkeypatch.setattr(ops, "_interpret", lambda: False)
    x = jax.ShapeDtypeStruct((n, f), F32, sharding=one_chip)
    compiled = jax.jit(jax.value_and_grad(
        lambda x, a: jnp.sum(csr_matvec(a, x) ** 2))).lower(
            x, _csr_spec(one_chip, n, nnz)).compile()
    text = compiled.as_text()
    scopes = op_scopes(compiled)
    calls = PALLAS_CALL.findall(text)
    assert sorted(re.search(r"\b(reduce(?:_t)?)\)*/jit", scopes[c]).group(1)
                  for c in calls) == ["reduce", "reduce_t"]
    updates = _scatter_update_shapes(text)
    assert updates            # the expansions' scatters are found
    assert not any(str(nnz) in shape for shape in updates), updates


def _gcn_step_for_v5e(one_chip, monkeypatch):
    """The whole full-batch GCN train step compiled for v5e, as the chip
    runs it: Pallas compiled (not interpreted), at the published widths
    (500 inputs, hidden 256, 3 layers) on a small graph, with a plan that
    puts every Pallas kernel family and its VJP in one program."""
    from repro.core import gnn
    from repro.core.plan import KernelPlan
    from repro.graphs import graph as G
    from repro.kernels import ops

    monkeypatch.setattr(ops, "_interpret", lambda: False)
    g = G.synth_dataset("pubmed", scale=0.02, seed=0)
    cfg = gnn.GNNConfig(model="gcn", hidden=256, n_layers=3,
                        inter_buckets=2, selector="fixed")
    dec = gnn.prepare(g, cfg)
    plan = KernelPlan.make(dec, [
        ("block_diag_fused", "bell_fused", "tcgnn_tile_fused"),
        ("block_diag", "bell", "tcgnn_tile"),
        ("block_diag", "csr", "bell_fused")])
    params = gnn.init_model(jax.random.PRNGKey(0), cfg, 500, g.n_classes)
    n = dec.n_pad
    args = (params, gnn._adam_init(params), dec,
            jnp.zeros((n, 500), F32), jnp.zeros((n,), I32),
            jnp.zeros((n,), bool), jnp.zeros((n,), F32))
    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        args)
    return gnn.make_train_step(cfg, plan).lower(*shapes).compile()


def test_gcn_train_step_compiles_for_v5e(one_chip, no_persistent_cache,
                                         monkeypatch):
    """The whole full-batch train step, as the chip runs it, with every
    Pallas kernel family and its VJP in one program."""
    compiled = _gcn_step_for_v5e(one_chip, monkeypatch)
    assert compiled.as_text().count("tpu_custom_call") >= 6


PALLAS_CALL = re.compile(r'^\s*(?:ROOT )?%([\w.\-]+) = .*'
                         r'custom_call_target="tpu_custom_call"', re.M)


def _same_program(scoped, bare) -> list:
    """Assert two compiled steps are the same program, op for op, fusion
    for fusion, under the same instruction names, once each op's metadata
    is set aside and each Pallas call is named as in ``scoped``.  Returns
    the Pallas calls' instruction names in ``scoped``."""
    def strip(text):
        # the computations' instructions, without their source metadata
        # (the module's source-location tables go too)
        body = "\n".join(line for line in text.splitlines()
                         if re.match(r"\s*(ROOT |ENTRY )?%", line))
        return re.sub(r", metadata=\{[^}]*\}", "", body)

    a, b = strip(scoped.as_text()), strip(bare.as_text())
    calls_a, calls_b = PALLAS_CALL.findall(a), PALLAS_CALL.findall(b)
    assert len(calls_a) == len(calls_b) >= 6
    for old, new in zip(calls_b, calls_a):
        b = re.sub(r"(?<![\w.\-])%" + re.escape(old) + r"(?![\w.\-])",
                   "%" + new, b)
    assert a == b
    return calls_a


def test_tier_scopes_change_no_op_for_v5e(one_chip, no_persistent_cache,
                                          monkeypatch):
    """The ``agg/<tier>/<kernel>`` scopes are metadata: with and without
    them the v5e step is the same program, op for op, fusion for fusion,
    under the same instruction names, once each op's metadata is set
    aside.  The one exception is a Pallas call's instruction name, which
    XLA takes from the innermost name-stack component: with a scope
    inside the transform it loses its transform prefix
    (``jvp_jit_bell_spmm__.2`` becomes ``bell_spmm.2``)."""
    from repro.core import adaptgear
    from repro.obs import op_scopes

    scoped = _gcn_step_for_v5e(one_chip, monkeypatch)
    monkeypatch.setattr(adaptgear, "tier_scope",
                        lambda sub, kernel: contextlib.nullcontext())
    bare = _gcn_step_for_v5e(one_chip, monkeypatch)
    calls = _same_program(scoped, bare)
    # each Pallas call lies under its tier's scope
    scopes = op_scopes(scoped)
    assert all(re.search(r"agg/(intra|inter\d)/", scopes[name])
               for name in calls)


def _gin_step_for_v5e(one_chip, gin_scopes: bool = True):
    """The whole full-batch GIN train step compiled for v5e, as the chip
    runs it, at the cell's widths: 50 inputs, 5 layers of hidden 64, 121
    classes.  Layer 0 aggregates its 50-wide input (aggregate-first), the
    others their 64-wide ``X W1`` (transform-first); the plan puts every
    Pallas kernel family, fused and unfused, and its VJP in one program.
    ``gin_scopes=False`` compiles it without the ``gin/*`` scopes."""
    from repro.core import gnn
    from repro.core.plan import KernelPlan
    from repro.graphs import graph as G
    from repro.kernels import ops

    named_scope = jax.named_scope

    def scope(name):
        if not gin_scopes and name.startswith("gin/"):
            return contextlib.nullcontext()
        return named_scope(name)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "_interpret", lambda: False)
        mp.setattr(jax, "named_scope", scope)
        g = G.synth_dataset("pubmed", scale=0.02, seed=0)
        cfg = gnn.GNNConfig(model="gin", hidden=64, n_layers=5,
                            inter_buckets=2, selector="fixed")
        dec = gnn.prepare(g, cfg)
        eps = gnn.layer_epilogues(cfg, 50, 121)
        assert [e.structure for e in eps] == (["aggregate_first"]
                                              + ["transform_first"] * 4)
        plan = KernelPlan.make(dec, [
            ("block_diag", "bell", "tcgnn_tile"),
            ("block_diag_fused", "bell_fused", "tcgnn_tile_fused"),
            ("block_diag", "csr", "bell_fused"),
            ("block_diag", "bell", "tcgnn_tile"),
            ("block_diag_fused", "csr_fused", "bell")], epilogues=eps)
        params = gnn.init_model(jax.random.PRNGKey(0), cfg, 50, 121)
        n = dec.n_pad
        args = (params, gnn._adam_init(params), dec,
                jnp.zeros((n, 50), F32), jnp.zeros((n,), I32),
                jnp.zeros((n,), bool), jnp.zeros((n,), F32))
        shapes = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), args)
        return gnn.make_train_step(cfg, plan).lower(*shapes).compile()


@pytest.fixture(scope="module")
def gin_step(one_chip, no_persistent_cache):
    return _gin_step_for_v5e(one_chip)


def test_gin_train_step_compiles_for_v5e(gin_step):
    """The GIN step at 50 -> 64 x 5 -> 121, every Pallas family and its
    VJP in one program, fused and unfused, at widths 50 and 64."""
    assert gin_step.as_text().count("tpu_custom_call") >= 6


def test_gin_scopes_change_no_op_for_v5e(gin_step, one_chip,
                                         no_persistent_cache):
    """``gin/self`` and ``gin/mlp`` are metadata: the v5e GIN step is the
    same program with and without them.  Both scopes reach the compiled
    step's op_names, and no Pallas call (every one is an aggregation)
    lies under either."""
    from repro.obs import op_scopes

    bare = _gin_step_for_v5e(one_chip, gin_scopes=False)
    calls = _same_program(gin_step, bare)
    scopes = op_scopes(gin_step)
    assert any("gin/self" in s for s in scopes.values())
    assert any("gin/mlp" in s for s in scopes.values())
    assert not any("gin/" in scopes[name] for name in calls)
    assert all("agg/" in scopes[name] for name in calls)


def test_gin_csr_reduces_lie_under_agg_for_v5e(gin_step):
    """Every call of the ``csr`` kernels' sorted reduce in the v5e GIN
    step, forward and transposed, lies under its tier's ``agg/`` scope,
    as ``reduce`` or ``reduce_t``: the device time of both directions
    counts as aggregation."""
    from repro.obs import op_scopes

    scopes = op_scopes(gin_step)
    reduces = [scopes[name] for name in PALLAS_CALL.findall(
        gin_step.as_text()) if "csr_sorted_reduce" in scopes[name]]
    where = sorted(re.search(r"agg/\w+/(csr\w*)\)*/(reduce(?:_t)?)/",
                             s).groups() for s in reduces)
    # layer 2 commits csr (forward and transposed reduce), layer 4
    # csr_fused (forward reduce; its backward is XLA's gather transpose)
    assert where == [("csr", "reduce"), ("csr", "reduce_t"),
                     ("csr_fused", "reduce")]
