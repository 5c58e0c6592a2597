"""Ahead-of-time compiles of every GNN Pallas kernel for a described TPU
v5e, with no chip attached: Mosaic (the TPU Pallas compiler) refuses an
unaligned block, a VMEM overrun or an unknown compiler parameter here, at
no chip time, instead of on the chip.

Widths are the chip smoke's (``chip_smoke.py``): f32, block size B = 16
(the community size), features 256 (hidden) and 512 (pubmed's 500 inputs
lane-padded).  The topology is described inside a module fixture — never
at import — because only one process at a time may load the TPU library;
under pytest-xdist only the worker that runs this file loads it.
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


@pytest.mark.parametrize("f", (256, 512))
@pytest.mark.parametrize("name", KERNELS)
def test_kernel_compiles_for_v5e(name, f, one_chip, no_persistent_cache):
    fn, specs = _case(name, f)
    args = [jax.ShapeDtypeStruct(*(s if isinstance(s[0], tuple) else (s, F32)),
                                 sharding=one_chip)
            for s in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _gcn_step_for_v5e(one_chip, monkeypatch):
    """The whole full-batch GCN train step compiled for v5e, as the chip
    runs it: Pallas compiled (not interpreted) and the accumulating
    dispatch on, at the published widths (500 inputs, hidden 256, 3
    layers) on a small graph, with a plan that puts every Pallas kernel
    family and its VJP in one program."""
    from repro.core import gnn
    from repro.core.plan import KernelPlan
    from repro.graphs import graph as G
    from repro.kernels import ops

    monkeypatch.setattr(ops, "_interpret", lambda: False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
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
    # each Pallas call lies under its tier's scope
    scopes = op_scopes(scoped)
    assert all(re.search(r"agg/(intra|inter\d)/", scopes[name])
               for name in calls_a)
