"""The GIN cell's own files: the graph rows, the model file's work counts,
the readers of the compiled step's scopes, and whole rehearsal runs that
come out correct when sound and not correct when broken."""
import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import graphgen, graphrows, run
from bench.models import gin
from bench.modes import full_model

CELL = "gin-64x5.full-cm.ppi"
# the cell's per-layer metrics that read the traced window
READERS = ("agg_kernel_ms.gin", "agg_roofline.gin", "epilogue_ms.gin",
           "step_mfu.full", "device_idle_pct.full")


@pytest.mark.parametrize("row,scale", [("cora", 0.2),
                                       ("soc_blogcatalog", 0.004)])
def test_graph_row_with_its_stats_is_graphgen_graph(row, scale):
    g = dict(row=row, scale=scale, comm_size=16, intra_frac=0.6,
             edge_seed=2147483752)
    want = graphgen.make(g, 2 ** 31 + 5)
    for entry in (g, dict(g, stats=list(graphgen.TABLE1[row]))):
        got = graphrows.make(entry, 2 ** 31 + 5)
        assert got.n == want.n and got.n_classes == want.n_classes
        for a in ("senders", "receivers", "features", "labels"):
            np.testing.assert_array_equal(getattr(got, a), getattr(want, a))


def test_ppi_row_has_table1_widths():
    c = run.resolve(CELL)
    g = graphrows.make(dict(c["traffic"]["graph"], scale=0.01), 3)
    assert g.features.shape == (569, 50) and g.n_classes == 121
    assert 0.8 * 8187 <= g.n_edges <= 8187


def test_work_counts_by_hand():
    n, e, fin, h, fo = 10, 30, 3, 4, 5
    # transform-first, layer 0: X W1 and dW1 (no dX); h1 W2, dW2, dh1;
    # two passes at the hidden width
    dense, agg, passes = gin.layer_work(n, e, fin, h, fo, True,
                                        "transform_first")
    assert (dense, agg, passes) == (2 * (2 * 10 * 3 * 4)
                                    + 3 * (2 * 10 * 4 * 5),
                                    2 * (2 * 30 * 4), [4, 4])
    # aggregate-first, layer 0: z W1, dW1 and dz (eps's gradient); one
    # forward pass at the input width, no backward pass
    dense, agg, passes = gin.layer_work(n, e, fin, h, fo, True,
                                        "aggregate_first")
    assert (dense, agg, passes) == (3 * (2 * 10 * 3 * 4)
                                    + 3 * (2 * 10 * 4 * 5),
                                    2 * 30 * 3, [3])
    assert gin.layer_work(n, e, fin, h, fo, False,
                          "aggregate_first")[2] == [3, 3]
    assert gin.layer_work(n, e, fin, h, fo, False,
                          "transform_first")[0] == (3 * (2 * 10 * 3 * 4)
                                                    + 3 * (2 * 10 * 4 * 5))
    # the step takes each layer at its cheaper structure, whatever the plan
    a = gin.step_work(n, e, fin, h, 2, fo, ["aggregate_first"] * 2)
    b = gin.step_work(n, e, fin, h, 2, fo, ["transform_first"] * 2)
    assert a["flops"] == b["flops"]
    assert a["agg_passes"] == [3, 4, 4] and b["agg_passes"] == [4, 4, 4, 4]
    l0 = min(sum(gin.layer_work(n, e, fin, h, h, True, s)[:2])
             for s in gin.STRUCTURES)
    l1 = min(sum(gin.layer_work(n, e, h, h, fo, False, s)[:2])
             for s in gin.STRUCTURES)
    assert a["flops"] == l0 + l1 == a["dense_flops"] + a["agg_flops"]


def _out(steps=2, **info):
    base = dict(steps=steps, n_nodes=100, n_edges=1000,
                work=dict(agg_passes=[64, 64], flops=10 ** 9),
                window_flops=steps * 10 ** 9, select_s=0.5, compile_s=2.0)
    return dict(info=dict(base, **info))


def _ctx(reduced_trace=None):
    return argparse.Namespace(reduced_trace=reduced_trace,
                              peaks=run.load_peaks("TPU v5 lite"))


@pytest.mark.parametrize("name", READERS)
def test_readers_read_nothing_without_a_trace(name):
    reader = run._module(run.BENCH / "metrics" / f"{name}.py")
    assert reader.read(_ctx(), _out(op_scopes={"fusion.1": "agg/x"})) is None


def test_readers_put_ops_down_to_their_scopes():
    op_s = {"%fusion.1 = f32[100,64]{1,0} fusion(%p)": 0.004,
            "%bd.2 = f32[100,128]{1,0} custom-call(%a), "
            'custom_call_target="tpu_custom_call"': 0.002,
            "%fusion.3 = f32[100,64]{1,0} fusion(%q)": 0.001,
            "%fusion.4 = f32[100,64]{1,0} fusion(%r)": 0.0005,
            "%copy.5 = f32[100,64]{1,0} copy(%s)": 0.007}
    scopes = {"fusion.1": "jit(step)/jvp(agg/inter/csr)/gather",
              "bd.2": "jit(step)/transpose(jvp(agg/intra/block_diag))/x",
              "fusion.3": "jit(step)/jvp(gin/mlp)/dot_general",
              "fusion.4": "jit(step)/transpose(jvp(gin/self))/mul",
              "copy.5": "jit(step)/jvp(ingin/mlp_agg/x)/copy"}
    rt = dict(op_s=op_s, window_s=0.5, busy_s=0.4)
    ctx, out = _ctx(rt), _out(op_scopes=scopes)
    read = {n: run._module(run.BENCH / "metrics" / f"{n}.py").read(ctx, out)
            for n in READERS}
    assert read["agg_kernel_ms.gin"] == pytest.approx(3.0)
    assert read["epilogue_ms.gin"] == pytest.approx(0.75)
    from bench import flops
    least = flops.agg_least_seconds(100, 1000, [64, 64], ctx.peaks)
    assert read["agg_roofline.gin"] == pytest.approx(100 * least * 2 / 0.006)
    assert read["step_mfu.full"] == pytest.approx(
        100 * 2e9 / (0.5 * ctx.peaks["flops_per_s"]))
    assert read["device_idle_pct.full"] == pytest.approx(20.0)
    # a program without the gin scopes: the epilogue reads nothing
    bare = {k: v for k, v in scopes.items() if "agg/" in v}
    assert read["epilogue_ms.gin"] is not None
    assert run._module(run.BENCH / "metrics" / "epilogue_ms.gin.py").read(
        ctx, _out(op_scopes=bare)) is None


def test_cell_reports_every_metric_listed_for_it():
    """Every per-layer metric whose ``workloads`` name the cell reads a
    number from what ``full_model.run`` leaves in ``info`` and a trace."""
    bench = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed == set(READERS) | {"select_s.full", "compile_s.full"}
    rt = dict(op_s={"%fusion.1 = f32[100,64]{1,0} fusion(%p)": 0.004,
                    "%fusion.3 = f32[100,64]{1,0} fusion(%q)": 0.001},
              window_s=0.5, busy_s=0.4)
    scopes = {"fusion.1": "jit(step)/jvp(agg/inter/csr)/gather",
              "fusion.3": "jit(step)/jvp(gin/mlp)/dot_general"}
    for name in listed:
        reader = run._module(run.BENCH / "metrics" / f"{name}.py")
        assert reader.read(_ctx(rt), _out(op_scopes=scopes)) is not None, name


def test_probe_nodes_come_from_the_seed():
    a = full_model.probe_nodes(56944, 2 ** 31 + 3)
    assert a.sum() == full_model.PROBE_NODES
    np.testing.assert_array_equal(a, full_model.probe_nodes(56944,
                                                            2 ** 31 + 3))
    assert (a != full_model.probe_nodes(56944, 4)).any()
    assert full_model.probe_nodes(100, 1).all()


def result(capsys, seed=5):
    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                   "0.3", "--rehearse"])
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    line = json.loads(out[-1])
    assert line["rehearsal"] is True
    return line, "\n".join(out)


def test_sound_rehearsal_is_correct(capsys):
    line, out = result(capsys, seed=2 ** 31 + 11)
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert "structures ['aggregate_first', 'transform_first'" in out


def state_unchanged(monkeypatch):
    from repro.core import gnn
    monkeypatch.setattr(gnn, "_adam_update",
                        lambda params, grads, opt, lr: (params, opt))


def half_batch(monkeypatch):
    from repro.core import gnn
    loss = gnn._loss

    def half(params, cfg, dec, x, labels, mask, plan, inv_deg):
        keep = jnp.arange(mask.shape[0]) % 2 == 0
        return loss(params, cfg, dec, x, labels, mask & keep, plan, inv_deg)
    monkeypatch.setattr(gnn, "_loss", half)


def eps_frozen(monkeypatch):
    from repro.core import adaptgear
    conv = adaptgear.gin_conv

    def frozen(params, *a, **k):
        return conv(dict(params, eps=jax.lax.stop_gradient(params["eps"])),
                    *a, **k)
    monkeypatch.setattr(adaptgear, "gin_conv", frozen)


@pytest.mark.parametrize("fault", [state_unchanged, half_batch, eps_frozen])
def test_broken_rehearsal_is_not_correct(capsys, monkeypatch, fault):
    fault(monkeypatch)
    line, _ = result(capsys)
    assert line["correct"] is False, line["checks"]
