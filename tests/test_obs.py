"""Telemetry subsystem (repro.obs): Chrome-trace validity with nested +
thread-attributed spans, metrics-registry thread safety under racing
workers, the selector-audit calibration report and JSONL export, the
null-object disabled path, verbose-logging idempotence, and the
non-interference contract — telemetry on vs off leaves losses, plans,
hit history, and trace counts bit-identical."""
import dataclasses
import json
import logging
import threading
import time

import numpy as np
import pytest

from repro.core import gnn
from repro.graphs import graph as G
from repro.obs import (NULL_AUDIT, NULL_TRACER, Counter, Histogram,
                       MetricsRegistry, SelectorAudit, Telemetry, Tracer,
                       enable_verbose)
from repro.train import gnn_steps


def small_graph(n=96, e=700, nf=5, nc=3, seed=0):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, e).astype(np.int32)
    dst = rng.integers(0, n, e).astype(np.int32)
    key = src.astype(np.int64) * n + dst
    _, keep = np.unique(key, return_index=True)
    src, dst = src[keep], dst[keep]
    feats = rng.standard_normal((n, nf)).astype(np.float32)
    labels = rng.integers(0, nc, n).astype(np.int32)
    return G.Graph(n, src, dst, feats, labels, nc)


# -- tracer ------------------------------------------------------------------

def test_tracer_nested_spans_and_chrome_trace_shape():
    tr = Tracer()
    with tr.span("outer", cat="host", index=0):
        with tr.span("inner", cat="host"):
            time.sleep(0.001)
    tr.instant("marker", cat="cache", what="x")
    doc = tr.chrome_trace()
    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    xs = {e["name"]: e for e in evs if e["ph"] == "X"}
    inst = [e for e in evs if e["ph"] == "i"]
    assert [m["name"] for m in meta] == ["thread_name"]
    assert meta[0]["args"]["name"] == threading.current_thread().name
    assert set(xs) == {"outer", "inner"}
    assert len(inst) == 1 and inst[0]["name"] == "marker"
    # nesting: inner lies inside outer on the same (remapped, small) tid
    out, inn = xs["outer"], xs["inner"]
    assert out["tid"] == inn["tid"] == 0
    assert out["ts"] <= inn["ts"]
    assert inn["ts"] + inn["dur"] <= out["ts"] + out["dur"] + 1e-3
    assert out["args"] == dict(index=0)
    # the whole document round-trips through JSON
    json.loads(json.dumps(doc))


def test_tracer_attributes_spans_to_emitting_thread():
    tr = Tracer()

    def worker(i):
        with tr.span("work", cat="host", i=i):
            time.sleep(0.001)

    ts = [threading.Thread(target=worker, args=(i,), name=f"obs-worker-{i}")
          for i in range(3)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    doc = tr.chrome_trace()
    names = {e["args"]["name"] for e in doc["traceEvents"]
             if e["ph"] == "M"}
    assert {"obs-worker-0", "obs-worker-1", "obs-worker-2"} <= names
    tids = {e["tid"] for e in doc["traceEvents"] if e["ph"] == "X"}
    assert len(tids) == 3 and max(tids) <= 3      # tracer ids, not raw idents


def test_tracer_ids_survive_thread_ident_reuse():
    # one short-lived thread after another: the OS hands the next thread
    # the ident of the one that just exited, the tracer does not
    tr = Tracer()
    for i in range(6):
        t = threading.Thread(target=lambda: tr.instant("x"),
                             name=f"short-{i}")
        t.start()
        t.join()
    tids = [e[2] for e in tr.events()]
    assert tids == list(range(6))
    doc = tr.chrome_trace()
    names = sorted(e["args"]["name"] for e in doc["traceEvents"]
                   if e["ph"] == "M")
    assert names == [f"short-{i}" for i in range(6)]


def test_tracer_ids_exact_under_racing_threads():
    import sys
    tr = Tracer()
    n_threads, n_spans = 16, 200

    def worker():
        for i in range(n_spans):
            with tr.span("outer", index=i):
                with tr.span("inner", index=i):
                    pass

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        ts = [threading.Thread(target=worker) for _ in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in ts)
    events = tr.events()
    assert len(events) == 2 * n_threads * n_spans
    assert len({e[7] for e in events}) == len(events)      # span ids
    assert sorted({e[2] for e in events}) == list(range(n_threads))
    by_id = {e[7]: e for e in events}
    for e in events:
        if e[0] == "inner":       # the parent is its own thread's outer
            parent = by_id[e[8]]
            assert parent[0] == "outer" and parent[2] == e[2]
            assert parent[6] == e[6]
        else:
            assert e[8] is None


def test_tracer_spans_record_parent_index_and_cpu_time():
    tr = Tracer()
    with tr.span("outer", index=4):
        # long enough for a thread clock that ticks every 10 ms
        with tr.span("busy", index=4):
            t_end = time.perf_counter() + 0.1
            while time.perf_counter() < t_end:
                pass
        with tr.span("sleep"):
            time.sleep(0.05)
        tr.instant("mark")
    ev = {e[0]: e for e in tr.events()}
    # the tracer's own record keeps its leading fields' meaning
    assert all(e[5] is None or e[5] >= e[4] for e in ev.values())
    outer_id = ev["outer"][7]
    assert ev["outer"][8] is None
    assert ev["busy"][8] == ev["sleep"][8] == ev["mark"][8] == outer_id
    assert ev["busy"][6] == dict(index=4)
    wall = {k: e[5] - e[4] for k, e in ev.items() if e[5] is not None}
    cpu = {k: e[10] - e[9] for k, e in ev.items() if e[5] is not None}
    assert cpu["busy"] > 0.5 * wall["busy"]      # computing: CPU ~ wall
    assert cpu["sleep"] < 0.5 * wall["sleep"]    # waiting: no CPU
    assert ev["mark"][9] is None and ev["mark"][10] is None


def _host_events(log_dir):
    import glob

    from jax.profiler import ProfileData
    (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    return [(e.name, dict(e.stats))
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith("repro.")]


def test_tracer_spans_reach_the_profiler(tmp_path):
    import jax
    tr = Tracer()

    def worker():
        with tr.span("sample", index=7):
            with tr.span("build", index=7):
                pass
        tr.instant("mark")

    with tr.span("setup"):             # the main thread's first span: id 0
        pass
    jax.profiler.start_trace(str(tmp_path))
    try:
        t = threading.Thread(target=worker, name="pipeline-test-0")
        t.start()
        t.join()
        with NULL_TRACER.span("device_step", index=1):
            pass
        NULL_TRACER.instant("quiet")
    finally:
        jax.profiler.stop_trace()
    got = sorted(_host_events(str(tmp_path)))
    # one lane per tracer thread id (the line names say only "python3")
    assert got == [("repro.build", dict(thread=1, index=7)),
                   ("repro.mark", dict(thread=1)),
                   ("repro.sample", dict(thread=1, index=7))]


def test_minibatch_spans_share_batch_index_and_nest_on_consumer():
    g = small_graph(n=128, e=1200)
    cfg = gnn.GNNConfig(model="gcn", sampler="cluster", comm_size=8,
                        clusters_per_batch=4, inter_buckets=2,
                        reorder="bfs", prefetch_depth=2,
                        pipeline_workers=2)
    tele = Telemetry(enabled=True)
    gnn_steps.train_minibatch(g, cfg, steps=5, eval_batches=0,
                              telemetry=tele)
    events = tele.tracer.events()
    by_id = {e[7]: e for e in events}
    # one batch's stages carry the batch index the sampler drew it under
    idx = {}
    for e in events:
        if e[0] in ("sample", "build", "resolve", "finish", "device_step"):
            idx.setdefault(e[0], []).append(e[6]["index"])
    for name in ("sample", "build", "resolve", "finish", "device_step"):
        assert sorted(idx[name]) == list(range(5)), name
    # the consumer's step: dispatch and sync inside device_step, on the
    # thread that waits on the pipeline
    consumer = {e[2] for e in events if e[0] == "device_step"}
    assert len(consumer) == 1
    assert {e[2] for e in events if e[0] == "pipeline.get"} == consumer
    for e in events:
        if e[0] in ("step.dispatch", "step.sync"):
            assert by_id[e[8]][0] == "device_step"
            assert e[2] in consumer
    names = [e[0] for e in events]
    assert names.count("step.dispatch") == 5
    assert names.count("pipeline.get") == 5
    # the racing stages run on the workers, never on the consumer
    assert not {e[2] for e in events if e[0] in ("build", "finish")} \
        & consumer


def test_tracer_export_writes_valid_json(tmp_path):
    tr = Tracer()
    with tr.span("s"):
        pass
    path = tr.export(str(tmp_path / "trace.json"))
    with open(path) as f:
        doc = json.load(f)
    assert any(e["ph"] == "X" and e["name"] == "s" for e in doc["traceEvents"])


def test_null_tracer_is_shared_noop_and_refuses_export():
    s1 = NULL_TRACER.span("a", cat="x", k=1)
    s2 = NULL_TRACER.span("b")
    assert s1 is s2                      # one shared singleton, no alloc
    with s1:
        pass
    assert NULL_TRACER.events() == []
    assert not NULL_TRACER.enabled
    with pytest.raises(RuntimeError):
        NULL_TRACER.export("/tmp/nope.json")


# -- metrics registry --------------------------------------------------------

def test_registry_get_or_create_and_type_collision():
    reg = MetricsRegistry()
    c1 = reg.counter("a.hits")
    c2 = reg.counter("a.hits")
    assert c1 is c2
    with pytest.raises(TypeError):
        reg.gauge("a.hits")
    g = reg.gauge("a.depth")
    g.set(7)
    h = reg.histogram("a.lat")
    h.observe(1.0)
    snap = reg.snapshot()
    assert list(snap) == sorted(snap)
    assert snap["a.depth"] == 7
    assert snap["a.lat"]["count"] == 1


def test_counter_exact_under_racing_threads():
    # the bug class the registry exists for: CPython `x += 1` is not
    # atomic across threads, a locked Counter.inc is
    reg = MetricsRegistry()
    n_threads, n_inc = 8, 5000

    def worker():
        c = reg.counter("race")          # racing get-or-create too
        for _ in range(n_inc):
            c.inc()

    ts = [threading.Thread(target=worker) for _ in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert reg.counter("race").value == n_threads * n_inc


def test_histogram_window_and_percentiles():
    h = Histogram("lat", window=100)
    for v in range(1000):
        h.observe(float(v))
    assert h.count == 1000               # exact forever
    assert h.total == sum(range(1000))
    # percentiles over the last 100 observations only
    assert h.percentile(0) == 900.0
    assert h.percentile(100) == 999.0
    snap = h.snapshot()
    assert snap["p50"] == pytest.approx(950.0, abs=1)
    assert snap["max"] == 999.0


def test_counter_set_supports_restore():
    c = Counter("x")
    c.inc(3)
    c.set(11)
    assert c.value == 11


# -- selector audit ----------------------------------------------------------

def test_audit_calibration_and_jsonl_export(tmp_path):
    au = SelectorAudit()
    au.plan(sig="sig0", layers=[["csr", "bell"]], tiers=["intra", "inter0"],
            modeled_s=[[1e-4, 2e-4]], source="cost_model")
    au.probe(tier="intra", kernel="csr", modeled_s=1e-4, measured_s=2e-4)
    au.probe(tier="intra", kernel="csr", modeled_s=1e-4, measured_s=3e-4)
    au.quarantine(sig="sig0", kernels=["bell"], reason="nan")
    au.observe_step([["csr", "bell"]], 5e-4)
    au.observe_step([["csr", "bell"]], 7e-4)
    cal = au.calibration()
    k = cal["kernels"]["csr"]
    assert k["n"] == 2
    assert k["rel_err"] == pytest.approx(1.5)   # median of {1.0, 2.0}
    (p,) = cal["plans"]
    assert p["n_steps"] == 2
    assert p["observed_step_s"] == pytest.approx(6e-4)
    assert p["modeled_s"] == pytest.approx(3e-4)
    path = au.export_jsonl(str(tmp_path / "audit.jsonl"))
    with open(path) as f:
        recs = [json.loads(line) for line in f]
    by_event = {}
    for r in recs:
        by_event.setdefault(r["event"], []).append(r)
    assert len(by_event["plan"]) == 1
    assert len(by_event["probe"]) == 2
    assert len(by_event["quarantine"]) == 1
    assert len(by_event["calibration"]) == 1


def test_null_audit_noop_and_refuses_export():
    NULL_AUDIT.plan(sig="s", layers=[], tiers=[], modeled_s=[], source="x")
    NULL_AUDIT.observe_step([], 0.1)
    assert NULL_AUDIT.events() == []
    assert NULL_AUDIT.calibration() == dict(kernels={}, plans=[])
    with pytest.raises(RuntimeError):
        NULL_AUDIT.export_jsonl("/tmp/nope.jsonl")


# -- Telemetry facade + logging ----------------------------------------------

def test_telemetry_disabled_uses_null_singletons_live_registry():
    t = Telemetry()
    assert t.tracer is NULL_TRACER
    assert t.audit is NULL_AUDIT
    t.metrics.counter("c").inc()
    s = t.summary()
    assert s["enabled"] is False
    assert s["n_span_events"] == 0
    assert s["metrics"]["c"] == 1


def test_enable_verbose_is_idempotent():
    logger = logging.getLogger("repro.test_obs")
    before = len(logger.handlers)
    enable_verbose("repro.test_obs")
    enable_verbose("repro.test_obs")
    assert len(logger.handlers) == before + 1


# -- non-interference: telemetry on vs off, bit-identical training -----------

def _run(cfg, g, steps=6):
    return gnn_steps.train_minibatch(g, cfg, steps=steps, eval_batches=1)


def test_telemetry_on_off_training_bit_identical(tmp_path):
    g = small_graph(n=128, e=1200)
    # no probing here: probe pinning keys on wall-clock measurements, a
    # nondeterminism source of its own that would confound the on/off
    # plan-equality assertion (the probe audit has its own test below)
    base = gnn.GNNConfig(model="gcn", sampler="cluster", comm_size=8,
                         clusters_per_batch=4, inter_buckets=2,
                         reorder="bfs")
    off = _run(base, g)
    on = _run(dataclasses.replace(
        base, telemetry=True,
        trace_out=str(tmp_path / "trace.json"),
        telemetry_out=str(tmp_path / "audit.jsonl")), g)
    # recording is append-only and never read back: identical training
    assert np.array_equal(np.asarray(off.losses), np.asarray(on.losses))
    assert off.plans == on.plans
    assert off.hit_history == on.hit_history
    assert off.n_traces == on.n_traces
    # the off run carries a disabled summary, the on run a full one
    assert off.telemetry["enabled"] is False
    assert on.telemetry["enabled"] is True
    assert on.telemetry["n_span_events"] > 0
    # exports landed and parse; the trace covers the instrumented stages
    with open(tmp_path / "trace.json") as f:
        doc = json.load(f)
    names = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
    assert {"build", "resolve", "finish", "device_step"} <= names
    with open(tmp_path / "audit.jsonl") as f:
        recs = [json.loads(line) for line in f]
    events = {r["event"] for r in recs}
    assert {"plan", "calibration", "metrics"} <= events


def test_audit_logs_tcgnn_candidates(tmp_path):
    """The selector-audit receipt covers the condensed-tile kernel: a
    mini-batch run committing tcgnn_tile on the inter tiers leaves plan
    events that name it, each with a modeled cost per (layer, tier) —
    so calibration reports price the MXU-dense candidate like any other
    registry kernel (telemetry-smoke CI gate)."""
    g = small_graph(n=128, e=1200)
    cfg = gnn.GNNConfig(model="gin", sampler="cluster", comm_size=8,
                        clusters_per_batch=4, inter_buckets=2,
                        reorder="bfs", selector="fixed",
                        fixed_kernels=("block_diag", "tcgnn_tile"),
                        telemetry=True,
                        telemetry_out=str(tmp_path / "audit.jsonl"))
    res = _run(cfg, g)
    assert any("tcgnn_tile" in layer for plan in res.plans for layer in plan)
    with open(tmp_path / "audit.jsonl") as f:
        recs = [json.loads(line) for line in f]
    plans = [r for r in recs if r["event"] == "plan"]
    assert plans, "telemetry-enabled run must leave plan receipts"
    tc_plans = [p for p in plans
                if any("tcgnn_tile" in layer for layer in p["layers"])]
    assert tc_plans, "audit must log the condensed-tile kernel candidate"
    for p in tc_plans:
        # every committed choice is priced: one modeled cost per
        # (layer, tier), finite and positive for tcgnn_tile too
        assert len(p["modeled_s"]) == len(p["layers"])
        for layer, row in zip(p["layers"], p["modeled_s"]):
            assert len(row) == len(p["tiers"])
            for kernel, cost in zip(layer, row):
                assert np.isfinite(cost) and cost > 0.0, (kernel, cost)
        assert p["modeled_total_s"] > 0.0


def test_probe_audit_records_modeled_vs_measured():
    g = small_graph(n=128, e=1200)
    cfg = gnn.GNNConfig(model="gcn", sampler="cluster", comm_size=8,
                        clusters_per_batch=4, inter_buckets=2,
                        reorder="bfs", probe_every=1, telemetry=True)
    res = _run(cfg, g)
    cal = res.telemetry["calibration"]
    assert cal["kernels"], "probe-on-every-miss must calibrate kernels"
    for k in cal["kernels"].values():
        assert k["n"] >= 1
        assert k["measured_s"] > 0
        assert k["rel_err"] >= 0
    # every observed plan carries its mint-time modeled total
    assert cal["plans"]
    assert all(p["n_steps"] > 0 for p in cal["plans"])


def test_telemetry_on_off_identical_through_async_pipeline():
    g = small_graph(n=128, e=1200)
    base = gnn.GNNConfig(model="gin", sampler="cluster", comm_size=8,
                         clusters_per_batch=4, inter_buckets=2,
                         reorder="bfs", prefetch_depth=3,
                         pipeline_workers=2)
    off = _run(base, g, steps=8)
    on = _run(dataclasses.replace(base, telemetry=True), g, steps=8)
    assert np.array_equal(np.asarray(off.losses), np.asarray(on.losses))
    assert off.plans == on.plans
    assert off.hit_history == on.hit_history
    assert off.n_traces == on.n_traces
    assert on.telemetry["n_span_events"] > 0


# -- named scopes in the compiled step ---------------------------------------

def test_op_scopes_name_each_tier_and_change_no_result(monkeypatch):
    import contextlib
    import re

    from repro.core import adaptgear
    from repro.obs import op_scopes
    g = small_graph(n=128, e=1200)
    cfg = gnn.GNNConfig(model="gcn", hidden=16, n_layers=2, inter_buckets=2,
                        reorder="bfs", comm_size=8, selector="fixed",
                        fixed_kernels=("block_diag", "csr"))
    res = gnn.train(g, cfg, steps=3)
    scopes = op_scopes(res.step)
    tiers = {m.group(1) for name in scopes.values()
             for m in re.finditer(r"agg/(\w+)/(\w+)", name)}
    assert "intra" in tiers
    assert any(t.startswith("inter") for t in tiers)
    # the backward pass keeps the scope inside its transform
    assert any(re.search(r"transpose\(jvp\(agg/intra/block_diag\)\)", n)
               for n in scopes.values())
    # instruction names as a profile's XLA Ops events carry them
    assert all(re.fullmatch(r"[\w.\-]+", k) for k in scopes)
    # scopes are metadata only: the step without them trains identically
    monkeypatch.setattr(adaptgear, "tier_scope",
                        lambda sub, kernel: contextlib.nullcontext())
    bare = gnn.train(g, cfg, steps=3)
    assert bare.losses == res.losses
    assert not any("agg/" in n for n in op_scopes(bare.step).values())



def test_gin_structure_counters_count_committed_layers():
    """``gnn.train`` counts each committed GIN layer's structure once in
    the registry it is given, split as the plan's epilogues; without a
    registry it counts nowhere, and GCN counts nothing."""
    g = small_graph(nf=5)
    cfg = gnn.GNNConfig(model="gin", hidden=8, n_layers=3,
                        selector="cost_model")
    reg = MetricsRegistry()
    res = gnn.train(g, cfg, steps=1, metrics=reg)
    structures = [ep.structure for ep in res.plan.epilogues]
    assert structures[0] == "aggregate_first"      # 5 inputs < hidden 8
    assert structures[1:] == ["transform_first"] * 2
    snap = reg.snapshot()
    assert snap == {f"gin.structure.{s}": structures.count(s)
                    for s in set(structures)}
    assert sum(snap.values()) == cfg.n_layers
    gnn.train(g, cfg, steps=1, metrics=reg)
    assert reg.snapshot() == {k: 2 * v for k, v in snap.items()}
    gcn = MetricsRegistry()
    gnn.train(g, dataclasses.replace(cfg, model="gcn"), steps=1, metrics=gcn)
    assert gcn.snapshot() == {}
