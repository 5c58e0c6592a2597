"""The reduction from a profiler trace to busy, window, op and gap times."""
from pathlib import Path

import pytest

from bench import tracing

FIXTURE = Path(__file__).parent / "data" / "tpu_profile.xplane.pb"
MS = 1_000_000


def test_reduce_by_hand():
    tr = tracing.Trace(
        device_ops=[[("fusion.1", 0 * MS, 4 * MS),
                     ("custom-call.2", 3 * MS, 6 * MS),   # overlaps fusion.1
                     ("fusion.1", 8 * MS, 9 * MS),
                     ("fusion.3", 12 * MS, 15 * MS)]],    # past the window
        spans=[(tracing.WINDOW, 1 * MS, 11 * MS),
               ("bench.step", 1 * MS, 7 * MS),
               ("bench.fetch_loss", 6 * MS, 8 * MS),
               ("bench.step", 8 * MS, 10 * MS)])
    r = tracing.reduce(tr)
    assert r["window_s"] == pytest.approx(0.010)
    # busy inside [1, 11] ms: [1, 6] and [8, 9]
    assert r["busy_s"] == pytest.approx(0.006)
    # only ops wholly inside the window count: fusion.1 [0, 4] does not
    assert r["op_s"] == pytest.approx({"fusion.1": 0.001,
                                       "custom-call.2": 0.003})
    assert [n for n, _ in r["top_ops"]] == ["custom-call.2", "fusion.1"]
    # gaps: [6, 8] midpoint 7 under fetch_loss (the innermost span),
    # [9, 11] midpoint 10 under no step (the step ended at 10)
    assert dict(r["gaps"]) == pytest.approx({"bench.fetch_loss": 0.002,
                                             "host.other": 0.002})


def test_nested_operations_count_once():
    tr = tracing.Trace(
        device_ops=[[("while.1", 0, 10 * MS), ("body.2", 1 * MS, 4 * MS),
                     ("body.2", 5 * MS, 9 * MS), ("inner.3", 6 * MS, 7 * MS),
                     ("after.4", 10 * MS, 12 * MS)]],
        spans=[(tracing.WINDOW, 0, 12 * MS)])
    r = tracing.reduce(tr)
    assert r["op_s"] == pytest.approx({"while.1": 0.003, "body.2": 0.006,
                                       "inner.3": 0.001, "after.4": 0.002})
    assert sum(r["op_s"].values()) == pytest.approx(r["busy_s"])


def test_busy_averages_over_devices():
    tr = tracing.Trace(device_ops=[[("a", 0, 10 * MS)], [("a", 0, 5 * MS)]],
                       spans=[(tracing.WINDOW, 0, 10 * MS)])
    r = tracing.reduce(tr)
    assert r["busy_s"] == pytest.approx(0.0075)
    assert r["op_s"]["a"] == pytest.approx(0.015)


def test_no_window_or_no_device_reads_nothing():
    assert tracing.reduce(tracing.Trace(device_ops=[[("a", 0, 1)]])) is None
    assert tracing.reduce(tracing.Trace(
        device_ops=[[]], spans=[(tracing.WINDOW, 0, 1)])) is None


def test_recorded_chip_trace():
    """A trace recorded on one TPU v5e: four steps of a small jitted
    function with a Pallas kernel, each step annotated, in one window."""
    from jax.profiler import ProfileData
    tr = tracing.from_profile(ProfileData.from_file(str(FIXTURE)))
    r = tracing.reduce(tr)
    assert r is not None
    assert 0 < r["busy_s"] < r["window_s"]
    assert sum(r["op_s"].values()) <= r["window_s"]
    labels = dict(r["gaps"])
    assert "bench.host_wait" in labels
    assert labels["bench.host_wait"] > 0.015


def test_short_name():
    assert tracing.short_name(
        "%fusion.9 = f32[88784,256]{1,0:T(8,128)} fusion(f32[88784,256]"
        "{1,0:T(8,128)} %bitcast.31), kind=kCustom") == \
        "fusion.9 fusion f32[88784,256]"
    assert tracing.short_name(
        '%step.1 = f32[512,256]{1,0:T(8,128)S(1)} custom-call(f32[512,256]'
        '{1,0:T(8,128)} %x.1), custom_call_target="tpu_custom_call"') == \
        "step.1 tpu_custom_call f32[512,256]"


def test_aggregation_ops_are_pallas_kernels_and_edge_length_ops():
    from bench.metrics_lib import is_aggregation
    edges = [1553849]
    assert is_aggregation('%k.1 = f32[8,16]{1,0} custom-call(f32[8,16] %x), '
                          'custom_call_target="tpu_custom_call"', edges)
    assert is_aggregation("%fusion.114 = s32[1553849]{0} fusion(s32[88785]"
                          "{0} %a, s32[1554432]{0} %b)", edges)
    assert is_aggregation("%fusion.6 = f32[1554432,256]{1,0} fusion()", edges)
    assert not is_aggregation("%convolution.3 = f32[88784,256]{1,0} "
                              "convolution(f32[88784,128] %x)", edges)
