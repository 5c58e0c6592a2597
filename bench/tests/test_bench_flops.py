"""Operation and byte counts on a hand-sized graph."""
import pytest

from bench import flops

PEAKS = dict(flops_per_s=1e12, hbm_bytes_per_s=1e9)


def test_gcn_counts_by_hand():
    # 4 nodes, 6 edges (self-loops included), widths 3 -> 2 -> 5
    w = flops.gcn_step(4, 6, 3, 2, 2, 5)
    # layer 0: XW 2*4*3*2=48, dW 48 (no input gradient); layer 1: 2*4*2*5=80
    # times 3 (XW, dW, dX)
    assert w["dense_flops"] == 48 * 2 + 80 * 3
    # A(XW) and its transpose per layer: 2 * 2*6*d
    assert w["agg_flops"] == 2 * 2 * 6 * 2 + 2 * 2 * 6 * 5
    assert w["flops"] == w["dense_flops"] + w["agg_flops"]
    assert w["agg_passes"] == [2, 2, 5, 5]


def test_sage_counts_by_hand():
    w = flops.sage_step(4, 6, 3, 2, 2, 5)
    # two weights per layer
    assert w["dense_flops"] == 2 * 48 * 2 + 2 * 80 * 3
    # the mean is taken at the narrower width: 2 and 2
    assert w["agg_passes"] == [2, 2, 2, 2]
    assert w["agg_flops"] == 4 * (2 * 6 * 2)


def test_counts_do_not_depend_on_padding_or_plan():
    assert flops.gcn_step(10, 20, 8, 8, 3, 4) == flops.gcn_step(
        10, 20, 8, 8, 3, 4)


def test_agg_least_seconds_takes_the_slower_bound():
    # bytes: 4*(2*4*2) + 8*6 + 4*5 = 132 per pass at width 2
    assert flops.agg_pass_bytes(4, 6, 2) == 132
    t = flops.agg_least_seconds(4, 6, [2], PEAKS)
    assert t == pytest.approx(max(2 * 6 * 2 / 1e12, 132 / 1e9))
