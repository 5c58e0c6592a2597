"""Each cell's control and planted faults, at a size a test run holds, come
out not correct: at least one of the cell's numbers passes its limit.  The
same readings at each cell's own size on the chip come from
``python3 bench/control.py``; PERF.md gives both.  The references' three
precisions rank as they should on a hand-sized graph."""
import argparse

import jax.numpy as jnp
import numpy as np
import pytest

from bench import compare, reference, run

CELLS = [w["name"] for w in run.json.loads(
    (run.ROOT / "BENCHMARK.json").read_text())["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("seed", [21, 2 ** 31 + 7])
def test_control_and_faults_fail(cell, seed):
    c = run.resolve(cell)
    mode = run._module(run.BENCH / "modes" / f"{c['traffic']['mode']}.py")
    ctx = run.Context(c, argparse.Namespace(seed=seed, seconds=1.0, trace=0,
                                            rehearse=True), None)
    readings = mode.control(ctx)
    assert "control" in readings and "stated_precision" in readings
    for variant, values in readings.items():
        if variant == "stated_precision":
            continue
        over = {k: v for k, v in values.items()
                if k in c["limits"] and v > c["limits"][k]}
        assert over, (variant, values, c["limits"])


def _graph(seed=3, n=300, e=3000, f=24):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, e).astype(np.int32)
    dst = rng.integers(0, n, e).astype(np.int32)
    x = jnp.asarray(rng.standard_normal((n, f)).astype(np.float32))
    labels = jnp.asarray(rng.integers(0, 5, n).astype(np.int32))
    return n, src, dst, x, labels


@pytest.mark.parametrize("model", ["gcn", "sage"])
def test_reference_precisions_rank(model):
    """bfloat16 products move the gradient less than float8 ones, and both
    move it: the stated precision lies between the reference and the
    control."""
    from bench import weights
    n, src, dst, x, labels = _graph()
    params = weights.init(weights.key(7), model, weights.dims(24, 32, 2, 5))
    mask = jnp.ones((n,), bool)
    grads = {}
    for p in reference.PRECISIONS:
        if model == "gcn":
            s, d, norm = reference.gcn_edges(n, src, dst)
            _, grads[p] = reference.gcn_loss_grad(
                params, x, jnp.asarray(s), jnp.asarray(d), jnp.asarray(norm),
                labels, mask, n=n, precision=p)
        else:
            _, grads[p] = reference.sage_loss_grad(
                params, x, jnp.asarray(src), jnp.asarray(dst),
                jnp.ones((len(src),), bool), labels, mask, precision=p)
    bf16 = compare.diff_gap(grads["bf16"], grads["highest"])
    fp8 = compare.diff_gap(grads["fp8"], grads["highest"])
    assert 0 < bf16 < fp8
