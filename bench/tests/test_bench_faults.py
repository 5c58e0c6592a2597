"""A whole run at a small size on the CPU, past the look for a chip: sound,
it comes out correct; with the timed path broken underneath in one of the
ways each cell can break, it comes out not correct."""
import json

import jax.numpy as jnp
import pytest

from bench import run

FULL = "gcn-256x3.full-cm.blogcatalog"
MB = "sage-256x2.mb.amazon0505"


def result(capsys, cell, seed=5):
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                   "0.5", "--rehearse"])
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["rehearsal"] is True
    return line


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


def fewer_neighbours(monkeypatch):
    from repro.sampling import sampler
    draw = sampler.NeighborSampler._sample_neighbors

    def short(self, v, fanout, rng):
        nbr = draw(self, v, fanout, rng)
        return nbr[:-1] if len(nbr) > 1 else nbr
    monkeypatch.setattr(sampler.NeighborSampler, "_sample_neighbors", short)


def fewer_seeds(monkeypatch):
    from repro.sampling import sampler
    draw = sampler.NeighborSampler._draw_seeds
    monkeypatch.setattr(sampler.NeighborSampler, "_draw_seeds",
                        lambda self: draw(self)[:-1])


@pytest.mark.parametrize("cell", [FULL, MB])
def test_sound_run_is_correct(capsys, cell):
    line = result(capsys, cell)
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("cell,fault", [
    (FULL, state_unchanged), (FULL, half_batch),
    (MB, state_unchanged), (MB, half_batch),
    (MB, fewer_neighbours), (MB, fewer_seeds)])
def test_broken_run_is_not_correct(capsys, monkeypatch, cell, fault):
    fault(monkeypatch)
    line = result(capsys, cell)
    assert line["correct"] is False, line["checks"]
