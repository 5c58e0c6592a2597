"""Graphs for rows that ``graphgen.TABLE1`` lacks.

A traffic file's ``graph`` entry may carry ``stats``: the row's nodes,
listed edges, features and classes, as Table 1 gives them.  ``make``
builds that graph with ``graphgen``'s own generator and streams: the edges
from ``edge_seed`` through ``graphgen.community_edges``, the features and
labels from the run seed, each drawn as ``graphgen.synth`` draws them.  So
for a row ``TABLE1`` has, given with its stats, it is ``graphgen.make``'s
graph exactly.  An entry without ``stats`` goes to ``graphgen.make``.
"""
from __future__ import annotations

import numpy as np

from bench import graphgen


def make(g: dict, seed: int):
    """The graph a traffic file's ``graph`` entry names, for run ``seed``."""
    if "stats" not in g:
        return graphgen.make(g, seed)
    from repro.graphs.graph import Graph
    nv, ne, nf, nc = g["stats"]
    comm_size = g["comm_size"]
    n = max(int(nv * g["scale"]), 2 * comm_size)
    e = max(int(ne * g["scale"]), n)
    rng = np.random.default_rng(np.random.SeedSequence([g.get("edge_seed", 0),
                                                        0]))
    src, dst = graphgen.community_edges(n, e, comm_size, g["intra_frac"], rng)
    frng = np.random.default_rng(np.random.SeedSequence([seed, 1]))
    feats = (frng.standard_normal((n, nf), dtype=np.float32)
             * np.float32(0.1))
    labels = frng.integers(0, nc, n).astype(np.int32)
    return Graph(n, src, dst, feats, labels, nc, name=g["row"])
