"""The benchmark's own graphs: Table-1 statistics and a planted-community
generator, kept here so that the yardstick does not move when the program's
generator does.  The result is the program's ``Graph`` container, built
from arrays made here alone.

The generator draws ``n_edges`` (src, dst) pairs, a share ``intra_frac``
inside communities of ``comm_size`` nodes hidden behind a random labelling,
and removes repeated pairs, so a graph keeps about 80-88% of the listed
edges.  Features are N(0, 0.1^2), labels uniform over the row's classes.

The edges are drawn from the traffic file's ``edge_seed`` (0 where it names
none) and are the same for every run seed, as a deployment serves one
graph: the program's tiers, and with them the shapes it compiles, follow
the edges, and two edge sets of one row can run at speeds 10% apart.  The
run seed draws the features and the labels.
"""
from __future__ import annotations

import numpy as np

# (#vertex, #edge, #feat, #class) from the AdaptGear paper's Table 1
TABLE1 = {
    "cora": (2708, 10556, 1433, 7),
    "pubmed": (19717, 99203, 500, 3),
    "soc_blogcatalog": (88784, 2093195, 128, 39),
    "amazon0505": (410236, 4878874, 96, 22),
}


def community_edges(n: int, n_edges: int, comm_size: int, intra_frac: float,
                    rng: np.random.Generator) -> tuple[np.ndarray, np.ndarray]:
    n_intra = int(n_edges * intra_frac)
    n_inter = n_edges - n_intra
    hide = rng.permutation(n)
    n_comm = max(n // comm_size, 1)
    base = rng.integers(0, n_comm, n_intra) * comm_size
    s_in = base + rng.integers(0, comm_size, n_intra)
    d_in = base + rng.integers(0, comm_size, n_intra)
    s_out = rng.integers(0, n, n_inter)
    d_out = rng.integers(0, n, n_inter)
    src = hide[np.concatenate([s_in, s_out]) % n]
    dst = hide[np.concatenate([d_in, d_out]) % n]
    _, keep = np.unique(src.astype(np.int64) * n + dst, return_index=True)
    return src[keep].astype(np.int32), dst[keep].astype(np.int32)


def synth(row: str, scale: float, seed: int, comm_size: int = 16,
          intra_frac: float = 0.6, edge_seed: int = 0):
    """A graph with the statistics of Table-1 ``row`` at ``scale``: edges
    drawn from ``edge_seed``, features and labels from ``seed`` (any
    non-negative integers)."""
    from repro.graphs.graph import Graph
    nv, ne, nf, nc = TABLE1[row]
    n = max(int(nv * scale), 2 * comm_size)
    e = max(int(ne * scale), n)
    rng = np.random.default_rng(np.random.SeedSequence([edge_seed, 0]))
    src, dst = community_edges(n, e, comm_size, intra_frac, rng)
    frng = np.random.default_rng(np.random.SeedSequence([seed, 1]))
    feats = (frng.standard_normal((n, nf), dtype=np.float32)
             * np.float32(0.1))
    labels = frng.integers(0, nc, n).astype(np.int32)
    return Graph(n, src, dst, feats, labels, nc, name=row)


def make(g: dict, seed: int):
    """The graph a traffic file's ``graph`` entry names, for run ``seed``."""
    return synth(g["row"], g["scale"], seed, g["comm_size"], g["intra_frac"],
                 g.get("edge_seed", 0))
