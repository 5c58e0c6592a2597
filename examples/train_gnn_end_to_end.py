"""End-to-end driver (the paper's kind: GNN training): train GCN and GIN
for a few hundred steps on a pubmed-scale synthetic graph with the full
AdaptGear pipeline, reporting the paper's Fig. 8-style comparison against
the static-kernel baselines.

``--inter-buckets k`` splits the inter-community subgraph into k density
tiers, each with its own feedback-selected kernel (k=1 is the paper's
two-subgraph decomposition).

  PYTHONPATH=src python examples/train_gnn_end_to_end.py [--steps 200]
"""
import argparse

from repro import compile_cache
from repro.core import gnn
from repro.graphs import graph as G


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--dataset", default="pubmed")
    ap.add_argument("--scale", type=float, default=0.25)
    ap.add_argument("--inter-buckets", type=int, default=1,
                    help="inter-community density tiers (1 = paper-faithful)")
    args = ap.parse_args()

    graph = G.synth_dataset(args.dataset, scale=args.scale, seed=0)
    print(f"{args.dataset}: {graph.n} vertices, {graph.n_edges} edges, "
          f"inter_buckets={args.inter_buckets}")

    for model in ("gcn", "gin"):
        ag = gnn.train(graph, gnn.GNNConfig(
            model=model, selector="feedback", reorder="louvain",
            inter_buckets=args.inter_buckets, warmup_iters=2),
            steps=args.steps)
        static = gnn.train(graph, gnn.GNNConfig(
            model=model, selector="fixed", fixed_kernels=("ell", "ell"),
            reorder="bfs"), steps=max(args.steps // 4, 10))
        print(f"{model}: adaptgear {ag.step_seconds*1e3:.2f} ms/step "
              f"(plan {ag.kernels}), static-full-graph "
              f"{static.step_seconds*1e3:.2f} ms/step  "
              f"-> {static.step_seconds/max(ag.step_seconds,1e-12):.2f}x; "
              f"final loss {ag.losses[-1]:.4f}, acc {ag.accuracy:.3f}")


if __name__ == "__main__":
    main()
