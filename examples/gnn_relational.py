"""GNN training through the relational substrate: GAT on a synthetic
Cora-sized graph, with the message-passing layer running the same
arrange -> gather(join) -> segment-reduce(monoid merge) pipeline as the
Datalog engine (DESIGN.md §4).

    PYTHONPATH=src python examples/gnn_relational.py [--steps 30]
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch
from repro.data.synthetic import random_graph
from repro.data.sampler import NeighborSampler
from repro.training.optim import train_state_init


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=120)
    args = ap.parse_args()

    arch = get_arch("gat-cora")
    g = random_graph(512, 2048, 24, n_classes=7, seed=3)
    # learnable labels: a hidden linear map of the features
    w_true = np.random.default_rng(0).normal(size=(24, 7))
    g["labels"] = (g["node_feat"] @ w_true).argmax(1).astype(np.int32)

    params, cfg = arch.init_smoke(jax.random.PRNGKey(0))
    state = train_state_init(params)
    step = jax.jit(arch.step_fn("full_graph_sm", smoke=True))

    # pad/trim the synthetic graph into the smoke input spec
    specs = arch.input_specs("full_graph_sm", smoke=True)
    n, e = specs["node_feat"].shape[0], specs["senders"].shape[0]
    batch = {
        "senders": jnp.asarray(g["senders"][:e] % n),
        "receivers": jnp.sort(jnp.asarray(g["receivers"][:e] % n)),
        "node_feat": jnp.asarray(g["node_feat"][:n]),
        "edge_feat": jnp.asarray(g["edge_feat"][:e]),
        "labels": jnp.asarray(g["labels"][:n] % 7),
    }
    losses = []
    for i in range(args.steps):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    print(f"GAT loss: {losses[0]:.3f} -> {losses[-1]:.3f} "
          f"({args.steps} full-batch steps)")
    assert losses[-1] < losses[0], "training should reduce loss"

    # the sip-style frontier sampler (minibatch_lg's substrate)
    smp = NeighborSampler(g["senders"], g["receivers"], 512,
                          fanouts=(5, 3))
    sub = smp.sample(np.arange(8))
    print(f"sampled subgraph: {sub['n_nodes']} nodes, "
          f"{sub['n_edges']} edges for 8 seeds")
    print("gnn_relational OK")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
