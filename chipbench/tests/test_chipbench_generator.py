"""The Graph500 generator and the references, on the CPU."""
from __future__ import annotations

import json

import numpy as np
import pytest

from chipbench import bench

PARAMS = {"scale": 8, "edge_factor": 16, "initiator": [0.57, 0.19, 0.19, 0.05],
          "structure_seed": 22, "seeded_labels": True}
SEEDS = [0, 2**31 + 17, 2**33 + 5]
CONFIGS = [c["name"] for c in json.loads(
    (bench.ROOT / "BENCHMARK.json").read_text())["configs"]]


def _gen():
    return bench.load_module(bench.HERE / "generators" / "graph500_kronecker.py")


@pytest.mark.parametrize("seed", SEEDS)
def test_same_seed_same_graph(seed):
    gen = _gen()
    a, b = gen.generate(PARAMS, seed), gen.generate(PARAMS, seed)
    assert np.array_equal(a.edges, b.edges)
    assert a.source() == b.source()
    assert np.array_equal(a.stream(500, 1), b.stream(500, 1))


def test_seeds_differ_and_shapes_hold():
    """Each undirected edge once: no self loop, no duplicate in either
    direction; the source has an edge."""
    gen = _gen()
    graphs = [gen.generate(PARAMS, s) for s in SEEDS]
    for g in graphs:
        key = gen.undirected_key(g.edges)
        assert len(np.unique(key, axis=0)) == len(g.edges)
        assert (key[:, 0] != key[:, 1]).all()
        assert 0.5 * (16 << 8) < len(g.edges) < 16 << 8
        assert g.edges.min() >= 0 and g.edges.max() < 1 << 8
        assert g.source() in g.edges
    assert not np.array_equal(graphs[0].edges, graphs[1].edges)
    assert not np.array_equal(graphs[0].stream(500, 1), graphs[0].stream(500, 2))


def test_seeds_relabel_one_structure():
    """Seeds draw labels and edge order, not the graph: the degree
    sequence, the search depth and the stream's new edges are the same
    for every seed."""
    gen = _gen()
    ref = bench.load_module(bench.HERE / "references" / "reach.py")
    seen = set()
    for seed in SEEDS:
        g = gen.generate(PARAMS, seed)
        deg = np.sort(np.bincount(g.edges.ravel(), minlength=256))
        inv = np.argsort(g.perm)
        stream = inv[g.stream(300, 1)]
        edbs = {"edge": g.edges, "source": g.relation("bfs_source")}
        reach, depth = ref.rounds(edbs, g.vertices)
        seen.add((deg.tobytes(), stream.tobytes(), len(reach), depth,
                  int(inv[g.source()])))
    assert len(seen) == 1


def test_fixed_labels_leave_only_the_order():
    gen = _gen()
    fixed = {**PARAMS, "seeded_labels": False}
    a, b = gen.generate(fixed, SEEDS[0]), gen.generate(fixed, SEEDS[1])
    assert not np.array_equal(a.edges, b.edges)
    assert np.array_equal(np.unique(a.edges, axis=0), np.unique(b.edges, axis=0))


def test_kronecker_skew():
    """The initiator's skew: the busiest tenth of the vertices holds most
    edge endpoints."""
    g = _gen().generate({**PARAMS, "scale": 12}, 3)
    deg = np.sort(np.bincount(g.edges.ravel(), minlength=1 << 12))[::-1]
    assert deg[: len(deg) // 10].sum() > 0.5 * deg.sum()


def test_relation_kinds():
    g = _gen().generate(PARAMS, 1)
    assert g.relation("undirected") is g.edges
    sym = g.relation("symmetric")
    assert len(sym) == 2 * len(g.edges)
    assert set(map(tuple, sym.tolist())) == set(map(tuple, g.edges.tolist())) | set(
        map(tuple, g.edges[:, ::-1].tolist()))
    assert g.relation("bfs_source").tolist() == [[g.source()]]
    with pytest.raises(KeyError):
        g.relation("weights")


@pytest.mark.parametrize("config", CONFIGS)
@pytest.mark.parametrize("seed", SEEDS)
def test_reference_equals_its_fixpoint(config, seed):
    """Each configuration's two reference forms agree, and the control
    (one round short) differs."""
    cfg = json.loads((bench.HERE / "configs" / f"{config}.json").read_text())
    ref = bench.load_module(bench.HERE / "references" / f"{cfg['reference']}.py")
    g = _gen().generate({**cfg, "scale": 8}, seed)
    edbs = {k: g.relation(v) for k, v in cfg["edbs"].items()}
    want = ref.answer(edbs, g.vertices)
    full, rounds = ref.rounds(edbs, g.vertices)
    assert rounds >= 2
    assert np.array_equal(full, want)
    cut, _ = ref.rounds(edbs, g.vertices, rounds - 1)
    assert not np.array_equal(cut, want)


@pytest.mark.parametrize("config", CONFIGS)
def test_reference_is_undirected(config):
    """The references take the graph as undirected: the same answer
    from the edges in either direction."""
    cfg = json.loads((bench.HERE / "configs" / f"{config}.json").read_text())
    ref = bench.load_module(bench.HERE / "references" / f"{cfg['reference']}.py")
    g = _gen().generate({**cfg, "scale": 8}, SEEDS[1])
    edbs = {k: g.relation(v) for k, v in cfg["edbs"].items()}
    flipped = {**edbs, "edge": edbs["edge"][:, ::-1]}
    assert np.array_equal(ref.answer(edbs, g.vertices),
                          ref.answer(flipped, g.vertices))
