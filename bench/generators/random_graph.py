"""Uniform random edge-labelled graphs, as the program's
`repro.graph.generators.random_graph` draws them: node labels first,
then sources, targets and edge labels, each uniform.

A configuration names this file by its `generator` key; its `params`
give `num_nodes`, `num_edges` (the draws), `num_node_labels` and
`num_edge_labels`."""
from __future__ import annotations

import numpy as np


def draw(params: dict, rng: np.random.Generator):
    """(node labels, src, elabel, dst) of the whole graph, duplicates kept."""
    n, e = int(params["num_nodes"]), int(params["num_edges"])
    labels = rng.integers(0, int(params["num_node_labels"]), n,
                          dtype=np.int32)
    src = rng.integers(0, n, e, dtype=np.int32)
    dst = rng.integers(0, n, e, dtype=np.int32)
    lab = rng.integers(0, int(params["num_edge_labels"]), e, dtype=np.int32)
    return labels, src, lab, dst


def edges(params: dict, rng: np.random.Generator, count: int):
    """(src, elabel, dst) of `count` further edges by the same law."""
    n = int(params["num_nodes"])
    src = rng.integers(0, n, count, dtype=np.int32)
    lab = rng.integers(0, int(params["num_edge_labels"]), count,
                       dtype=np.int32)
    dst = rng.integers(0, n, count, dtype=np.int32)
    return src, lab, dst
