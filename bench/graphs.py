"""The benchmark's graph and edge generators, kept apart from the program.

`canonical` is copied from the program's `Graph.from_edges` (sort by
(src, elabel, dst), drop duplicate triples), and each graph family is a
file of its own under `generators/`, copied from the program's
`repro.graph.generators`, so that later changes to the program cannot
move the yardstick.  Everything here is plain numpy and returns plain
arrays; the harness wraps them in the program's `Graph` itself.

A configuration file names its generator and parameters; the same edge
law also draws the edges that maintenance traffic inserts, so that new
edges follow the graph's own distribution.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class EdgeSet:
    """A node-labelled graph as sorted, duplicate-free edge columns."""
    node_labels: np.ndarray  # int32 [N]
    src: np.ndarray          # int32 [E], sorted by (src, elabel, dst)
    elabel: np.ndarray       # int32 [E]
    dst: np.ndarray          # int32 [E]

    @property
    def num_nodes(self) -> int:
        return int(self.node_labels.shape[0])

    @property
    def num_edges(self) -> int:
        return int(self.src.shape[0])


def canonical(node_labels, src, elabel, dst) -> EdgeSet:
    """Sort edges by (src, elabel, dst) and drop duplicate triples."""
    src = np.asarray(src, np.int32)
    elabel = np.asarray(elabel, np.int32)
    dst = np.asarray(dst, np.int32)
    order = np.lexsort((dst, elabel, src))
    src, elabel, dst = src[order], elabel[order], dst[order]
    if src.size:
        keep = np.ones(src.size, bool)
        keep[1:] = ((src[1:] != src[:-1]) | (elabel[1:] != elabel[:-1])
                    | (dst[1:] != dst[:-1]))
        src, elabel, dst = src[keep], elabel[keep], dst[keep]
    return EdgeSet(np.asarray(node_labels, np.int32), src, elabel, dst)


class EdgeLaw:
    """Draws edges (src, elabel, dst) by a configuration's generator: the
    module `generators/<generator>.py`, with `draw(params, rng)` for the
    whole graph and `edges(params, rng, count)` for further edges."""

    def __init__(self, generator, params: dict):
        self.gen, self.params = generator, params
        self.num_nodes = int(params["num_nodes"])
        self.distinct_edges = int(params["distinct_edges"])
        self.num_node_labels = int(params["num_node_labels"])
        self.num_edge_labels = int(params["num_edge_labels"])

    def graph(self, rng: np.random.Generator) -> EdgeSet:
        """The whole graph: the generator's draws, in its order, without
        duplicates, then held at exactly `distinct_edges` edges: a
        seed-drawn share of the surplus is dropped, or further edges
        drawn by the same law fill a deficit.  Every seed then gives the
        program the same array shapes, so that one compile serves every
        seed."""
        labels, src, lab, dst = self.gen.draw(self.params, rng)
        g = canonical(labels, src, lab, dst)
        while g.num_edges < self.distinct_edges:
            s, l, d = self.edges(rng, self.distinct_edges - g.num_edges)
            g = canonical(labels, np.concatenate([g.src, s]),
                          np.concatenate([g.elabel, l]),
                          np.concatenate([g.dst, d]))
        if g.num_edges > self.distinct_edges:
            keep = np.ones(g.num_edges, bool)
            keep[rng.choice(g.num_edges, g.num_edges - self.distinct_edges,
                            replace=False)] = False
            g = EdgeSet(labels, g.src[keep], g.elabel[keep], g.dst[keep])
        return g

    def edges(self, rng: np.random.Generator, count: int):
        """`count` edges drawn by the same law as the graph's."""
        return self.gen.edges(self.params, rng, count)


def edge_keys(num_nodes: int, num_edge_labels: int, src, elabel, dst):
    """One int64 key per edge, ordered as (src, elabel, dst)."""
    n, nl = np.int64(num_nodes), np.int64(num_edge_labels)
    if num_nodes * num_edge_labels * num_nodes >= 2 ** 62:
        raise OverflowError("edge keys need more than 62 bits")
    return ((np.asarray(src, np.int64) * nl + np.asarray(elabel, np.int64))
            * n + np.asarray(dst, np.int64))


def split_keys(num_nodes: int, num_edge_labels: int, keys):
    n, nl = np.int64(num_nodes), np.int64(num_edge_labels)
    dst = keys % n
    rest = keys // n
    return ((rest // nl).astype(np.int32), (rest % nl).astype(np.int32),
            dst.astype(np.int32))
