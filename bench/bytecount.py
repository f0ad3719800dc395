"""HBM bytes each measured program needs at the least, from its shapes.

The counts follow the algorithm, not the implementation, so a later
kernel that replaces a sort or a scatter is read against the same work.
A roofline share is these bytes over the chip's HBM peak, divided by
the program's device time.
"""
from __future__ import annotations


def build_bytes(num_nodes: int, num_edges: int, iterations: int) -> int:
    """One in-memory build: iteration 0 reads the node labels (4N); each
    iteration j >= 1 reads the edge triples once (12E), the previous
    level's pids (4N) and writes the new level's pids (4N)."""
    return 4 * num_nodes + iterations * (12 * num_edges + 8 * num_nodes)


def hop_bytes(quotient_edges: int, batch: int, n_tgt: int,
              n_src: int) -> int:
    """One backward hop of a query wave over one quotient level: the
    level's edge triples once (12 Eq), the wave's [batch, n_tgt] boolean
    target mask read once and its [batch, n_src] source mask written
    once."""
    return 12 * quotient_edges + batch * n_tgt + batch * n_src
