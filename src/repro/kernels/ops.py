"""jit'd public wrappers for the Pallas kernels + host-side layout builders.

Kernels are TPU-target (pl.pallas_call + BlockSpec VMEM tiling).  On the
CPU backend they run interpreted and are validated against the oracles
in ref.py; on any other backend they compile (`interpret_mode`).  The
engine paths use XLA-native math by default; ``use_kernel=True`` switches
the folds over.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import sig_fold as _sig_fold
from . import flash_attention as _flash

# re-exports
flash_attention = _flash.flash_attention
sig_fold = _sig_fold.sig_fold


@jax.jit
def edge_hash(elabel: jax.Array, pid_tgt: jax.Array):
    """Fused per-edge signature hash (jnp path; oracle = ref.edge_hash_ref).

    Exists so repro.core can route hashing through the kernels package on
    TPU; on CPU it is the same pure-jnp computation as the oracle.
    """
    from repro.core import signatures as sig
    return sig.hash_pair(elabel, pid_tgt)


def blocked_csr_layout(src: np.ndarray, dst: np.ndarray, elabel: np.ndarray,
                       num_nodes: int, *, nodes_per_block: int = 8,
                       edges_per_block_align: int = 128):
    """Build the blocked-CSR layout sig_fold consumes.

    Edges (sorted by src) are grouped by source node-block; every block is
    padded to a common edge budget so the Pallas grid is rectangular.
    Returns dict of padded arrays + meta. Skew cost: total padding is
    (num_blocks * eb - E); heavy-hub graphs should use larger blocks.
    """
    src = np.asarray(src)
    dst = np.asarray(dst)
    elabel = np.asarray(elabel)
    nb = nodes_per_block
    num_blocks = -(-num_nodes // nb)
    blk_of_edge = (src // nb).astype(np.int64)
    counts = np.bincount(blk_of_edge, minlength=num_blocks)
    eb = max(int(counts.max(initial=0)), 1)
    eb = -(-eb // edges_per_block_align) * edges_per_block_align
    e_lab = np.zeros(num_blocks * eb, dtype=np.int32)
    e_dst = np.zeros(num_blocks * eb, dtype=np.int32)
    e_lsrc = np.zeros(num_blocks * eb, dtype=np.int32)
    e_valid = np.zeros(num_blocks * eb, dtype=bool)
    if src.size:
        # Fully vectorized scatter: stable-sort edges by block, compute each
        # edge's slot within its block from the block start offsets, and
        # write all columns with one fancy-indexed assignment each.
        order = np.argsort(blk_of_edge, kind="stable")
        blk_sorted = blk_of_edge[order]
        starts = np.zeros(num_blocks + 1, dtype=np.int64)
        np.cumsum(counts, out=starts[1:])
        slot = np.arange(src.size, dtype=np.int64) - starts[blk_sorted]
        flat = blk_sorted * eb + slot
        e_lab[flat] = elabel[order]
        e_dst[flat] = dst[order]
        e_lsrc[flat] = (src[order] - blk_sorted * nb).astype(np.int32)
        e_valid[flat] = True
    return dict(
        elabel=e_lab, dst=e_dst, local_src=e_lsrc, valid=e_valid,
        nodes_per_block=nb, edges_per_block=eb, num_blocks=num_blocks,
        padded_nodes=num_blocks * nb)


@functools.partial(jax.jit, static_argnames=(
    "nodes_per_block", "edges_per_block", "num_nodes", "interpret"))
def sig_fold_from_layout(elabel, dst, local_src, valid, pid_prev, *,
                         nodes_per_block: int, edges_per_block: int,
                         num_nodes: int, interpret: Optional[bool] = None):
    """Gather pid_prev[dst] then run the sig_fold kernel; trims padding."""
    pid_tgt = pid_prev[dst]
    hi, lo = _sig_fold.sig_fold(
        elabel, pid_tgt, local_src, valid, nodes_per_block=nodes_per_block,
        edges_per_block=edges_per_block, interpret=interpret)
    return hi[:num_nodes], lo[:num_nodes]
