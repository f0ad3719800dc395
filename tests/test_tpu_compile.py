"""Compile the device programs for one described v5e chip — no chip needed.

The TPU compiler is installed next to jax, so each program here is
lowered and compiled for a v5e that is described, not attached: the
Pallas kernels at their callers' widths (Mosaic refusals — tiling, VMEM,
unsupported ops — surface here, never in interpret mode), the donated
maintenance store programs, the fused maintenance k-loop, the quotient
hop and the fused build at a small shape.  Nothing runs, so these say
nothing about results or time.

The topology is described inside a module fixture, never at import:
only one process may hold the TPU library at a time.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

U32 = jnp.uint32
I32 = jnp.int32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def spec(one_chip):
    def make(shape, dtype=I32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("nodes_per_block,edges_per_block,num_blocks", [
    (8, 128, 1024),      # blocked_csr_layout's defaults
    (64, 1024, 64),      # the widest tile of the block sweep
])
def test_sig_fold_compiles(spec, nodes_per_block, edges_per_block,
                           num_blocks):
    from repro.kernels import sig_fold as sf
    e = num_blocks * edges_per_block
    c = jax.jit(lambda *a: sf.sig_fold(
        *a, nodes_per_block=nodes_per_block,
        edges_per_block=edges_per_block, interpret=False)).lower(
        spec((e,)), spec((e,)), spec((e,)), spec((e,), jnp.bool_)).compile()
    assert _has_kernel(c)


# the smallest and a large `device_maint.bucket` edge bucket
@pytest.mark.parametrize("lanes,num_sigs", [(8, 8), (1 << 20, 1 << 15)])
@pytest.mark.parametrize("dedup", [False, True])
def test_frontier_sig_fold_compiles(spec, lanes, num_sigs, dedup):
    from repro.kernels import sig_fold as sf
    c = jax.jit(lambda *a: sf.frontier_sig_fold(
        *a, num_sigs=num_sigs, dedup=dedup, interpret=False)).lower(
        spec((lanes,), U32), spec((lanes,), U32), spec((lanes,)),
        spec((lanes,), jnp.bool_)).compile()
    assert _has_kernel(c)
    # the scan kernel streams fixed tiles: its scoped VMEM does not grow
    # with the batch, so the program's temp is the scatter's, not the
    # kernel's [nodes x edges] broadcast
    assert c.memory_analysis().temp_size_in_bytes < 64 * lanes + (1 << 20)


@pytest.mark.parametrize("chunk", [1 << 16, 1 << 20])  # default, smoke's
def test_chunk_sig_fold_compiles(spec, chunk):
    from repro.kernels import sig_fold as sf
    c = jax.jit(lambda *a: sf.chunk_sig_fold(
        *a, num_segments=chunk, dedup=True, interpret=False)).lower(
        spec((chunk,)), spec((chunk,)), spec((chunk,)),
        spec((chunk,), jnp.bool_), spec((1,), jnp.bool_)).compile()
    assert _has_kernel(c)


def test_store_programs_compile_with_donation(spec):
    """The store probe, and the merge-insert with its donated columns:
    the code's own backend check sees the CPU here and would not donate,
    so the donation is set up in the test, as `device_maint` sets it up
    on the chip."""
    from repro.core import device_maint as dm
    cap, p = 1 << 22, 1 << 11          # a LinkedMDB-sized level store
    cols = (spec((cap,), U32), spec((cap,), U32), spec((cap,)))
    dm._probe_step.lower(*cols, spec((p,), U32), spec((p,), U32),
                         spec(()), spec(())).compile()
    merge = jax.jit(dm._merge_step_impl, static_argnames=("new_cap",),
                    donate_argnums=(0, 1, 2)).lower(
        *cols, spec((p,), U32), spec((p,), U32), spec((p,)), spec(()),
        spec(()), new_cap=cap).compile()
    assert merge.memory_analysis().alias_size_in_bytes > 0  # donated


def test_levels_resident_step_compiles(spec):
    from repro.core import device_maint as dm
    k, cap, nb, eb = 5, 1 << 22, 1 << 15, 1 << 17   # LinkedMDB-sized
    stores = tuple((spec((cap,), U32), spec((cap,), U32), spec((cap,)))
                   for _ in range(k))
    dm._levels_resident_step.lower(
        spec((nb,), U32), spec(()), spec((k, eb), U32), spec((k, eb), U32),
        spec((k, nb + 1)), spec((k,)), spec((k, nb)), stores,
        spec((k,))).compile()


@pytest.mark.parametrize("b,blocks,edges", [
    (16, 1 << 12, 1 << 14),
    (16, 1 << 21, 1 << 23),     # the query cell's level shape
])
def test_quotient_hop_compiles(spec, b, blocks, edges):
    from repro.quotient.engine import _hop
    c = _hop.lower(spec((b, blocks), jnp.bool_), spec((edges,)),
                   spec((edges,)), spec((blocks + 1,)), spec((b,))).compile()
    assert not re.search(r"\bscatter\b", c.as_text())


def test_fused_build_compiles(spec):
    """The whole in-memory build as one program, at a small shape: the
    TPU compile of a sort grows with its length up to ~2^16 elements,
    where this program already takes over a minute (the full LinkedMDB
    shape about a minute and a half)."""
    from repro.core import partition
    n, e = 1 << 10, 1 << 12
    c = jax.jit(partition._fused_build_impl, static_argnames=(
        "k", "num_nodes", "mode", "use_kernel", "early_stop")).lower(
        spec((n,)), spec((e,)), spec((e,)), spec((e,)), k=10, num_nodes=n,
        mode="sorted", use_kernel=False, early_stop=True).compile()
    assert c.memory_analysis().temp_size_in_bytes > 0
