"""Fused-vs-staged parity and dispatch-count contracts (PR 8).

The fused build (`build_bisim(fused=True)`) and the device store resolve
(`DeviceSigStore.probe_mint_insert`) must be bit-identical to their
staged references — same pids, same per-iteration counts, same store
contents — while honouring the one-sync contract the docstrings
advertise.  These tests are the oracle those docstrings point at.
"""

import numpy as np
import pytest

import repro.core.device_maint as dm
from repro import obs
from repro.core import partition
from repro.core.device_maint import DeviceSigStore, bucket
from repro.core.sig_store import SigStore, mint_novel
from repro.graph import generators

jax = pytest.importorskip("jax")
jnp = jax.numpy


GRAPHS = {
    "random": lambda: generators.random_graph(120, 500, 4, 3, seed=11),
    "powerlaw": lambda: generators.powerlaw_graph(150, 700, 3, 2, seed=5),
    "dag": lambda: generators.random_dag(100, 380, 4, 2, seed=2),
}
MODES = ["multiset", "sorted", "dedup_hash"]


# --------------------------------------------------------------- build parity
@pytest.mark.parametrize("gname", sorted(GRAPHS))
@pytest.mark.parametrize("mode", MODES)
def test_fused_build_matches_staged(gname, mode):
    g = GRAPHS[gname]()
    fused = partition.build_bisim(g, 6, mode=mode, fused=True)
    for sync_every in (1, 3):
        staged = partition.build_bisim(g, 6, mode=mode, fused=False,
                                       sync_every=sync_every)
        np.testing.assert_array_equal(fused.pids, staged.pids)
        assert fused.counts == staged.counts
        assert fused.converged_at == staged.converged_at
        # non-timing stats must agree too (bytes metrics are derived from
        # the same shapes, seconds is wall-clock and excluded)
        for a, b in zip(fused.stats, staged.stats):
            assert (a.iteration, a.num_partitions) == \
                (b.iteration, b.num_partitions)
            assert (a.bytes_sorted, a.bytes_scanned) == \
                (b.bytes_sorted, b.bytes_scanned)


@pytest.mark.parametrize("early_stop", [True, False])
def test_fused_build_early_stop_parity(early_stop):
    g = GRAPHS["random"]()
    fused = partition.build_bisim(g, 8, mode="sorted", fused=True,
                                  early_stop=early_stop)
    staged = partition.build_bisim(g, 8, mode="sorted", fused=False,
                                   early_stop=early_stop)
    np.testing.assert_array_equal(fused.pids, staged.pids)
    assert fused.converged_at == staged.converged_at


def test_fused_build_with_store_raises():
    g = GRAPHS["random"]()
    with pytest.raises(ValueError, match="fused"):
        partition.build_bisim(g, 3, fused=True, with_store=True)


# ----------------------------------------------------------- dispatch counts
def test_fused_build_single_sync():
    """The fused-build contract: exactly ONE device->host sync (the final
    history fetch) and ONE dispatch for the entire k-loop."""
    g = GRAPHS["powerlaw"]()
    with obs.tracing() as tracer:
        partition.build_bisim(g, 6, mode="multiset", fused=True)
    syncs = tracer.find("build.sync")
    dispatches = tracer.find_events("build.dispatch")
    assert len(syncs) == 1
    assert len(dispatches) == 1
    assert dispatches[0]["attrs"]["path"] == "fused"


def test_staged_build_sync_count_scales_with_sync_every():
    g = GRAPHS["powerlaw"]()
    counts = {}
    for sync_every in (1, 3):
        with obs.tracing() as tracer:
            partition.build_bisim(g, 6, mode="multiset", fused=False,
                                  sync_every=sync_every)
        counts[sync_every] = len(tracer.find("build.sync"))
    assert counts[1] > counts[3] >= 1


# ------------------------------------------------------ store resolve parity
def _fresh_pair(entries=()):
    """A host SigStore and its device mirror holding the same entries."""
    host = SigStore.empty()
    next_pid = 0
    if len(entries):
        keys = np.asarray(entries, dtype=np.uint64)
        _, next_pid = host.get_or_assign(keys, next_pid)
    return host, DeviceSigStore(host), next_pid


def _staged_resolve(dev, qhi, qlo, count, next_pid):
    """Reference composition of the resolve's three steps, spelled out:
    the probe program, host minting of the misses (`mint_novel`), and
    the merge-by-rank program fed the sorted novel keys."""
    out = dm._probe_step(dev.khi, dev.klo, dev.kpid, qhi, qlo,
                         jnp.int32(count), jnp.int32(dev.size))
    out = np.asarray(jax.device_get(out[:count])).astype(np.int64)
    miss = out < 0
    if not miss.any():
        return out, next_pid
    keys = (qhi[:count].astype(np.uint64) << np.uint64(32)) \
        | qlo[:count].astype(np.uint64)
    ukeys, pids, inv = mint_novel(keys[miss], next_pid)
    out[miss] = pids[inv]
    n = ukeys.shape[0]
    p = bucket(n)
    nhi = np.full(p, 0xFFFFFFFF, np.uint32)
    nlo = np.full(p, 0xFFFFFFFF, np.uint32)
    npid = np.zeros(p, np.int32)
    nhi[:n] = (ukeys >> np.uint64(32)).astype(np.uint32)
    nlo[:n] = ukeys.astype(np.uint32)
    npid[:n] = pids
    cap = dev.khi.shape[0]
    new_cap = cap if dev.size + n <= cap else bucket(dev.size + n)
    dev.khi, dev.klo, dev.kpid = dm._merge_step(
        dev.khi, dev.klo, dev.kpid, nhi, nlo, npid, np.int32(n),
        np.int32(dev.size), new_cap=new_cap)
    dev.size += n
    dev._host = None
    return out, next_pid + n


def _random_probes(rng, count, pool):
    keys = rng.choice(pool, size=count)
    hi = (keys >> np.uint64(32)).astype(np.uint32)
    lo = keys.astype(np.uint32)
    p = bucket(count)
    qhi = np.zeros(p, np.uint32)
    qlo = np.zeros(p, np.uint32)
    qhi[:count] = hi
    qlo[:count] = lo
    return qhi, qlo


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_probe_mint_insert_matches_staged(seed):
    rng = np.random.default_rng(seed)
    pool = rng.integers(1, 2**63, size=400, dtype=np.uint64)
    _, fused_dev, np_f = _fresh_pair(pool[:50])
    _, staged_dev, np_s = _fresh_pair(pool[:50])
    host = SigStore.empty()
    keys0 = np.asarray(pool[:50], dtype=np.uint64)
    _, np_h = host.get_or_assign(keys0, 0)
    for _ in range(6):
        count = int(rng.integers(1, 120))
        qhi, qlo = _random_probes(rng, count, pool)
        got_f, np_f = fused_dev.probe_mint_insert(qhi, qlo, count, np_f)
        got_s, np_s = _staged_resolve(staged_dev, qhi, qlo, count, np_s)
        keys = (qhi[:count].astype(np.uint64) << np.uint64(32)) \
            | qlo[:count].astype(np.uint64)
        got_h, np_h = host.get_or_assign(keys, np_h)
        np.testing.assert_array_equal(got_f, got_s)
        np.testing.assert_array_equal(got_f, got_h)
        assert np_f == np_s == np_h
    # mirrored store contents identical to the host store
    np.testing.assert_array_equal(fused_dev.to_host().keys, host.keys)
    np.testing.assert_array_equal(fused_dev.to_host().pids, host.pids)


def test_probe_mint_insert_empty_store_all_novel():
    """Edge cases: resolving against an empty store (everything minted)
    and a second all-novel batch that forces a capacity regrow."""
    _, dev, next_pid = _fresh_pair()
    assert dev.size == 0
    keys = np.arange(1, 11, dtype=np.uint64) * np.uint64(0x9E3779B9)
    hi = (keys >> np.uint64(32)).astype(np.uint32)
    lo = keys.astype(np.uint32)
    p = bucket(10)
    qhi = np.zeros(p, np.uint32)
    qlo = np.zeros(p, np.uint32)
    qhi[:10], qlo[:10] = hi, lo
    got, next_pid = dev.probe_mint_insert(qhi, qlo, 10, next_pid)
    # all novel: pids are dense 0..9 in first-occurrence order
    np.testing.assert_array_equal(np.sort(got), np.arange(10))
    assert next_pid == 10 and dev.size == 10
    # second all-novel wave exceeding capacity; probing old keys again
    # must return the original pids
    keys2 = np.arange(100, 160, dtype=np.uint64) * np.uint64(0x85EBCA6B)
    count2 = keys2.size + keys.size
    allk = np.concatenate([keys, keys2])
    p2 = bucket(count2)
    qhi2 = np.zeros(p2, np.uint32)
    qlo2 = np.zeros(p2, np.uint32)
    qhi2[:count2] = (allk >> np.uint64(32)).astype(np.uint32)
    qlo2[:count2] = allk.astype(np.uint32)
    got2, next_pid = dev.probe_mint_insert(qhi2, qlo2, count2, next_pid)
    np.testing.assert_array_equal(got2[:10], got)
    assert next_pid == 10 + keys2.size
    host = dev.to_host()
    assert len(host.keys) == dev.size == 10 + keys2.size


def test_probe_mint_insert_duplicate_probes_one_pid():
    """Duplicate novel keys inside one batch mint exactly one pid."""
    _, dev, next_pid = _fresh_pair()
    k = np.uint64(0xDEADBEEFCAFE)
    qhi = np.zeros(8, np.uint32)
    qlo = np.zeros(8, np.uint32)
    qhi[:4] = np.uint32(k >> np.uint64(32))
    qlo[:4] = np.uint32(k & np.uint64(0xFFFFFFFF))
    got, next_pid = dev.probe_mint_insert(qhi, qlo, 4, next_pid)
    assert next_pid == 1 and dev.size == 1
    np.testing.assert_array_equal(got, np.zeros(4, np.int64))


# -------------------------------------------------------------- bucket policy
def test_bucket_floor_and_waste():
    assert bucket(0) == dm.BUCKET_FLOOR
    assert bucket(1) == dm.BUCKET_FLOOR
    assert bucket(dm.BUCKET_FLOOR) == dm.BUCKET_FLOOR
    for n in [9, 17, 100, 1000, 4097, 65537]:
        b = bucket(n)
        assert b >= n and (b & (b - 1)) == 0
        if n >= dm.BUCKET_FLOOR:
            assert b < 2 * n, f"bucket({n})={b} wastes >= 2x"
    assert bucket(3, floor=1) == 4
    assert bucket(0, floor=64) == 64
    with pytest.raises(ValueError, match="power of two"):
        bucket(10, floor=3)
    with pytest.raises(ValueError, match="power of two"):
        bucket(10, floor=0)


def test_bucketing_bounds_compiled_programs():
    """Regression guard for the jit-cache: resolving a sweep of batch
    sizes against one store may only compile O(log n) distinct
    probe-program shapes — one per (capacity, probe) bucket pair."""
    _, dev, next_pid = _fresh_pair()
    rng = np.random.default_rng(3)
    shapes = set()
    for count in [1, 2, 3, 5, 7, 8, 9, 15, 17, 31, 40, 63, 70, 100, 127]:
        keys = rng.integers(1, 2**63, size=count, dtype=np.uint64)
        p = bucket(count)
        qhi = np.zeros(p, np.uint32)
        qlo = np.zeros(p, np.uint32)
        qhi[:count] = (keys >> np.uint64(32)).astype(np.uint32)
        qlo[:count] = keys.astype(np.uint32)
        _, next_pid = dev.probe_mint_insert(qhi, qlo, count, next_pid)
        shapes.add((p, dev.khi.shape[0]))
    # 15 distinct counts; buckets collapse them to a handful of shapes
    assert len(shapes) <= 8, shapes
