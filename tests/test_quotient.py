"""Quotient serving subsystem (ISSUE 9): differential correctness of
the structural query engine over the k-bisimulation partition.

Three evaluators must agree on every query:

  * `QuotientEngine` — the jitted fixed-slot batched device evaluator,
  * `eval_ref`       — the numpy reference (bit-parity oracle), and
  * `eval_brute`     — direct evaluation on the original graph,

over 3 generators x 3 signature modes x levels j in {1, k/2, k}, on
realizable paths (sampled via random walks) and unrealizable ones.
On top of that: extent-run algebra (encode/lookup/expand/splice)
against naive recomputation, artifact torn-file rejection, the
epoch/staleness contract under an interleaved update/query stream
(patched artifact == freshly materialized oracle after every batch),
and the patch cost staying far below full rematerialization.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import BisimMaintainer
from repro.exmem import OocBackend
from repro.exmem.durability import ChecksumError
from repro.graph import generators as gen
from repro.quotient import (ExtentRuns, LabelPath, PointLookup,
                            QuotientEngine, QuotientIndex, QuotientService,
                            ReachTemplate, eval_brute, eval_ref,
                            materialize_quotient, normalize_query)

MODES = ["sorted", "dedup_hash", "multiset"]
GENERATORS = {
    "random": lambda: gen.random_graph(40, 110, 3, 2, seed=2),
    "powerlaw": lambda: gen.powerlaw_graph(36, 100, 2, 2, seed=3),
    "structured": lambda: gen.structured_graph(10, seed=5),
}
K = 4
LEVELS = sorted({1, K // 2, K})


def _walk_labels(g, rng, length):
    """Edge labels of a random walk of `length` hops, or None."""
    for _ in range(120):
        cur = int(rng.integers(g.num_nodes))
        labs = []
        for _ in range(length):
            out = np.flatnonzero(g.src == cur)
            if out.size == 0:
                labs = None
                break
            e = int(rng.choice(out))
            labs.append(int(g.elabel[e]))
            cur = int(g.dst[e])
        if labs is not None:
            return tuple(labs)
    return None


def _query_suite(g, rng, k):
    """Realizable + unrealizable paths at every level in LEVELS, with
    and without endpoint constraints, plus point lookups."""
    qs = []
    levels = sorted({1, max(1, k // 2), k})
    for level in levels:
        for length in range(1, level + 1):
            p = _walk_labels(g, rng, length)
            if p is not None:
                qs.append(LabelPath(p, level=level))
                qs.append(ReachTemplate(p, src_label=0, level=level))
                qs.append(ReachTemplate(p, tgt_label=1, level=level))
        # almost certainly unrealizable: labels outside the alphabet
        qs.append(LabelPath(tuple([9] * min(length, level)), level=level))
    for nid in (0, int(g.num_nodes) - 1):
        for level in levels:
            qs.append(PointLookup(nid, level))
    return qs


def _check_all(engine, index, g, pid_history, queries, ctx=()):
    answers = engine.query(queries)
    for q, a in zip(queries, answers):
        r = eval_ref(index, q)
        b = eval_brute(g, q, pid_history)
        if isinstance(q, PointLookup):
            assert a == r == b, (*ctx, q)
        else:
            np.testing.assert_array_equal(
                a, r, err_msg=f"engine != ref: {ctx} {q}")
            np.testing.assert_array_equal(
                a, b, err_msg=f"engine != brute: {ctx} {q}")


# ----------------------------------------------- three-way differential
@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("gname", sorted(GENERATORS))
def test_engine_ref_brute_agree(tmp_path, gname, mode):
    g = GENERATORS[gname]()
    m = BisimMaintainer(g, K, mode=mode)
    index = materialize_quotient(
        g, m.backend, str(tmp_path / "q"),
        counts=[int(x) for x in m.next_pid], mode=mode)
    engine = QuotientEngine(index, max_batch=4)  # force multiple waves
    rng = np.random.default_rng(17)
    hist = [m.backend.pid_column(j) for j in range(K + 1)]
    _check_all(engine, index, m.graph, hist,
               _query_suite(m.graph, rng, K), ctx=(gname, mode))
    assert engine.stats["waves"] >= 1 and engine.stats["hops"] >= 1


def test_engine_batching_is_order_and_width_invariant(tmp_path):
    """The same queries through max_batch=1 (unbatched) and a wide
    batch, shuffled, give identical answers slot for slot."""
    g = GENERATORS["powerlaw"]()
    m = BisimMaintainer(g, K, mode="sorted")
    index = materialize_quotient(
        g, m.backend, str(tmp_path / "q"),
        counts=[int(x) for x in m.next_pid], mode="sorted")
    rng = np.random.default_rng(23)
    queries = [q for q in _query_suite(m.graph, rng, K)
               if not isinstance(q, PointLookup)]
    perm = rng.permutation(len(queries))
    narrow = QuotientEngine(index, max_batch=1)
    wide = QuotientEngine(index, max_batch=64)
    a1 = narrow.query(queries)
    a2 = wide.query([queries[i] for i in perm])
    for slot, i in enumerate(perm):
        np.testing.assert_array_equal(a1[i], a2[slot])
    assert narrow.stats["waves"] > wide.stats["waves"]


# ------------------------------------------------------------ device hop
def _random_level(rng, n_src, n_tgt, n_edges, n_labels, src_hi):
    """Random src-sorted edge columns; sources drawn below `src_hi`, so
    blocks in [src_hi, n_src) have no out-edges."""
    src = np.sort(rng.integers(0, max(src_hi, 1), n_edges)).astype(np.int32)
    return (src, rng.integers(0, n_labels, n_edges).astype(np.int32),
            rng.integers(0, n_tgt, n_edges).astype(np.int32))


@pytest.mark.parametrize("b,n_edges,src_hi", [
    (1, 0, 0),          # an empty level
    (16, 0, 0),
    (1, 300, 60),       # sparse: many blocks with no out-edges
    (16, 300, 40),      # trailing blocks above max(src)
    (16, 5000, 64),     # dense: long runs per block
    (4, 1023, 64),      # the scan's blocks: exactly one, full
    (4, 2048, 64),      # one edge into a third block
])
def test_hop_matches_scatter_or_reference(b, n_edges, src_hi):
    """The prefix-sum hop against `np.logical_or.at` over the same hits,
    with padding (WANT_NONE) and WANT_ALL slots among real labels."""
    from repro.quotient.engine import _hop, _src_offsets
    from repro.quotient.queries import WANT_ALL, WANT_NONE
    rng = np.random.default_rng(1000 * b + n_edges + src_hi)
    n_src, n_tgt, n_labels = 64, 48, 3
    src, el, dst = _random_level(rng, n_src, n_tgt, n_edges, n_labels,
                                 src_hi)
    mask = rng.random((b, n_tgt)) < 0.3
    want = rng.integers(0, n_labels, b).astype(np.int32)
    want[max(b // 2, 1):] = WANT_NONE
    if b > 1:
        want[-1] = WANT_ALL
    got = np.asarray(_hop(mask, el, dst, _src_offsets(src, n_src), want))
    hit = mask[:, dst] & (el[None, :] == want[:, None])
    ref = np.zeros((b, n_src), bool)
    rows = np.repeat(np.arange(b), n_edges)
    np.logical_or.at(ref, (rows, np.tile(src, b)), hit.ravel())
    np.testing.assert_array_equal(got, ref)
    assert ref.any() == (n_edges > 0)
    assert not got[want < 0].any()
    assert not got[:, max(src_hi, 1):].any()


def test_hop_has_no_scatter():
    """The hop is a gather, a prefix sum and a gather at the offsets:
    neither its lowered program nor the compiled one holds a scatter."""
    from repro.quotient.engine import _hop
    b, n_tgt, n_src, eq = 4, 32, 24, 100
    lowered = _hop.lower(
        jax.ShapeDtypeStruct((b, n_tgt), jnp.bool_),
        jax.ShapeDtypeStruct((eq,), jnp.int32),
        jax.ShapeDtypeStruct((eq,), jnp.int32),
        jax.ShapeDtypeStruct((n_src + 1,), jnp.int32),
        jax.ShapeDtypeStruct((b,), jnp.int32))
    # as a word: the test's own name is in the programs' source metadata
    for text in (lowered.as_text(), lowered.compile().as_text()):
        assert not re.search(r"\bscatter\b", text)


def test_refresh_rejects_level_not_sorted_by_src(tmp_path):
    g = GENERATORS["random"]()
    m = BisimMaintainer(g, 2)
    index = materialize_quotient(g, m.backend, str(tmp_path / "q"),
                                 counts=[int(x) for x in m.next_pid])
    engine = QuotientEngine(index, max_batch=2)
    L = index.levels[1]
    assert L.num_edges > 1 and L.src[0] < L.src[-1]
    index.levels[1] = type(L)(L.src[::-1].copy(), L.elabel, L.dst)
    with pytest.raises(ValueError, match="not sorted by src"):
        engine.refresh([1])
    with pytest.raises(ValueError, match="not sorted by src"):
        QuotientEngine(index)


def test_normalize_query_validation():
    with pytest.raises(ValueError):
        normalize_query(LabelPath((), level=2), K)     # empty path
    with pytest.raises(ValueError):
        normalize_query(LabelPath((0, 1, 2), level=2), K)  # m > level
    with pytest.raises(ValueError):
        normalize_query(LabelPath((0,), level=K + 1), K)   # level > k
    with pytest.raises(ValueError):
        normalize_query(LabelPath((-1,), level=1), K)  # negative label
    with pytest.raises(TypeError):
        normalize_query("not a query", K)
    labels, src_l, tgt_l, level = normalize_query(LabelPath((0, 1)), K)
    assert labels == (0, 1) and level == 2  # default: smallest exact


# -------------------------------------------------------- extent runs
def test_extent_runs_roundtrip_and_splice_fuzz():
    rng = np.random.default_rng(31)
    for _ in range(20):
        n = int(rng.integers(1, 200))
        n_blocks = int(rng.integers(1, 12))
        col = rng.integers(0, n_blocks, n).astype(np.int64)
        runs = ExtentRuns.from_column(col, n, n_blocks,
                                      window=int(rng.integers(3, 40)))
        ids = rng.integers(0, n, min(n, 13)).astype(np.int64)
        np.testing.assert_array_equal(runs.pid_of(ids), col[ids])
        for b in range(n_blocks):
            np.testing.assert_array_equal(
                runs.expand([b]), np.flatnonzero(col == b))
            assert runs.block_size(b) == int((col == b).sum())
        # splice a random sorted-unique id set, plus a contiguous tail
        # extension (splice rejects gapped extensions by contract)
        grow = int(rng.integers(0, 5))
        pick = np.unique(np.concatenate(
            [rng.integers(0, n, 3), np.arange(n, n + grow)]))
        vals = rng.integers(0, n_blocks + 2, pick.size).astype(np.int64)
        n2 = n + grow
        col2 = np.concatenate([col, np.zeros(n2 - n, np.int64)])
        col2[pick] = vals
        spliced = runs.splice(pick, vals, num_nodes=n2,
                              n_blocks=n_blocks + 2)
        np.testing.assert_array_equal(
            spliced.pid_of(np.arange(n2)), col2)
        # a splice never leaves gaps or unmerged equal-pid runs
        assert spliced.start[0] == 0
        assert np.all(np.diff(spliced.start) > 0)
        assert np.all(spliced.pid[1:] != spliced.pid[:-1])


def test_extent_runs_splice_rejects_gap():
    runs = ExtentRuns.from_column(np.zeros(4, np.int64), 4, 1)
    with pytest.raises(ValueError):
        runs.splice(np.array([6]), np.array([0]), num_nodes=7)


# ------------------------------------------------- artifact durability
def test_artifact_reload_and_torn_file_rejection(tmp_path):
    g = GENERATORS["random"]()
    m = BisimMaintainer(g, K, mode="sorted")
    root = str(tmp_path / "q")
    index = materialize_quotient(g, m.backend, root,
                                 counts=[int(x) for x in m.next_pid],
                                 mode="sorted")
    re = QuotientIndex.load(root, verify=True)
    assert re.counts == index.counts and re.k == index.k
    for j in range(1, K + 1):
        np.testing.assert_array_equal(re.levels[j].src,
                                      index.levels[j].src)
        np.testing.assert_array_equal(re.runs[j].start,
                                      index.runs[j].start)
    # flip bits in a run file -> the top manifest rejects the artifact
    with open(os.path.join(root, "runs_pid_2.npy"), "r+b") as f:
        f.seek(-2, os.SEEK_END)
        f.write(b"\xff\xff")
    with pytest.raises(ChecksumError):
        QuotientIndex.load(root, verify=True)


def test_artifact_rejects_torn_level_chunk(tmp_path):
    g = GENERATORS["structured"]()
    m = BisimMaintainer(g, K, mode="sorted")
    root = str(tmp_path / "q")
    materialize_quotient(g, m.backend, root,
                         counts=[int(x) for x in m.next_pid],
                         mode="sorted")
    victim = os.path.join(root, "level_01", "edges_tst",
                          "chunk_000000.npy")
    with open(victim, "r+b") as f:
        f.truncate(os.path.getsize(victim) // 2)
    with pytest.raises(ChecksumError):
        QuotientIndex.load(root, verify=True)


# --------------------------------------- liveness / staleness contract
def _interleaved_stream(make_maint, tmp_path, *, steps=4, seed=47):
    """Update/query interleave: after every absorbed batch the served
    answers must equal both brute force on the mutated graph and a
    freshly materialized oracle index (the patched artifact is not just
    consistent — it is the *same partition* a cold rebuild would serve),
    and the epoch must advance by exactly one per batch."""
    m = make_maint()
    svc = QuotientService(m, str(tmp_path / "svc"), max_batch=8)
    rng = np.random.default_rng(seed)
    for step in range(steps):
        n = m.backend.num_nodes
        cnt = int(rng.integers(1, 5))
        before = svc.epoch
        op = int(rng.integers(0, 3))
        if op == 0:
            svc.add_edges(rng.integers(0, n, cnt).astype(np.int32),
                          rng.integers(0, 3, cnt).astype(np.int32),
                          rng.integers(0, n, cnt).astype(np.int32))
        elif op == 1 and m.graph.num_edges:
            g = m.graph
            take = rng.integers(0, g.num_edges, min(3, g.num_edges))
            svc.delete_edges(g.src[take], g.elabel[take], g.dst[take])
        else:
            svc.add_nodes(rng.integers(0, 3, cnt))
        assert svc.epoch == before + 1, "epoch must advance once per batch"
        assert svc.engine.epoch == svc.epoch, "engine lags the service"

        g = m.graph
        hist = [m.backend.pid_column(j) for j in range(m.k + 1)]
        queries = _query_suite(g, rng, m.k)
        _check_all(svc.engine, svc.index, g, hist, queries,
                   ctx=("stream", step))
        oracle = materialize_quotient(
            g, m.backend, str(tmp_path / f"oracle_{step}"),
            counts=[int(x) for x in m.next_pid], mode=m.mode)
        for q in queries:
            a, b = eval_ref(svc.index, q), eval_ref(oracle, q)
            if isinstance(q, PointLookup):
                assert a == b, (step, q)
            else:
                np.testing.assert_array_equal(
                    a, b, err_msg=f"patched != fresh at step {step}: {q}")
    return svc


@pytest.mark.parametrize("mode", MODES)
def test_service_staleness_contract_inmemory(tmp_path, mode):
    svc = _interleaved_stream(
        lambda: BisimMaintainer(GENERATORS["random"](), K, mode=mode),
        tmp_path)
    assert svc.patches >= 1


def test_service_patch_cost_stays_incremental_ooc(tmp_path):
    """On the disk backend, absorbing a small batch must cost a small
    fraction of full rematerialization (sort of touched rows, not
    k x sort(E)) — and must go down the patch path, not the rebuild."""
    backend = OocBackend(GENERATORS["structured"](), chunk_edges=64,
                         chunk_nodes=48, workdir=str(tmp_path / "b"))
    m = BisimMaintainer(backend, K, mode="sorted")
    svc = QuotientService(m, str(tmp_path / "svc"), max_batch=8)
    mat_sort = svc.io.sort_cost
    assert mat_sort > 0
    pre = svc.io.sort_cost
    svc.add_edges(np.array([1, 5], np.int32), np.array([0, 1], np.int32),
                  np.array([9, 3], np.int32))
    patch_sort = svc.io.sort_cost - pre
    assert svc.patches == 1 and svc.rematerializations == 0
    assert patch_sort < mat_sort, (
        f"patch sorted {patch_sort} rows, full materialization only "
        f"{mat_sort} — the patch is not incremental")

    rng = np.random.default_rng(3)
    g = m.graph
    hist = [backend.pid_column(j) for j in range(K + 1)]
    _check_all(svc.engine, svc.index, g, hist,
               _query_suite(g, rng, K), ctx=("ooc-patch",))
    backend.close()


def test_service_rematerializes_on_compact_and_change_k(tmp_path):
    """compact and change_k move ids / the level ladder, so the service
    must rebuild the artifact — and still serve exact answers."""
    m = BisimMaintainer(GENERATORS["random"](), K, mode="sorted")
    svc = QuotientService(m, str(tmp_path / "svc"), max_batch=8)
    rng = np.random.default_rng(5)
    svc.delete_node(3)
    svc.compact()
    assert svc.rematerializations >= 1
    svc.change_k(2)
    assert svc.index.k == 2 and svc.engine.epoch == svc.epoch
    g = m.graph
    hist = [m.backend.pid_column(j) for j in range(m.k + 1)]
    queries = [q for q in _query_suite(g, rng, 2)]
    _check_all(svc.engine, svc.index, g, hist, queries, ctx=("remat",))
