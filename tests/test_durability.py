"""Crash-safety tests: checksummed artifacts, fsync discipline, the
maintenance WAL, checkpoint/resume builds, and fault-injected teardown.

Covers the durability layer end to end:

  * checksum manifests — a byte-flipped or truncated table chunk fails
    `OocGraph.load` with `ChecksumError`, never a wrong partition;
  * the parent-directory fsync after every atomic rename (the classic
    vanishing-commit bug), pinned by counting `fsync_dir` calls;
  * `FaultPlan` injection through the aio primitives: crashes publish
    nothing, transients are retried, torn writes are caught by the
    checksums, and teardown after a mid-write crash leaks neither
    pipeline threads nor temp files;
  * the `WriteAheadLog` commit/replay/truncate protocol, including a
    corrupted committed record and a torn commit line;
  * `build_bisim_oocore(checkpoint=True)` killed at *every* injected
    fault point and resumed — bit-identical pid history, continuing
    `IOStats`;
  * `OocBackend` snapshot/restore with WAL replay, and graceful device
    degradation.

Everything runs with ``io_threads=0`` where determinism of the global
fault-point sequence matters (single-threaded => stable indices).
"""
import os
import warnings

import numpy as np
import pytest

from repro.core import (BisimMaintainer, ChecksumError, FaultPlan,
                        InjectedCrash, TransientIOError, build_bisim,
                        install_fault_plan, same_partition, with_retries)
from repro.exmem import (OocBackend, OocGraph, WriteAheadLog,
                         build_bisim_oocore)
from repro.exmem import aio as aio_mod
from repro.exmem.aio import StreamingWriter, atomic_save, live_aio_threads
from repro.exmem.durability import Manifest, atomic_write_json, read_json
from repro.graph import generators as gen


# CI crash-recovery job: CRASH_SWEEP=full widens the kill-point sweeps
# from a seeded spread to every injected fault point
SWEEP_ALL = os.environ.get("CRASH_SWEEP", "") == "full"


def _graph():
    return gen.random_graph(60, 170, 3, 2, seed=7)


# ------------------------------------------------------ checksum manifests
def _ooc_dir(tmp_path, sub="tables"):
    root = str(tmp_path / sub)
    OocGraph.from_graph(_graph(), root, chunk_nodes=24, chunk_edges=32)
    return root


def _one_chunk(root, table="edges_tst"):
    d = os.path.join(root, table)
    return os.path.join(d, sorted(os.listdir(d))[0])


def test_load_verifies_and_accepts_clean_tables(tmp_path):
    root = _ooc_dir(tmp_path)
    g = OocGraph.load(root).to_memory()
    assert g.num_nodes == 60 and g.num_edges == 170


@pytest.mark.parametrize("table", ["nodes", "edges_tst", "edges_tts"])
def test_load_rejects_byte_flip(tmp_path, table):
    root = _ooc_dir(tmp_path, table)
    path = _one_chunk(root, table)
    with open(path, "rb+") as f:
        f.seek(os.path.getsize(path) - 3)
        b = f.read(1)
        f.seek(-1, os.SEEK_CUR)
        f.write(bytes([b[0] ^ 0xFF]))
    with pytest.raises(ChecksumError):
        OocGraph.load(root)
    OocGraph.load(root, verify=False)  # escape hatch for forensics


def test_load_rejects_truncation_and_missing_chunk(tmp_path):
    root = _ooc_dir(tmp_path)
    path = _one_chunk(root)
    with open(path, "rb+") as f:
        f.truncate(os.path.getsize(path) // 2)
    with pytest.raises(ChecksumError):
        OocGraph.load(root)
    os.remove(path)
    with pytest.raises(ChecksumError):
        OocGraph.load(root)


def test_load_rejects_missing_manifest(tmp_path):
    root = _ooc_dir(tmp_path)
    os.remove(os.path.join(root, "manifest.json"))
    with pytest.raises(ChecksumError):
        OocGraph.load(root)


def test_mutated_tables_reverify(tmp_path):
    """Table mutations (insert/delete/append) keep the manifest current:
    a reload verifies the rewritten chunks."""
    root = _ooc_dir(tmp_path)
    t = OocGraph(root)
    t.insert_edges(np.array([1, 2], np.int32), np.array([0, 1], np.int32),
                   np.array([3, 4], np.int32))
    t.append_nodes(np.array([0, 1], np.int32))
    t2 = OocGraph.load(root)  # verify=True
    assert t2.num_nodes == 62 and t2.num_edges == 172


def test_manifest_verify_reports_first_bad_file(tmp_path):
    man = Manifest()
    a = np.arange(10, dtype=np.int64)
    atomic_save(str(tmp_path / "a.npy"), a)
    man.add_array("a.npy", a)
    man.write(str(tmp_path))
    man2 = Manifest.load(str(tmp_path))
    man2.verify(str(tmp_path))
    np.save(str(tmp_path / "a.npy"), a + 1)
    with pytest.raises(ChecksumError):
        man2.verify(str(tmp_path))


# --------------------------------------------------- fsync-after-rename
def _count_fsync_dir(monkeypatch):
    calls = []
    real = aio_mod.fsync_dir
    monkeypatch.setattr(aio_mod, "fsync_dir",
                        lambda p: (calls.append(p), real(p))[1])
    return calls


def test_atomic_save_fsyncs_parent_dir(tmp_path, monkeypatch):
    """Regression (satellite): the rename alone is not durable — the
    parent directory must be fsync'd or a crash can lose the name."""
    calls = _count_fsync_dir(monkeypatch)
    path = str(tmp_path / "x.npy")
    atomic_save(path, np.arange(4), fsync=True)
    assert calls == [str(tmp_path)]
    calls.clear()
    atomic_save(path, np.arange(4), fsync=False)  # scratch: no fsyncs
    assert calls == []


def test_streaming_writer_fsyncs_parent_dir(tmp_path, monkeypatch):
    calls = _count_fsync_dir(monkeypatch)
    path = str(tmp_path / "w.npy")
    w = StreamingWriter(path, np.int64, 4, threaded=False, fsync=True)
    w.write(np.arange(4, dtype=np.int64))
    w.close()
    assert calls == [str(tmp_path)]
    w2 = StreamingWriter(str(tmp_path / "s.npy"), np.int64, 1,
                         threaded=False, fsync=False)
    w2.write(np.zeros(1, np.int64))
    w2.close()
    assert calls == [str(tmp_path)]  # scratch file: still just the one


def test_atomic_write_json_fsyncs_parent_dir(tmp_path, monkeypatch):
    calls = _count_fsync_dir(monkeypatch)
    atomic_write_json(str(tmp_path / "s.json"), {"a": 1})
    assert calls == [str(tmp_path)]
    assert read_json(str(tmp_path / "s.json")) == {"a": 1}


# ------------------------------------------------------- fault injection
def test_injected_crash_publishes_nothing(tmp_path):
    path = str(tmp_path / "x.npy")
    with install_fault_plan(FaultPlan(crash_at=1)):
        with pytest.raises(InjectedCrash):
            atomic_save(path, np.arange(8))
    assert not os.path.exists(path)
    assert [f for f in os.listdir(tmp_path) if f.endswith(".aio-tmp")] == []


def test_transient_errors_are_retried(tmp_path):
    path = str(tmp_path / "x.npy")
    with install_fault_plan(FaultPlan(transient_at=(1,))) as plan:
        atomic_save(path, np.arange(8))
    np.testing.assert_array_equal(np.load(path), np.arange(8))
    assert plan.points_seen == 2  # the failed try + the successful retry


def test_with_retries_gives_up_and_never_eats_crashes():
    attempts = []

    def flaky():
        attempts.append(1)
        raise TransientIOError("always")

    with pytest.raises(TransientIOError):
        with_retries(flaky, retries=3, backoff_s=0)
    assert len(attempts) == 4  # 3 retried + the final propagating try

    def dead():
        raise InjectedCrash("boom")

    with pytest.raises(InjectedCrash):
        with_retries(dead, retries=3, backoff_s=0)


def test_torn_write_is_caught_by_checksum(tmp_path):
    """A rename that beats the data blocks to disk publishes a truncated
    file under the *live* name — the one corruption atomicity cannot
    prevent and only the manifest CRC can catch.  Tear a chunk rewrite
    on an already-committed table: everything else is intact, so the
    checksum is the only witness."""
    root = _ooc_dir(tmp_path, "t")
    path = _one_chunk(root)
    with install_fault_plan(FaultPlan(torn_at=1,
                                      kinds=frozenset({"atomic_save"}))):
        with pytest.raises(InjectedCrash):
            atomic_save(path, np.asarray(np.load(path)))
    with pytest.raises(ChecksumError):
        OocGraph.load(root)
    # and a crash on the very first spill write commits nothing at all
    with install_fault_plan(FaultPlan(torn_at=1)):
        with pytest.raises(InjectedCrash):
            OocGraph.from_graph(_graph(), str(tmp_path / "t2"),
                                chunk_nodes=24, chunk_edges=32)
    assert not os.path.exists(str(tmp_path / "t2" / "manifest.json"))


def test_streaming_writer_crash_teardown_leaks_nothing(tmp_path):
    """Satellite: a mid-write crash in the threaded writer must leave no
    aio thread and no temp file behind (sticky error, abort cleans)."""
    path = str(tmp_path / "w.npy")
    with install_fault_plan(FaultPlan(crash_at=1,
                                      kinds=frozenset({"sw_write"}))):
        w = StreamingWriter(path, np.int64, 8, threaded=True)
        try:
            with pytest.raises(InjectedCrash):
                for i in range(8):
                    w.write(np.array([i], np.int64))
                w.close()
        finally:
            w.abort()
    assert not os.path.exists(path)
    assert not os.path.exists(path + ".aio-tmp")
    assert live_aio_threads() == []


def test_build_crash_teardown_leaks_no_threads(tmp_path):
    """A build killed mid-flight (with the async pipeline ON) must not
    leak reader/writer threads or leave a backend unjoinable."""
    g = _graph()
    with install_fault_plan(FaultPlan(crash_at=30)):
        with pytest.raises(InjectedCrash):
            build_bisim_oocore(g, 3, chunk_edges=32, chunk_nodes=24,
                               workdir=str(tmp_path / "b"), io_threads=2)
    assert live_aio_threads() == []


def test_backend_close_is_idempotent_even_after_crash(tmp_path):
    be = OocBackend(_graph(), chunk_edges=32, chunk_nodes=24,
                    workdir=str(tmp_path / "m"), io_threads=0)
    m = BisimMaintainer(be, 2)
    with install_fault_plan(FaultPlan(crash_at=2)):
        with pytest.raises(InjectedCrash):
            m.add_edges(np.array([0], np.int32), np.array([0], np.int32),
                        np.array([1], np.int32))
    be.close()
    be.close()  # idempotent
    assert live_aio_threads() == []


# -------------------------------------------------------------- the WAL
def test_wal_append_commit_replay_truncate(tmp_path):
    wal = WriteAheadLog(str(tmp_path / "wal"), group=1)
    a1 = {"src": np.array([1, 2], np.int32), "dst": np.array([3, 4])}
    assert wal.append("add_edges", a1) == 1
    assert wal.append("compact", {}) == 2
    got = list(wal.replay())
    assert [(lsn, op) for lsn, op, _ in got] == [(1, "add_edges"),
                                                (2, "compact")]
    np.testing.assert_array_equal(got[0][2]["src"], a1["src"])
    # truncate: lsn 1 absorbed by a snapshot, numbering continues
    wal.truncate(1)
    assert [lsn for lsn, _, _ in wal.replay()] == [2]
    assert wal.append("delete_node", {"nid": np.array([5])}) == 3


def test_wal_group_commit_bounds_the_loss_window(tmp_path):
    wal = WriteAheadLog(str(tmp_path / "wal"), group=3)
    wal.append("a", {})
    wal.append("b", {})
    assert wal.committed_lsn == 0        # below group size: not yet durable
    assert [lsn for lsn, _, _ in wal.replay()] == []
    wal.append("c", {})                  # group full -> auto-commit
    assert wal.committed_lsn == 3
    wal.append("d", {})
    # a crash here loses only the uncommitted tail (<= group-1 records)
    wal2 = WriteAheadLog(str(tmp_path / "wal"), group=3)
    assert [op for _, op, _ in wal2.replay()] == ["a", "b", "c"]
    # the lost record's lsn is reused: its file was never committed, and
    # the new record atomically replaces it (temp + rename)
    assert wal2.append("e", {}) == 4
    wal2.commit()
    assert [op for _, op, _ in wal2.replay()] == ["a", "b", "c", "e"]


def test_wal_rejects_corrupt_committed_record(tmp_path):
    wal = WriteAheadLog(str(tmp_path / "wal"), group=1)
    wal.append("add_edges", {"src": np.arange(64, dtype=np.int64)})
    rec = os.path.join(str(tmp_path / "wal"), "rec_00000001.npy")
    with open(rec, "rb+") as f:
        f.seek(os.path.getsize(rec) - 2)
        f.write(b"\xff")
    with pytest.raises(ChecksumError):
        list(WriteAheadLog(str(tmp_path / "wal")).replay())


def test_wal_ignores_torn_commit_line(tmp_path):
    wal = WriteAheadLog(str(tmp_path / "wal"), group=1)
    wal.append("a", {})
    wal.append("b", {})
    log = os.path.join(str(tmp_path / "wal"), "commits.log")
    with open(log, "a") as f:
        f.write("3 12")  # torn mid-line: no trailing fields/newline
    wal2 = WriteAheadLog(str(tmp_path / "wal"))
    assert [lsn for lsn, _, _ in wal2.replay()] == [1, 2]
    assert wal2.committed_lsn == 2


def test_wal_lsn_floor_survives_full_truncation(tmp_path):
    """A snapshot that absorbs the whole log leaves commits.log empty;
    reopening with the snapshot's floor must keep numbering monotone or
    the next replay's `lsn > after_lsn` filter would drop new records."""
    wal = WriteAheadLog(str(tmp_path / "wal"), group=1)
    wal.append("a", {})
    wal.append("b", {})
    wal.truncate(2)
    wal2 = WriteAheadLog(str(tmp_path / "wal"), start_lsn=2)
    assert wal2.append("c", {}) == 3
    assert [op for _, op, _ in wal2.replay(after_lsn=2)] == ["c"]


# ------------------------------------------------- checkpoint/resume build
def _clean_build(workdir, g, k=3):
    res = build_bisim_oocore(g, k, chunk_edges=32, chunk_nodes=24,
                             workdir=workdir, io_threads=0)
    return [np.load(p) for p in res.pid_paths], res


def test_build_checkpoint_resume_from_every_kill_point(tmp_path):
    """The acceptance loop: kill a checkpointed build at every injected
    fault point in turn, resume, and demand a bit-identical pid history
    plus continuing (not restarting) IOStats."""
    g = _graph()
    ref_pids, ref = _clean_build(str(tmp_path / "ref"), g)

    # observer pass: count this scenario's fault points
    wd0 = str(tmp_path / "obs")
    with install_fault_plan(FaultPlan()) as obs:
        build_bisim_oocore(g, 3, chunk_edges=32, chunk_nodes=24,
                           workdir=wd0, io_threads=0, checkpoint=True)
    total = obs.points_seen
    assert total > 20

    # sweep a deterministic spread of kill points across the whole build
    # (every 7th point plus the first and last); the CI crash-recovery
    # job sets CRASH_SWEEP=full for the every-single-point version
    points = (range(1, total + 1) if SWEEP_ALL
              else sorted({1, total} | set(range(4, total, 7))))
    for n in points:
        wd = str(tmp_path / f"kill_{n:04d}")
        with install_fault_plan(FaultPlan(crash_at=n)):
            with pytest.raises(InjectedCrash):
                build_bisim_oocore(g, 3, chunk_edges=32, chunk_nodes=24,
                                   workdir=wd, io_threads=0,
                                   checkpoint=True)
        res = build_bisim_oocore(g, 3, chunk_edges=32, chunk_nodes=24,
                                 workdir=wd, io_threads=0,
                                 checkpoint=True, resume=True)
        assert res.converged_at == ref.converged_at, n
        for j, refp in enumerate(ref_pids):
            np.testing.assert_array_equal(
                np.load(res.pid_paths[j]), refp,
                err_msg=f"kill point {n}, level {j}")
        # accounting continued: the resumed run covers at least the
        # reference work (replayed levels + recovery verification scans)
        assert res.io.sort_cost >= ref.io.sort_cost, n
        assert res.io.scan_cost >= ref.io.scan_cost, n


def test_build_resume_requires_matching_params(tmp_path):
    g = _graph()
    wd = str(tmp_path / "b")
    build_bisim_oocore(g, 2, chunk_edges=32, chunk_nodes=24, workdir=wd,
                       io_threads=0, checkpoint=True)
    with pytest.raises(ValueError):
        build_bisim_oocore(g, 2, chunk_edges=64, chunk_nodes=24,
                           workdir=wd, io_threads=0, checkpoint=True,
                           resume=True)


def test_build_checkpoint_requires_workdir():
    with pytest.raises(ValueError):
        build_bisim_oocore(_graph(), 2, checkpoint=True)


# --------------------------------------------- snapshot/restore + replay
def _stream(m, rng):
    n = m.backend.num_nodes
    m.add_edges(rng.integers(0, n, 3).astype(np.int32),
                rng.integers(0, 3, 3).astype(np.int32),
                rng.integers(0, n, 3).astype(np.int32))
    m.delete_node(int(rng.integers(0, n)))
    g = m.graph
    take = rng.integers(0, g.num_edges, 2)
    m.delete_edges(g.src[take], g.elabel[take], g.dst[take])


def test_snapshot_restore_replays_committed_tail(tmp_path):
    wd = str(tmp_path / "m")
    be = OocBackend(_graph(), chunk_edges=32, chunk_nodes=24, workdir=wd,
                    io_threads=0, wal=True)
    m = BisimMaintainer(be, 2, wal=True)
    rng = np.random.default_rng(0)
    _stream(m, rng)
    m.snapshot()
    _stream(m, rng)         # committed to the WAL, *not* snapshotted
    expect = [np.asarray(m.pids[j]).copy() for j in range(m.k + 1)]
    g_after = m.graph
    del m
    be.aio.close()          # simulated crash: no close(), no snapshot

    be2, state = OocBackend.restore(wd, io_threads=0)
    m2 = BisimMaintainer.restore(be2, state)
    assert m2.k == 2 and m2.wal
    for j in range(m2.k + 1):
        np.testing.assert_array_equal(np.asarray(m2.pids[j]), expect[j], j)
    g2 = m2.graph
    assert g2.num_edges == g_after.num_edges
    # recovery cost is visible in the restored backend's IOStats
    assert be2.io.scan_cost > 0
    # and the recovered maintainer keeps maintaining correctly
    _stream(m2, np.random.default_rng(1))
    ref = build_bisim(m2.graph, m2.k, mode=m2.mode, early_stop=False)
    for j in range(m2.k + 1):
        assert same_partition(m2.pids[j], ref.pids[j]), j
    be2.close()


def test_restore_rejects_corrupted_snapshot(tmp_path):
    wd = str(tmp_path / "m")
    be = OocBackend(_graph(), chunk_edges=32, chunk_nodes=24, workdir=wd,
                    io_threads=0, wal=True)
    m = BisimMaintainer(be, 2, wal=True)
    m.snapshot()
    be.aio.close()
    pid0 = os.path.join(wd, "snapshot", "pid_000.npy")
    with open(pid0, "rb+") as f:
        f.seek(os.path.getsize(pid0) - 1)
        f.write(b"\x7f")
    with pytest.raises(ChecksumError):
        OocBackend.restore(wd, io_threads=0)


def test_restore_without_snapshot_raises(tmp_path):
    with pytest.raises(ChecksumError):
        OocBackend.restore(str(tmp_path), io_threads=0)


def test_wal_requires_backend_support():
    from repro.core import InMemoryBackend
    with pytest.raises(ValueError):
        BisimMaintainer(InMemoryBackend(_graph()), 2, wal=True)


# ------------------------------------------------- graceful degradation
def test_device_failure_falls_back_to_host(tmp_path):
    """A transient device-step fault degrades to the bit-identical numpy
    path with a warning — the update still lands, and the maintainer
    stays correct afterwards with device propagation off."""
    be = OocBackend(_graph(), chunk_edges=32, chunk_nodes=24,
                    workdir=str(tmp_path / "m"), io_threads=0)
    m = BisimMaintainer(be, 2, device=True)
    assert m.device

    def flaky_device(*a, **k):
        raise TransientIOError("device step timed out")

    be.propagate_level_device = flaky_device
    with pytest.warns(RuntimeWarning, match="degrading"):
        m.add_edges(np.array([0, 1], np.int32), np.array([0, 1], np.int32),
                    np.array([2, 3], np.int32))
    assert not m.device  # degraded permanently, not per-call
    ref = build_bisim(m.graph, m.k, mode=m.mode, early_stop=False)
    for j in range(m.k + 1):
        assert same_partition(m.pids[j], ref.pids[j]), j
    be.close()


@pytest.mark.parametrize("backend", ["memory", "ooc"])
def test_device_error_propagates(tmp_path, backend):
    """Any device error other than a transient fault (an XLA compile
    refusal, device OOM, a runtime error) propagates out of the update
    and leaves device propagation on: a failed chip step never passes as
    a host-path run."""
    from repro.core import InMemoryBackend
    if backend == "ooc":
        be = OocBackend(_graph(), chunk_edges=32, chunk_nodes=24,
                        workdir=str(tmp_path / "m"), io_threads=0)
    else:
        be = InMemoryBackend(_graph())
    m = BisimMaintainer(be, 2, device=True)
    assert m.device

    def dead_device(*a, **k):
        raise RuntimeError("device lost")

    # every rung of the device ladder fails the same way
    be.propagate_levels_resident = dead_device
    be.propagate_level_resident = dead_device
    be.propagate_level_device = dead_device
    with warnings.catch_warnings(record=True) as caught, \
            pytest.raises(RuntimeError, match="device lost"):
        warnings.simplefilter("always")
        m.add_edges(np.array([0, 1], np.int32), np.array([0, 1], np.int32),
                    np.array([2, 3], np.int32))
    assert not [w for w in caught if "degrading" in str(w.message)]
    assert m.device
    if backend == "ooc":
        be.close()
