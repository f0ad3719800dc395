"""Build_Bisim (Algorithm 1): k-bisimulation partition construction.

Bottom-up over iterations j = 0..k (Prop. 1): iteration 0 ranks node labels;
iteration j constructs sig_j from pid_{j-1} and ranks the signatures. The
early-stop condition of §3.2/App. A.3 — two consecutive iterations with an
equal number of partition blocks mean the *full* bisimulation partition has
been reached — is applied by default.

The whole k-iteration loop is device-resident, at one of two fusion
levels:

* **Fused** (default for ``with_store=False``): the entire build —
  iteration 0 plus a `lax.while_loop` over iterations 1..k carrying the
  pid buffer, the (k+1, N) pid history, the per-iteration counts and the
  convergence iteration — is ONE jitted program.  Early-stop is checked
  inside the loop body on device, so a converged build performs exactly
  one dispatch and one device->host sync (the final history fetch).
* **Staged** (``with_store=True`` builds that must materialize per-level
  signature arrays, or ``fused=False``): one jitted signature->rank step
  (`_bisim_step`) is reused across iterations, and the host drains the
  scalar (count, converged) flags every ``sync_every`` iterations.  On
  accelerators the previous-iteration pid buffer is donated back to XLA
  each step, so the loop runs with a constant number of N-sized buffers.

Both arrangements run the same integer ops in the same order, so their pid
histories and counts are bit-identical (asserted by the parity sweep in
tests/test_fused_build.py).  Every device->host drain runs inside a
``build.sync`` tracer span and every program launch emits a
``build.dispatch`` event, so a ``--trace`` run shows the dispatch/sync
count per build and the host time blocked on each sync.

The signature store S is extracted from the already-computed (hi, lo)
arrays with zero Python loops: each level's store is an array-backed sorted
``SigStore`` (see sig_store.py) — the paper's sorted signature file S —
keyed by the fused 64-bit signature hash (level 0: the node label) and
shared as-is with the maintenance algorithms (§4).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.graph.storage import Graph
from repro.obs import tracer as obs
from . import signatures as sig
from .sig_store import SigStore


@dataclasses.dataclass
class IterationStats:
    iteration: int
    num_partitions: int
    seconds: float
    # Bytes touched by the bulk operators this iteration — the TPU analogue
    # of the paper's STXXL I/O volume column in Table 7.
    bytes_sorted: int
    bytes_scanned: int


@dataclasses.dataclass
class BisimResult:
    pids: np.ndarray                # int32 [k_eff+1, N] pid history (Table 3)
    counts: list                    # partitions per iteration
    stats: list                     # list[IterationStats]
    converged_at: Optional[int]     # iteration where counts stabilized, or None
    k_requested: int
    # Signature store S per level: SigStore (sorted u64-key -> pid arrays);
    # level 0 keyed by node label — only when with_store=True (needed by
    # maintenance, §4).
    stores: Optional[list] = None
    next_pid: Optional[list] = None

    @property
    def k_effective(self) -> int:
        return self.pids.shape[0] - 1

    def pid_at(self, j: int) -> np.ndarray:
        """pId_j with the paper's Change-k semantics: past the convergence
        point the partition no longer changes (Prop. 7)."""
        return self.pids[min(j, self.k_effective)]


def _iteration0(node_labels: jax.Array):
    return sig.dense_rank_ints(node_labels)


def _bisim_step_impl(pid0, src, dst, elabel, pid_prev, *, num_nodes, mode,
                     use_kernel):
    """One fused iteration: sig_j hashes + dense rank, single XLA program.

    `pid_prev` is returned as an (aliased) output so its buffer survives
    donation — the caller re-binds its history entry to the passthrough.
    """
    hi, lo = sig.signature_hashes(
        pid0, src, dst, elabel, pid_prev, num_nodes=num_nodes, mode=mode,
        use_kernel=use_kernel)
    pid_new, count = sig.dense_rank_pairs(hi, lo)
    return pid_prev, pid_new, count, hi, lo


_bisim_step_jit = None


def _bisim_step(*args, **kwargs):
    """Jit `_bisim_step_impl` lazily: donating pid_prev lets XLA reuse the
    previous iteration's pid buffer in place, but CPU ignores donation (and
    warns), and querying the backend at import time would force JAX
    initialization as an import side effect — so the decision is made at
    the first call, when the backend is already up."""
    global _bisim_step_jit
    if _bisim_step_jit is None:
        donate = () if jax.default_backend() == "cpu" else (4,)
        _bisim_step_jit = jax.jit(
            _bisim_step_impl,
            static_argnames=("num_nodes", "mode", "use_kernel"),
            donate_argnums=donate)
    return _bisim_step_jit(*args, **kwargs)


def _fused_build_impl(node_labels, src, dst, elabel, *, k, num_nodes, mode,
                      use_kernel, early_stop):
    """The whole build as one XLA program: iteration 0 + a while_loop over
    iterations 1..k with the early-stop test evaluated on device.

    The carry is (next iteration j, pid_prev, count_prev, history, counts,
    converged_at) where history is the fixed-shape (k+1, N) pid buffer and
    converged_at is -1 until the first iteration whose partition count
    equals its predecessor's (Prop. 7).  Returns (history, counts,
    iterations executed, converged_at) — all device arrays, fetched by the
    caller in a single transfer.
    """
    pid0, count0 = _iteration0(node_labels)
    history = jnp.zeros((k + 1, num_nodes), jnp.int32).at[0].set(pid0)
    counts = jnp.zeros(k + 1, jnp.int32).at[0].set(count0)

    def cond(carry):
        j, _pid, _cprev, _hist, _cnts, conv_at = carry
        running = j <= k
        if early_stop:
            running = running & (conv_at < 0)
        return running

    def body(carry):
        j, pid_prev, count_prev, hist, cnts, conv_at = carry
        hi, lo = sig.signature_hashes(
            pid0, src, dst, elabel, pid_prev, num_nodes=num_nodes,
            mode=mode, use_kernel=use_kernel)
        pid_new, count = sig.dense_rank_pairs(hi, lo)
        hist = jax.lax.dynamic_update_slice(
            hist, pid_new[None, :], (j, jnp.int32(0)))
        cnts = cnts.at[j].set(count)
        conv_at = jnp.where((count == count_prev) & (conv_at < 0),
                            j, conv_at)
        return (j + jnp.int32(1), pid_new, count.astype(count_prev.dtype),
                hist, cnts, conv_at)

    init = (jnp.int32(1), pid0, count0, history, counts, jnp.int32(-1))
    j_end, _, _, history, counts, conv_at = jax.lax.while_loop(
        cond, body, init)
    return history, counts, j_end - jnp.int32(1), conv_at


_fused_build = jax.jit(
    _fused_build_impl,
    static_argnames=("k", "num_nodes", "mode", "use_kernel", "early_stop"))


def bisim_step(pid0, src, dst, elabel, pid_prev, *, num_nodes: int,
               mode: str, use_kernel: bool = False):
    """One fused sig_j -> dense-rank iteration, shared outside the build
    loop (maintenance Change-k runs extra iterations through the same
    cached program).  `pid_prev` is donated on accelerators — pass a
    buffer you no longer need; the aliased passthrough comes back first.

    Returns (pid_prev_alias, pid_new, count, hi, lo) device arrays.
    """
    return _bisim_step(pid0, src, dst, elabel, pid_prev,
                       num_nodes=num_nodes, mode=mode, use_kernel=use_kernel)


def build_bisim(graph: Graph, k: int, *, mode: str = "sorted",
                early_stop: bool = True, with_store: bool = False,
                use_kernel: bool = False, sync_every: int = 2,
                fused: Optional[bool] = None) -> BisimResult:
    """Compute the k-bisimulation partition of `graph`.

    mode: 'sorted' (paper-faithful), 'dedup_hash' (exact, cheaper sort) or
          'multiset' (sort-free counting-bisimulation refinement).

    fused=None (default) picks the fused single-dispatch while_loop build
    whenever it is applicable (``with_store=False``): the whole loop runs
    as one XLA program with the early-stop test on device, and the only
    device->host sync is the final history fetch.  ``fused=False`` forces
    the staged path; ``fused=True`` with ``with_store=True`` raises,
    because materializing per-level signature arrays requires the staged
    loop (the documented fallback ladder: fused -> staged -> host).

    On the staged path, early-stop checking is batched: each step leaves
    its partition count and a device-side convergence flag
    (count_j == count_{j-1}) on device, and the host drains them in one
    transfer every `sync_every` iterations (default 2 — half the
    round-trips of a per-iteration scalar sync). Up to `sync_every - 1`
    extra iterations may be dispatched past the fixpoint; their results
    are trimmed, so the returned history is identical to a per-iteration
    check — and bit-identical to the fused path.
    """
    if sync_every < 1:
        raise ValueError("sync_every must be >= 1")
    if fused and with_store:
        raise ValueError("fused build cannot materialize per-level stores; "
                         "use the staged sync_every path (fused=None/False)")
    n = graph.num_nodes
    with obs.span("build.upload", nodes=n, edges=graph.num_edges):
        node_labels = jnp.asarray(graph.node_labels)
        src = jnp.asarray(graph.src)
        dst = jnp.asarray(graph.dst)
        elabel = jnp.asarray(graph.elabel)
    esize = max(graph.num_edges, 1)
    key_bytes = {"sorted": 12, "dedup_hash": 12, "multiset": 0}[mode]

    if fused is None:
        fused = not with_store
    if fused:
        return _build_fused(graph, k, node_labels, src, dst, elabel,
                            mode=mode, early_stop=early_stop,
                            use_kernel=use_kernel, n=n, esize=esize,
                            key_bytes=key_bytes)

    t0 = time.perf_counter()
    obs.event("build.dispatch", path="staged", what="iteration0")
    pid0, count0 = _iteration0(node_labels)
    with obs.span("build.sync", path="staged", what="count0"):
        c0 = int(count0)  # host sync point for the timing below
    stats = [IterationStats(0, c0, time.perf_counter() - t0,
                            bytes_sorted=4 * n, bytes_scanned=4 * n)]
    counts = [c0]
    history = [pid0]          # device-resident pid history
    sig_pairs = []            # device-resident (hi, lo) per level, if stored

    # Table-7-style accounting: sorted modes sort E (3 or 2 keys) and N,
    # multiset only scans E and sorts N (for ranking).

    # First step consumes a copy so donation never consumes pid0, which is
    # also history[0] and the non-donated first argument.
    pid_prev = pid0 + jnp.int32(0)
    converged_at = None
    pending = []  # (iteration, count_dev, converged_flag_dev, seconds)

    def _drain() -> bool:
        """One host transfer for all pending (count, flag) scalars."""
        nonlocal converged_at
        if not pending:
            return converged_at is not None
        t_sync = time.perf_counter()
        with obs.span("build.sync", path="staged", what="drain",
                      batched=len(pending)):
            host = jax.device_get([(c, f) for _, c, f, _ in pending])
        # The device_get wait is where the batched steps' compute is paid
        # for; amortize it over the drained iterations so per-iteration
        # seconds stay meaningful (sum over stats ~ wall time, as with
        # the old per-iteration sync).
        dt_sync = (time.perf_counter() - t_sync) / len(pending)
        for (j, _, _, dt), (c, f) in zip(pending, host):
            counts.append(int(c))
            stats.append(IterationStats(
                j, int(c), dt + dt_sync,
                bytes_sorted=key_bytes * esize + 8 * n,
                bytes_scanned=12 * esize + 8 * n))
            if early_stop and converged_at is None and bool(f):
                converged_at = j
        pending.clear()
        return converged_at is not None

    count_prev = count0
    for j in range(1, k + 1):
        t0 = time.perf_counter()
        obs.event("build.dispatch", path="staged", what="step", iteration=j)
        prev_alias, pid_new, count, hi, lo = _bisim_step(
            pid0, src, dst, elabel, pid_prev, num_nodes=n, mode=mode,
            use_kernel=use_kernel)
        flag = count == count_prev  # device-side convergence flag
        dt = time.perf_counter() - t0
        if j > 1:
            history[-1] = prev_alias
        history.append(pid_new)
        if with_store:
            sig_pairs.append((hi, lo))
        pending.append((j, count, flag, dt))
        count_prev = count
        if early_stop and len(pending) >= sync_every and _drain():
            break
        pid_prev = pid_new
    _drain()
    if converged_at is not None:
        # Trim iterations dispatched past the fixpoint (Prop. 7: the
        # partition no longer changes, so dropping them loses nothing).
        keep = converged_at + 1
        history = history[:keep]
        counts = counts[:keep]
        stats = stats[:keep]
        sig_pairs = sig_pairs[:keep - 1]

    # Single bulk host transfer of the pid history (+ signatures if stored).
    with obs.span("build.sync", path="staged", what="history"):
        pids_host, sig_host = jax.device_get((history, sig_pairs))
    pids = np.stack([np.asarray(p) for p in pids_host])

    stores, next_pid = None, None
    if with_store:
        # Store extraction is pure array work on the already-computed
        # hashes: level 0 keyed by node label, level j by sig_j hash.
        stores = [SigStore.from_labels(graph.node_labels, pids[0])]
        for j, (h, l) in enumerate(sig_host, start=1):
            stores.append(SigStore.from_hash_pairs(h, l, pids[j]))
        next_pid = list(counts[: len(stores)])

    return BisimResult(
        pids=pids, counts=counts, stats=stats,
        converged_at=converged_at, k_requested=k, stores=stores,
        next_pid=next_pid)


def _build_fused(graph: Graph, k: int, node_labels, src, dst, elabel, *,
                 mode: str, early_stop: bool, use_kernel: bool, n: int,
                 esize: int, key_bytes: int) -> BisimResult:
    """The single-dispatch build: one program launch, one host sync."""
    t0 = time.perf_counter()
    obs.event("build.dispatch", path="fused", what="while_loop", k=k)
    hist_d, cnts_d, iters_d, conv_d = _fused_build(
        node_labels, src, dst, elabel, k=k, num_nodes=n, mode=mode,
        use_kernel=use_kernel, early_stop=early_stop)
    # THE device->host sync: history, counts and the two loop scalars in
    # one transfer (one build.sync span for the whole build).
    with obs.span("build.sync", path="fused", what="history") as sp:
        hist, cnts, iters, conv = jax.device_get(
            (hist_d, cnts_d, iters_d, conv_d))
        iters = int(iters)
        sp.set(iterations=iters)
    dt = time.perf_counter() - t0

    converged_at = int(conv) if early_stop and int(conv) >= 0 else None
    keep = iters + 1  # converged loops stop right after the fixpoint step
    pids = np.asarray(hist[:keep])
    counts = [int(c) for c in cnts[:keep]]
    # The loop ran as one program, so per-iteration wall time is not
    # observable; amortize the total evenly (sum over stats == wall time,
    # as on the staged path).  The byte columns use the same formulas.
    dt_each = dt / keep
    stats = [IterationStats(0, counts[0], dt_each,
                            bytes_sorted=4 * n, bytes_scanned=4 * n)]
    for j in range(1, keep):
        stats.append(IterationStats(
            j, counts[j], dt_each,
            bytes_sorted=key_bytes * esize + 8 * n,
            bytes_scanned=12 * esize + 8 * n))
    return BisimResult(
        pids=pids, counts=counts, stats=stats,
        converged_at=converged_at, k_requested=k)


def partition_blocks(pids: np.ndarray) -> dict:
    """Group node ids by partition id (small-graph helper for tests)."""
    blocks = {}
    for node, p in enumerate(np.asarray(pids).tolist()):
        blocks.setdefault(p, []).append(node)
    return blocks


def _pair_counts(a: np.ndarray, b: np.ndarray):
    """(#distinct a, #distinct b, #distinct (a, b) pairs) of two equal-
    length labelings, by dense re-ranking and one fused int64 key."""
    ua, ra = np.unique(np.asarray(a).ravel(), return_inverse=True)
    ub, rb = np.unique(np.asarray(b).ravel(), return_inverse=True)
    pairs = np.unique(ra.astype(np.int64) * len(ub) + rb)
    return len(ua), len(ub), len(pairs)


def same_partition(a: np.ndarray, b: np.ndarray) -> bool:
    """Do two pid labelings induce the same partition (up to renaming)?

    They do iff the blocks correspond one to one, i.e. iff there are as
    many distinct (a, b) pairs as distinct a values and as distinct b
    values — three sorts, so a multi-million-node history checks in a
    fraction of a second."""
    a = np.asarray(a)
    b = np.asarray(b)
    if a.shape != b.shape:
        return False
    na, nb, npairs = _pair_counts(a, b)
    return na == nb == npairs


def refines(fine: np.ndarray, coarse: np.ndarray) -> bool:
    """Is partition `fine` a refinement of `coarse`?  (Every fine block
    lies in one coarse block: one distinct pair per fine value.)"""
    nf, _, npairs = _pair_counts(fine, coarse)
    return nf == npairs
