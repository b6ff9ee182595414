"""Sharded host-prep pool: the backend seam that parallelizes batch prep.

The engine is host-bound (PERF.md section 5: the device idles most of
every cell): the serial Python prep — sign-bytes assembly, signature
splitting, nibble/window-table extraction — caps throughput below the
device-step rate. Two backends share one caller
API behind ``make_host_pool``:

- **thread** (``HostPrepPool``): worker threads. The two heavy prep
  stages both release the GIL (the native _prep.so work runs inside
  ctypes; the numpy fallback spends its time in vectorized C loops), so
  sharding a batch's rows across threads is real parallelism even on GIL
  builds — but the residual pure-Python slices (per-row SHA-512 driving
  loop, Python sign-bytes encode when the C codec is absent) stay
  serialized.
- **process** (``ProcHostPrepPool``): worker processes past the GIL
  entirely. The two TYPED prep tasks — compact ed25519 prep and
  canonical sign-bytes — ship through ``multiprocessing.shared_memory``
  segments (inputs packed once, outputs written shard-in-place by the
  workers; see ``prep_proc``), because generic closures can't cross a
  process boundary. Everything else (``submit``/``map_shards`` with
  arbitrary closures) transparently delegates to an embedded thread
  pool, so a process pool is a drop-in superset. Spawn failure at
  construction raises ``HostPoolSpawnError`` and ``make_host_pool``
  degrades to the thread backend; a worker lost at runtime costs only
  its shard (recomputed inline) and flips the pool to the thread path
  for subsequent batches.

Design constraints, in order:

- **The submit side must stay off the lock radar.** ``submit`` is
  hotpath-pinned by txlint (analysis/passes.py): one allocation plus one
  ``queue.SimpleQueue.put`` — a reentrant C-level enqueue that never
  blocks and takes no Python-visible lock. The engine thread can enqueue
  shards mid-step without adding a lock edge to the audited graph.
- **The caller is a worker.** ``map_shards`` splits ``[0, n)`` into
  ``workers`` contiguous shards, enqueues all but the last, and runs the
  last inline on the calling thread — a pool of W workers uses W-1
  threads (or processes), and ``workers=1`` degenerates to the serial
  path with zero queue traffic. While waiting for its own shards the
  thread caller steals queued jobs (other engines' shards included), so
  a shared pool never idles a caller behind a busy worker.
- **Shards are contiguous and ordered.** Each prep stage writes rows
  ``[lo, hi)`` of preallocated output arrays, so the assembled batch is
  byte-identical to the serial prep regardless of completion order or
  backend (parity pinned by tests/test_mesh_engine.py and
  tests/test_procprep.py).
- **Nothing outlives its owner.** Every pool self-registers with a
  module atexit hook (``close_all_pools``) that closes workers and
  unlinks any shared-memory segment still tracked, so co-located engines
  in tests never leak worker processes or /dev/shm segments even when an
  owner forgets to call ``close()``.
"""

from __future__ import annotations

import atexit
import queue as _queue
import threading
import weakref

import numpy as np

from ..analysis.lockgraph import make_lock
from ..analysis.racegraph import shared_field
from ..utils.clock import monotonic


class HostPoolSpawnError(RuntimeError):
    """Worker processes could not be spawned (or never acked ready)."""


# -- atexit pool registry ----------------------------------------------------
# every constructed pool lands here (weakly); the atexit hook closes the
# stragglers so worker processes and shm segments never outlive the run

_LIVE_POOLS: "weakref.WeakSet" = weakref.WeakSet()
_ATEXIT_ARMED = False


def register_pool(pool) -> None:
    global _ATEXIT_ARMED
    _LIVE_POOLS.add(pool)
    if not _ATEXIT_ARMED:
        atexit.register(close_all_pools)
        _ATEXIT_ARMED = True


def close_all_pools(timeout: float = 1.0) -> None:
    """Close every still-live pool (idempotent; atexit + test teardown)."""
    for pool in list(_LIVE_POOLS):
        try:
            pool.close(timeout=timeout)
        except Exception:
            pass


class _Job:
    """One enqueued shard: ``fn(lo, hi)`` plus its completion latch."""

    __slots__ = ("fn", "lo", "hi", "done", "result", "error")

    def __init__(self, fn, lo: int, hi: int):
        self.fn = fn
        self.lo = lo
        self.hi = hi
        self.done = threading.Event()
        self.result = None
        self.error: BaseException | None = None

    def run(self) -> None:
        try:
            self.result = self.fn(self.lo, self.hi)
        except BaseException as exc:  # re-raised on the caller in map_shards
            self.error = exc
        finally:
            self.done.set()


class HostPrepPool:
    """Fixed-size thread pool specialized for contiguous-shard batch prep.

    ``workers`` counts the calling thread: a pool of 4 spawns 3 daemon
    threads and runs the caller's shard inline. Shared freely between
    engines (engines over one DeviceVoteVerifier share its pool,
    ``ensure_host_pool``); per-call wait accounting is returned to
    each caller rather than accumulated globally.
    """

    backend = "thread"

    def __init__(self, workers: int, name: str = "hostprep"):
        self.workers = max(1, int(workers))
        self._q: _queue.SimpleQueue = _queue.SimpleQueue()
        self._closed = False
        self._stats_mtx = make_lock("engine.HostPrepPool._stats_mtx")
        # stats counters: every caller thread folds its tallies in here
        self._sh_stats = shared_field("engine.HostPrepPool.stats")  # txlint: shared(self._stats_mtx)
        self.jobs_total = 0
        self.steals_total = 0
        self.pool_wait_s = 0.0
        self._threads: list[threading.Thread] = []
        for i in range(self.workers - 1):
            t = threading.Thread(
                target=self._worker, name=f"{name}-{i}", daemon=True
            )
            t.start()
            self._threads.append(t)
        register_pool(self)

    # -- submit side (hotpath-pinned: O(1), no locks) -------------------
    def submit(self, fn, lo: int, hi: int) -> _Job:
        """Enqueue ``fn(lo, hi)``; returns the job handle.

        One object allocation + one SimpleQueue.put (lock-free C
        enqueue). Never blocks; safe to call from inside the engine's
        step loop.
        """
        job = _Job(fn, lo, hi)
        self._q.put(job)
        return job

    # -- worker side ----------------------------------------------------
    def _worker(self) -> None:
        while True:
            job = self._q.get()
            if job is None:
                return
            job.run()

    def _steal_one(self) -> bool:
        """Run one queued job on the calling thread, if any is waiting."""
        try:
            job = self._q.get_nowait()
        except _queue.Empty:
            return False
        if job is None:
            # keep the shutdown sentinel flowing to a real worker
            self._q.put(None)
            return False
        job.run()
        return True

    # -- caller side ----------------------------------------------------
    def shard_bounds(self, n: int) -> list[tuple[int, int]]:
        """Contiguous ``[lo, hi)`` spans covering ``[0, n)``, one per worker.

        Early shards get the remainder, so spans differ in length by at
        most one row; empty spans are dropped (n < workers).
        """
        w = min(self.workers, max(1, n))
        base, extra = divmod(n, w)
        bounds = []
        lo = 0
        for i in range(w):
            hi = lo + base + (1 if i < extra else 0)
            if hi > lo:
                bounds.append((lo, hi))
            lo = hi
        return bounds

    def map_shards(self, n: int, fn) -> tuple[list, float]:
        """Run ``fn(lo, hi)`` over contiguous shards of ``[0, n)``.

        Returns ``(results, pool_wait_s)``: per-shard results in shard
        order, and the wall time this caller spent blocked on shards it
        did not execute itself (the "host-bound on the queue" half of
        the trace/report.py critical-path split). The last shard always
        runs inline on the caller; while any submitted shard is still
        pending the caller drains the queue, so a congested shared pool
        costs queueing delay, never deadlock.
        """
        bounds = self.shard_bounds(n)
        if len(bounds) <= 1 or self._closed:
            lo, hi = bounds[0] if bounds else (0, 0)
            return [fn(lo, hi)], 0.0
        jobs = [self.submit(fn, lo, hi) for lo, hi in bounds[:-1]]
        lo, hi = bounds[-1]
        inline = _Job(fn, lo, hi)
        inline.run()
        wait_s = 0.0
        steals = 0
        for job in jobs:
            if job.done.is_set():
                continue
            # steal queued work (ours or another caller's) before parking.
            # Count locally — concurrent callers steal at once, and an
            # unlocked `self.steals_total += 1` here loses increments
            # (race-auditor finding; the counter folds in under the
            # stats lock below with the rest of this call's tallies).
            while not job.done.is_set() and self._steal_one():
                steals += 1
            if not job.done.is_set():
                t0 = monotonic()
                job.done.wait()
                wait_s += monotonic() - t0
        results = []
        for job in jobs + [inline]:
            if job.error is not None:
                raise job.error
            results.append(job.result)
        with self._stats_mtx:
            self._sh_stats.note_write()
            self.jobs_total += len(bounds)
            self.steals_total += steals
            self.pool_wait_s += wait_s
        return results, wait_s

    def stats(self) -> dict:
        with self._stats_mtx:
            self._sh_stats.note_read()
            return {
                "backend": self.backend,
                "workers": self.workers,
                "jobs_total": self.jobs_total,
                "steals_total": self.steals_total,
                "pool_wait_s": self.pool_wait_s,
            }

    def close(self, timeout: float = 1.0) -> None:
        """Stop the worker threads (idempotent; pending jobs still run)."""
        if self._closed:
            return
        self._closed = True
        for _ in self._threads:
            self._q.put(None)
        for t in self._threads:
            t.join(timeout=timeout)


# ---------------------------------------------------------------------------
# Process backend


def _default_mp_method() -> str:
    """forkserver > spawn > fork: the forkserver's children fork from a
    clean helper process — never from this one, whose jax runtime threads
    and locked allocator arenas make direct fork a deadlock lottery —
    while staying an order of magnitude cheaper per worker than spawn
    once the server is warm. The worker target (prep_proc.worker_main)
    lives in an import-light module precisely so spawn/forkserver
    children never pay the jax import."""
    import multiprocessing as mp

    methods = mp.get_all_start_methods()
    for m in ("forkserver", "spawn", "fork"):
        if m in methods:
            return m
    return "spawn"


class ProcHostPrepPool:
    """Process-backed host-prep pool: typed shared-memory prep tasks plus
    a full embedded thread pool for everything else.

    ``workers`` counts the calling thread, exactly like the thread
    backend: a pool of 4 spawns 3 worker PROCESSES (and 3 fallback
    threads) and always runs the last shard inline on the caller — so a
    dead worker or a broken pool only ever degrades throughput, never
    correctness. Typed tasks (``prepare_compact_shm``,
    ``sign_bytes_shm``) marshal inputs into one shared-memory segment,
    let workers write contiguous output shards into a second, and copy
    the assembled arrays out before unlinking both — per-call segments,
    nothing persistent to version or leak. Generic ``submit`` /
    ``map_shards`` closures delegate to the embedded ``HostPrepPool``
    untouched.

    Failure envelope: construction raises ``HostPoolSpawnError`` unless
    every worker acks ready within ``spawn_timeout`` (callers fall back
    to the thread backend via ``make_host_pool``). At runtime a missing
    shard ack — worker crash, OOM-kill — is recomputed inline by the
    caller (byte-identical by construction: same row function, same
    rows) and flips ``broken``, steering later batches to the embedded
    thread pool. Stale acks from a slow-not-dead worker are ignored by
    call sequence number, and its late writes land either on rows the
    caller already recomputed with identical bytes or on an unlinked
    segment nobody will read.
    """

    backend = "process"

    def __init__(
        self,
        workers: int,
        name: str = "hostprep",
        mp_context: str | None = None,
        spawn_timeout: float = 10.0,
        shard_timeout: float = 30.0,
    ):
        self.workers = max(1, int(workers))
        self._inner = HostPrepPool(self.workers, name=name)
        self._closed = False
        self._broken = False
        self._stats_mtx = make_lock("engine.ProcHostPrepPool._stats_mtx")
        # shm stats + live-segment registry + call sequence + broken flag
        self._sh_stats = shared_field("engine.ProcHostPrepPool.stats")  # txlint: shared(self._stats_mtx)
        self._shard_timeout = shard_timeout
        self._call_seq = 0
        self.shm_calls = 0
        self.shm_bytes_total = 0
        self.proc_jobs_total = 0
        self.proc_wait_s = 0.0
        self.inline_recoveries = 0
        self._procs: list = []
        self._live_segs: dict[str, object] = {}
        self.mp_method = None
        if self.workers <= 1:
            register_pool(self)
            return  # degenerate pool: all typed work runs inline
        import multiprocessing as mp

        from .. import prep_proc

        method = mp_context or _default_mp_method()
        try:
            ctx = mp.get_context(method)
            self._task_q = ctx.SimpleQueue()
            self._done_q = ctx.Queue()
            for i in range(self.workers - 1):
                p = ctx.Process(
                    target=prep_proc.worker_main,
                    args=(self._task_q, self._done_q),
                    name=f"{name}-proc-{i}",
                    daemon=True,
                )
                p.start()
                self._procs.append(p)
            deadline = monotonic() + spawn_timeout
            ready = 0
            while ready < len(self._procs):
                left = deadline - monotonic()
                if left <= 0:
                    raise TimeoutError("worker ready handshake timed out")
                try:
                    ack = self._done_q.get(timeout=left)
                except _queue.Empty:
                    raise TimeoutError("worker ready handshake timed out")
                if isinstance(ack, tuple) and ack and ack[0] == "ready":
                    ready += 1
        except Exception as exc:
            self._terminate()
            self._inner.close()
            raise HostPoolSpawnError(
                f"process host-prep pool failed to start ({method}): {exc}"
            ) from exc
        self.mp_method = method
        register_pool(self)

    # -- generic API: delegate to the embedded thread pool ---------------
    def submit(self, fn, lo: int, hi: int):
        """Enqueue a generic closure shard on the embedded thread pool
        (closures can't cross the process boundary). Pure delegation —
        stays on the thread backend's lock-free enqueue."""
        return self._inner.submit(fn, lo, hi)

    def shard_bounds(self, n: int) -> list[tuple[int, int]]:
        return self._inner.shard_bounds(n)

    def map_shards(self, n: int, fn) -> tuple[list, float]:
        return self._inner.map_shards(n, fn)

    @property
    def healthy(self) -> bool:
        """True while typed tasks still route to worker processes."""
        return bool(self._procs) and not self._broken and not self._closed

    # -- typed shared-memory tasks ---------------------------------------
    def prepare_compact_shm(self, msgs, sigs, val_idx, epoch):
        """Compact ed25519 prep across worker processes.

        Returns ``(s_nib, h_nib, vidx, r_y, r_sign, pre_ok, wait_s)`` or
        None when the process path is unavailable (caller falls back to
        thread shards — same bytes either way)."""
        if not self.healthy:
            return None
        from .. import prep_proc

        n = len(msgs)
        msg_cat, offs = prep_proc.cat_msgs(msgs)
        sig_arr, sig_ok = prep_proc.cat_sigs(sigs)
        ins = {
            "msg_cat": msg_cat,
            "offs": offs,
            "sig_arr": sig_arr,
            "sig_ok": sig_ok,
            "vi": np.asarray(val_idx, dtype=np.int64),
            "pub_arr": epoch.pub_arr,
            "key_ok": epoch.key_ok,
        }
        outs_spec = {
            "s_nib": ((n, 64), np.uint8),
            "h_nib": ((n, 64), np.uint8),
            "vidx": ((n,), np.int32),
            "r_y": ((n, 32), np.uint8),
            "r_sign": ((n,), np.uint8),
            "pre_ok": ((n,), np.uint8),
        }
        res = self._run_typed("compact", ins, None, outs_spec, n)
        if res is None:
            return None
        o, wait_s = res
        return (
            o["s_nib"], o["h_nib"], o["vidx"], o["r_y"], o["r_sign"],
            o["pre_ok"].astype(bool), wait_s,
        )

    def sign_bytes_shm(self, heights, tx_hashes, ts_ns, chain_id: str):
        """Canonical sign bytes across worker processes.

        Returns ``(list[bytes], wait_s)`` or None when the process path
        is unavailable or the batch has hostile out-of-band fields
        (oversize hash, height/timestamp beyond int64) — those route
        through the per-vote Python encoder instead."""
        if not self.healthy:
            return None
        from .. import prep_proc

        n = len(heights)
        hb = [h.encode("utf-8", "surrogatepass") for h in tx_hashes]
        max_hash = max((len(b) for b in hb), default=0)
        if max_hash > 1024:
            return None  # hostile oversize hash: don't size shm by it
        try:
            hs = np.asarray(heights, dtype=np.int64)
            ts = np.asarray(ts_ns, dtype=np.int64)
        except (OverflowError, ValueError):
            return None
        hash_offs = np.zeros(n + 1, np.int64)
        np.cumsum(np.fromiter((len(b) for b in hb), np.int64, n), out=hash_offs[1:])
        hash_cat = (
            np.frombuffer(b"".join(hb), np.uint8) if n else np.zeros(0, np.uint8)
        )
        stride = prep_proc.sign_bytes_stride(max_hash, chain_id)
        ins = {
            "heights": hs,
            "ts_ns": ts,
            "hash_cat": hash_cat,
            "hash_offs": hash_offs,
        }
        outs_spec = {
            "rows": ((n, stride), np.uint8),
            "lens": ((n,), np.int32),
        }
        res = self._run_typed(
            "signbytes", ins, {"chain_id": chain_id}, outs_spec, n
        )
        if res is None:
            return None
        o, wait_s = res
        rows, lens = o["rows"], o["lens"]
        return [rows[i, : lens[i]].tobytes() for i in range(n)], wait_s

    # -- machinery --------------------------------------------------------
    def _run_typed(self, task, ins, extra, outs_spec, n):
        """Fan one typed task out as contiguous shards over shm segments.

        The caller packs inputs, runs the LAST shard inline, then blocks
        on per-shard acks; missing or errored shards are recomputed
        inline (and a timeout marks the pool broken). Returns
        ``(outputs_by_name, wait_s)`` with the outputs copied out of the
        (already unlinked) segment, or None when the pool can't take
        typed work."""
        if not self.healthy or n <= 0:
            return None
        from multiprocessing import shared_memory

        from .. import prep_proc

        in_layout, in_bytes = prep_proc.pack_layout(ins)
        out_arrays = {
            name: np.zeros(shape, dtype) for name, (shape, dtype) in outs_spec.items()
        }
        out_layout, out_bytes = prep_proc.pack_layout(out_arrays)
        seg_in = shared_memory.SharedMemory(create=True, size=in_bytes)
        seg_out = shared_memory.SharedMemory(create=True, size=out_bytes)
        self._track(seg_in, seg_out)
        ins_views = outs_views = None
        wait_s = 0.0
        recompute: list[tuple[int, int]] = []
        try:
            prep_proc.write_arrays(seg_in.buf, in_layout, ins)
            bounds = self._inner.shard_bounds(n)
            with self._stats_mtx:
                self._sh_stats.note_write()
                self._call_seq += 1
                call = self._call_seq
            pending: dict[tuple, tuple[int, int]] = {}
            for idx, (lo, hi) in enumerate(bounds[:-1]):
                sid = (call, idx)
                pending[sid] = (lo, hi)
                self._task_q.put((
                    "task", task, sid, seg_in.name, in_layout,
                    seg_out.name, out_layout, lo, hi, extra,
                ))
            ins_views = prep_proc.views(seg_in.buf, in_layout)
            if extra:
                ins_views = {**ins_views, **extra}
            outs_views = prep_proc.views(seg_out.buf, out_layout)
            lo, hi = bounds[-1]
            prep_proc.run_task(task, ins_views, outs_views, lo, hi)
            deadline = monotonic() + self._shard_timeout
            while pending:
                left = deadline - monotonic()
                if left <= 0:
                    break
                t0 = monotonic()
                try:
                    ack = self._done_q.get(timeout=left)
                except _queue.Empty:
                    wait_s += monotonic() - t0
                    break
                wait_s += monotonic() - t0
                if not (isinstance(ack, tuple) and len(ack) == 3):
                    continue
                sid, err, _busy = ack
                span = pending.pop(sid, None)
                if span is not None and err is not None:
                    recompute.append(span)
            if pending:
                # lost worker: its shards never acked — recompute inline
                # and stop routing typed work at this pool
                recompute.extend(pending.values())
                with self._stats_mtx:
                    self._sh_stats.note_write()
                    self._broken = True
            for lo, hi in recompute:
                prep_proc.run_task(task, ins_views, outs_views, lo, hi)
            out = {name: np.array(view) for name, view in outs_views.items()}
        finally:
            ins_views = None
            outs_views = None
            self._untrack(seg_in, seg_out)
        with self._stats_mtx:
            self._sh_stats.note_write()
            self.shm_calls += 1
            self.shm_bytes_total += in_bytes + out_bytes
            self.proc_jobs_total += len(bounds)
            self.proc_wait_s += wait_s
            self.inline_recoveries += len(recompute)
        return out, wait_s

    def _track(self, *segs) -> None:
        with self._stats_mtx:
            self._sh_stats.note_write()
            for s in segs:
                self._live_segs[s.name] = s

    def _untrack(self, *segs) -> None:
        with self._stats_mtx:
            self._sh_stats.note_write()
            for s in segs:
                self._live_segs.pop(s.name, None)
        for s in segs:
            try:
                s.close()
            except BufferError:
                pass
            try:
                s.unlink()
            except FileNotFoundError:
                pass

    def _terminate(self) -> None:
        for p in self._procs:
            try:
                if p.is_alive():
                    p.terminate()
                p.join(timeout=0.5)
            except Exception:
                pass
        self._procs = []

    def stats(self) -> dict:
        s = self._inner.stats()
        with self._stats_mtx:
            self._sh_stats.note_read()
            s.update(
                backend=self.backend,
                mp_method=self.mp_method,
                processes=len(self._procs),
                healthy=self.healthy,
                shm_calls=self.shm_calls,
                shm_bytes_total=self.shm_bytes_total,
                proc_jobs_total=self.proc_jobs_total,
                proc_wait_s=self.proc_wait_s,
                inline_recoveries=self.inline_recoveries,
            )
        return s

    def close(self, timeout: float = 1.0) -> None:
        """Stop workers and unlink any tracked shm segment (idempotent)."""
        if self._closed:
            return
        self._closed = True
        for _ in self._procs:
            try:
                self._task_q.put(None)
            except Exception:
                break
        for p in self._procs:
            try:
                p.join(timeout=timeout)
            except Exception:
                pass
        self._terminate()
        for q in (getattr(self, "_done_q", None),):
            try:
                q.close()
            except Exception:
                pass
        self._inner.close(timeout=timeout)
        with self._stats_mtx:
            self._sh_stats.note_write()
            segs = list(self._live_segs.values())
            self._live_segs.clear()
        for s in segs:
            try:
                s.close()
            except Exception:
                pass
            try:
                s.unlink()
            except Exception:
                pass


def make_host_pool(
    workers: int,
    backend: str = "thread",
    name: str = "hostprep",
    mp_context: str | None = None,
):
    """Backend-dispatching pool factory with graceful degradation.

    ``backend="process"`` tries ``ProcHostPrepPool`` and falls back to
    the thread backend if worker processes can't be spawned (restricted
    sandboxes, exhausted pids) — callers check ``pool.backend`` for what
    they actually got."""
    workers = max(1, int(workers))
    if backend == "process" and workers > 1:
        try:
            return ProcHostPrepPool(workers, name=name, mp_context=mp_context)
        except HostPoolSpawnError:
            pass
    return HostPrepPool(workers, name=name)
