"""DataLoader.

Reference: python/paddle/fluid/reader.py:412 (DataLoader: forked worker
processes + shared-memory tensor transfer + _DataLoaderIter reorder logic)
feeding operators/reader/buffered_reader.cc (device double-buffering).

TPU-native design:
- num_workers > 0 starts worker PROCESSES; each worker materializes+collates
  its index batch and ships the arrays through POSIX shared memory
  (multiprocessing.shared_memory), the analogue of the reference's mmap'd
  _shared_memory tensors.  Results are re-ordered by sequence number and the
  number of in-flight batches is bounded by num_workers * prefetch_factor —
  never the whole epoch.
- start method: "fork" matches the reference and is cheapest, but forking a
  process that already carries live XLA/jax runtime threads can deadlock
  the child on an inherited lock.  So when the parent is multi-threaded the
  pool defaults to "forkserver" (workers import only numpy + the user's
  dataset module — see io/_worker.py); `multiprocessing_context=` overrides.
- the consumer side stages batches onto the device asynchronously
  (jax.device_put pipeline) — the buffered_reader equivalent.
- persistent_workers keeps the pool alive across epochs; worker_init_fn
  runs once in each worker (reference semantics).
"""
from __future__ import annotations

import itertools
import multiprocessing as mp
import os
import pickle
import queue
import threading
import time
import warnings
from typing import Optional

import jax
import numpy as np

from ..core.tensor import Tensor
from .dataset import BatchSampler, IterableDataset
from ._worker import (default_collate_fn, fetch as _fetch,  # noqa: F401
                      decode as _decode, worker_loop as _worker_loop)


_obs_handles = None


def _obs():
    """(data_wait_histogram, queue_depth_gauge) — observability handles,
    created once and cached (registry.reset() zeroes values in place, so
    the cache stays valid)."""
    global _obs_handles
    if _obs_handles is None:
        from ..observability import metrics as _m
        _obs_handles = (
            _m.histogram("dataloader_data_wait_seconds",
                         "time the consumer waited for its next batch "
                         "(the train loop's data-starvation signal)"),
            _m.gauge("dataloader_queue_depth",
                     "device-prefetch queue depth seen at consume time"))
    return _obs_handles


def _default_mp_context() -> str:
    """"fork" when single-threaded (cheap, reference behavior); "forkserver"
    once runtime threads exist — forking a jax/XLA-threaded parent can
    deadlock the child on an inherited lock."""
    if threading.active_count() > 1:
        return "forkserver"
    return "fork"


class _WorkerDied(Exception):
    """Internal: a worker process exited with tasks in flight (respawnable)."""


class _WorkerPool:
    """Worker processes with bounded in-flight tasks + reordering.

    Dead workers (OOM-killed, segfaulted, fault-injected) are respawned —
    with backoff via utils.retry — and their lost tasks resubmitted, up to
    `max_respawns` per epoch (PDTPU_WORKER_RESPAWNS, default 2); only after
    that budget does the epoch fail with UnavailableError.
    """

    def __init__(self, dataset, collate_fn, num_workers, use_shm,
                 worker_init_fn, timeout, mp_context=None,
                 max_respawns=None):
        if mp_context is None or isinstance(mp_context, str):
            method = mp_context or _default_mp_context()
        else:
            method = mp_context.get_start_method()
        self._timeout = timeout if timeout and timeout > 0 else None
        self._epoch = 0
        if max_respawns is None:
            max_respawns = int(os.environ.get("PDTPU_WORKER_RESPAWNS", "2"))
        self._max_respawns = max_respawns
        try:
            self._start(mp.get_context(method), dataset, collate_fn,
                        num_workers, use_shm, worker_init_fn)
        except (AttributeError, TypeError, pickle.PicklingError) as e:
            if method == "fork" or mp_context is not None:
                raise
            # forkserver/spawn needs picklable dataset/collate/init_fn;
            # locally-defined ones force the fork path (reference behavior,
            # at the cost of fork-with-threads deadlock risk)
            warnings.warn(
                f"DataLoader falling back to fork workers: {e} "
                "(make dataset/collate_fn/worker_init_fn module-level "
                "picklables to use the thread-safe forkserver start method)",
                RuntimeWarning)
            self._start(mp.get_context("fork"), dataset, collate_fn,
                        num_workers, use_shm, worker_init_fn)

    def _start(self, ctx, dataset, collate_fn, num_workers, use_shm,
               worker_init_fn):
        self._ctx = ctx
        self._worker_args = (dataset, collate_fn, use_shm, worker_init_fn,
                             num_workers)
        self._task_q = ctx.Queue()
        # results come back on a pipe a worker.  One queue for all of them
        # has one write lock, and a worker that dies (os._exit, the OOM
        # killer) while its feeder thread holds that lock blocks every
        # surviving worker's put for ever, with all workers alive.
        self._readers = [None] * num_workers
        self._procs = []
        try:
            for wid in range(num_workers):
                self._procs.append(self._spawn_worker(wid))
        except Exception:
            for p in self._procs:
                if p.is_alive():
                    p.terminate()
            self._procs = []
            self._close_readers()
            raise

    def _spawn_worker(self, wid):
        """Start worker `wid` on a result pipe of its own (a dead
        predecessor's pipe is closed: what it held is lost and its tasks
        are resubmitted)."""
        dataset, collate_fn, use_shm, worker_init_fn, nw = self._worker_args
        # fault config is read HERE (parent, spawn time) and passed as an
        # arg: a forkserver's cached environment must not decide whether
        # the injection is armed — and a respawned worker picks up the
        # config current at respawn time (disarmed once the test clears it)
        from ..utils import faults as _faults
        reader, writer = self._ctx.Pipe(duplex=False)
        try:
            p = self._ctx.Process(
                target=_worker_loop,
                args=(dataset, collate_fn, self._task_q, writer, wid,
                      use_shm, worker_init_fn, nw,
                      _faults.get("worker_crash")),
                daemon=True)
            p.start()
        except Exception:
            reader.close()
            raise
        finally:
            # the worker holds the only write end: its death reads as EOF
            writer.close()
        self._drop_reader(wid)
        self._readers[wid] = reader
        return p

    def _drop_reader(self, wid):
        if self._readers[wid] is not None:
            self._readers[wid].close()
            self._readers[wid] = None

    def _close_readers(self):
        for wid in range(len(self._readers)):
            self._drop_reader(wid)

    def respawn_dead(self):
        """Replace every dead worker process; returns how many were
        replaced.  Transient spawn failures (fd/pid exhaustion under load)
        back off and retry via the shared RetryPolicy."""
        from ..utils.monitor import stat_add
        from ..utils.retry import RetryPolicy
        replaced = 0
        policy = RetryPolicy(retries=2, base_delay=0.1, max_delay=1.0,
                             retry_on=(OSError, RuntimeError))
        for i, p in enumerate(self._procs):
            if p.is_alive():
                continue
            self._procs[i] = policy.call(self._spawn_worker, i)
            replaced += 1
        if replaced:
            stat_add("STAT_dataloader_worker_respawns", replaced)
        return replaced

    def _recv(self, wid):
        """One result off worker `wid`'s pipe, or None when the pipe has
        ended (the worker died, maybe in the middle of a message)."""
        try:
            return self._readers[wid].recv()
        except (EOFError, OSError):
            self._drop_reader(wid)
            return None

    def _get_result(self):
        """Blocking result fetch that detects dead workers and honors the
        user timeout with a meaningful error (reference: reader.py raises on
        worker exit; torch detects OOM-killed workers the same way)."""
        from multiprocessing.connection import wait
        waited = 0.0
        while True:
            live = {r: wid for wid, r in enumerate(self._readers)
                    if r is not None}
            ready = wait(list(live), timeout=1.0)
            for r in ready:
                result = self._recv(live[r])
                if result is not None:
                    return result
            if not self.alive() or not live:
                raise _WorkerDied()
            if ready:       # a pipe ended at once: no second was waited
                continue
            waited += 1.0
            if self._timeout is not None and waited >= self._timeout:
                from ..core.errors import ExecutionTimeoutError
                raise ExecutionTimeoutError(
                    f"[ExecutionTimeout] DataLoader worker timed out "
                    f"after {waited:.0f}s")

    def run(self, index_batches, max_in_flight):
        """Yield collated numpy batches in order.

        Every task/result carries an epoch id: stale in-flight results from
        an abandoned or failed earlier run (persistent workers) are decoded
        and dropped — decoding frees their shared-memory blocks and keeps
        sequence numbers from colliding across epochs.  A worker death
        respawns the dead workers and resubmits every submitted-but-
        undelivered task; duplicate deliveries (a surviving worker also had
        the task) are decoded and dropped."""
        self._epoch += 1
        epoch = self._epoch
        it = enumerate(index_batches)
        pending = {}
        outstanding = {}  # seq -> indices, submitted but not yet received
        next_seq = 0
        exhausted = False
        respawns_left = self._max_respawns
        try:
            while True:
                while not exhausted and len(outstanding) < max_in_flight:
                    try:
                        seq, idx = next(it)
                    except StopIteration:
                        exhausted = True
                        break
                    idx = list(idx)
                    self._task_q.put((epoch, seq, idx))
                    outstanding[seq] = idx
                if not outstanding and next_seq not in pending:
                    return
                while next_seq not in pending:
                    try:
                        ep, seq, batch, err = self._get_result()
                    except _WorkerDied:
                        if respawns_left <= 0:
                            from ..core.errors import UnavailableError
                            raise UnavailableError(
                                "[Unavailable] DataLoader worker process "
                                "died unexpectedly (killed or crashed) "
                                f"with a task in flight; respawn budget "
                                f"({self._max_respawns}) exhausted")
                        respawns_left -= 1
                        self.respawn_dead()
                        # the dead worker's tasks are lost — resubmit every
                        # undelivered one (dupes from surviving workers are
                        # dropped below)
                        for seq2, idx2 in sorted(outstanding.items()):
                            self._task_q.put((epoch, seq2, idx2))
                        continue
                    if ep != epoch or seq < next_seq or seq in pending:
                        if batch is not None:
                            _decode(batch)  # free stale/duplicate shm
                        continue
                    if err is not None:
                        raise RuntimeError(
                            f"DataLoader worker failed: {err}")
                    pending[seq] = batch
                    outstanding.pop(seq, None)
                yield _decode(pending.pop(next_seq))
                next_seq += 1
        finally:
            # abandoned / failed epoch: free every shm block we can see now;
            # later-arriving strays are freed by the stale-epoch branch above
            # on the next run, or by shutdown()'s drain
            for b in pending.values():
                _decode(b)
            self._drain()

    def _drain(self):
        """Decode-and-discard everything currently in the result pipes
        (frees shared-memory blocks whose ownership passed to this side)."""
        for wid in range(len(self._readers)):
            try:
                while (self._readers[wid] is not None
                       and self._readers[wid].poll()):
                    result = self._recv(wid)
                    if result is not None and result[2] is not None:
                        _decode(result[2])
            except Exception:
                continue

    def shutdown(self):
        import time as _time
        for _ in self._procs:
            try:
                self._task_q.put(None)
            except Exception:
                pass
        # drain WHILE joining: a worker blocked on a full result pipe can
        # only reach its exit sentinel if this side keeps consuming (and
        # decoding frees the shm ownership that was transferred to us)
        deadline = _time.monotonic() + 5.0
        procs = list(self._procs)
        while procs and _time.monotonic() < deadline:
            self._drain()
            procs = [p for p in procs if p.is_alive()]
            if procs:
                procs[0].join(timeout=0.1)
        for p in procs:
            p.terminate()
        self._procs = []
        self._drain()  # workers have exited: anything left is ours to free
        self._close_readers()

    def alive(self):
        return bool(self._procs) and all(p.is_alive() for p in self._procs)


class DataLoader:
    """paddle.io.DataLoader — iterates device-resident batches."""

    def __init__(self, dataset, feed_list=None, places=None,
                 return_list=True, batch_sampler=None, batch_size=1,
                 shuffle=False, drop_last=False, collate_fn=None,
                 num_workers=0, use_buffer_reader=True, prefetch_factor=2,
                 use_shared_memory=True, timeout=0, worker_init_fn=None,
                 persistent_workers=False, multiprocessing_context=None):
        from ..core.errors import InvalidArgumentError
        if batch_sampler is None and (not isinstance(batch_size, int)
                                      or batch_size <= 0):
            raise InvalidArgumentError(
                f"[DataLoader] batch_size must be a positive int, got "
                f"{batch_size!r}")
        if not isinstance(num_workers, int) or num_workers < 0:
            raise InvalidArgumentError(
                f"[DataLoader] num_workers must be a non-negative int, got "
                f"{num_workers!r}")
        if timeout and timeout < 0:
            raise InvalidArgumentError(
                f"[DataLoader] timeout must be >= 0, got {timeout!r}")
        self.dataset = dataset
        self.return_list = return_list
        self.collate_fn = collate_fn or default_collate_fn
        self.num_workers = num_workers
        self.prefetch_factor = max(2, prefetch_factor)
        self.prefetch = self.prefetch_factor if use_buffer_reader else 0
        self.use_shared_memory = use_shared_memory
        self.timeout = timeout
        self.worker_init_fn = worker_init_fn
        self.persistent_workers = persistent_workers
        self.multiprocessing_context = multiprocessing_context
        self._iterable_mode = isinstance(dataset, IterableDataset)
        if self._iterable_mode:
            if num_workers:
                import warnings
                warnings.warn(
                    "DataLoader(num_workers>0) over an IterableDataset "
                    "runs single-process: parallel workers would need "
                    "stream sharding the dataset does not declare "
                    "(map-style datasets DO use the worker pool)",
                    stacklevel=2)
                self.num_workers = 0
            self.batch_sampler = None
            self.batch_size = batch_size
            self.drop_last = drop_last
        elif batch_sampler is not None:
            self.batch_sampler = batch_sampler
        else:
            self.batch_sampler = BatchSampler(dataset, shuffle=shuffle,
                                              batch_size=batch_size,
                                              drop_last=drop_last)
        self._pool: Optional[_WorkerPool] = None
        self._pool_busy = False
        self._pool_lock = threading.Lock()
        # owned (non-persistent) pools of live iterations, so an abandoned
        # iterator whose producer thread is wedged can still be torn down
        # from close()/__del__ instead of leaking worker processes
        self._owned_pools: set = set()

    def __len__(self):
        if self._iterable_mode:
            raise TypeError("IterableDataset has no length")
        return len(self.batch_sampler)

    def close(self):
        """Shut down the persistent pool and any owned pools left behind by
        abandoned iterators.  Idempotent; also runs from __del__."""
        with self._pool_lock:
            owned = list(self._owned_pools)
            self._owned_pools.clear()
            pool, self._pool = self._pool, None
            self._pool_busy = False
        for p in owned + ([pool] if pool is not None else []):
            try:
                p.shutdown()
            except Exception:
                pass

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    def _new_pool(self):
        # transient spawn failures (fd/pid exhaustion on a loaded host)
        # back off and retry; real config errors (unpicklable dataset under
        # an explicit spawn context) surface immediately
        from ..utils.retry import retry_call
        return retry_call(
            _WorkerPool, self.dataset, self.collate_fn, self.num_workers,
            self.use_shared_memory, self.worker_init_fn, self.timeout,
            mp_context=self.multiprocessing_context,
            retries=2, base_delay=0.2, max_delay=2.0,
            retry_on=(OSError,))

    def _acquire_pool(self):
        """Returns (pool, owned): owned pools are shut down by the caller.
        Persistent workers are reused across epochs, but a concurrent second
        iterator over the same loader gets its own temporary pool (the shared
        result queue cannot serve two epochs at once).  The check-and-mark is
        under a lock: two threads iterating one loader must not both claim
        the persistent pool."""
        if not self.persistent_workers:
            return self._new_pool(), True
        with self._pool_lock:
            if self._pool is not None and (self._pool_busy
                                           or not self._pool.alive()):
                if not self._pool_busy:
                    self._pool.shutdown()
                    self._pool = None
                else:  # concurrent iteration: temporary private pool
                    return self._new_pool(), True
            if self._pool is None:
                self._pool = self._new_pool()
            self._pool_busy = True
            return self._pool, False

    def _batches_numpy(self, pool_box=None):
        if self._iterable_mode:
            # workers for iterable datasets would need stream sharding;
            # single-process here (the common map-style path is parallel)
            it = iter(self.dataset)
            while True:
                chunk = list(itertools.islice(it, self.batch_size))
                if not chunk:
                    return
                if len(chunk) < self.batch_size and self.drop_last:
                    return
                yield self.collate_fn(chunk)
        elif self.num_workers > 0:
            pool, owned = self._acquire_pool()
            if owned:
                with self._pool_lock:
                    self._owned_pools.add(pool)
                if pool_box is not None:
                    pool_box.append(pool)
            max_in_flight = self.num_workers * self.prefetch_factor
            try:
                yield from pool.run(self.batch_sampler, max_in_flight)
            finally:
                if owned:
                    with self._pool_lock:
                        self._owned_pools.discard(pool)
                    pool.shutdown()
                else:
                    with self._pool_lock:
                        self._pool_busy = False
        else:
            for idx in self.batch_sampler:
                yield _fetch(self.dataset, idx, self.collate_fn)

    def __iter__(self):
        # device prefetch pipeline (buffered_reader equivalent): stage the
        # next `prefetch` batches onto the device asynchronously.
        from ..utils.monitor import stat_add

        def to_device(np_batch):
            stat_add("STAT_dataloader_batch_count")
            stat_add("STAT_dataloader_bytes",
                     sum(a.nbytes for a in jax.tree_util.tree_leaves(np_batch)
                         if isinstance(a, np.ndarray)))
            return jax.tree_util.tree_map(
                lambda a: Tensor(jax.device_put(a)) if isinstance(a, np.ndarray) else a,
                np_batch)

        wait_h, depth_g = _obs()

        if self.prefetch <= 0:
            gen = self._batches_numpy()
            while True:
                t0 = time.perf_counter()
                try:
                    b = next(gen)
                except StopIteration:
                    return
                wait_h.observe(time.perf_counter() - t0)
                yield to_device(b)

        q: "queue.Queue" = queue.Queue(maxsize=self.prefetch)
        sentinel = object()
        stop = threading.Event()

        def put_bounded(item):
            # blocking put that aborts if the consumer has gone away
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return
                except queue.Full:
                    continue

        pool_box: list = []

        def producer():
            gen = self._batches_numpy(pool_box)
            try:
                for b in gen:
                    put_bounded(to_device(b))  # device_put is async
                    if stop.is_set():
                        break
            except BaseException as e:  # re-raised on the consumer side
                put_bounded(e)
            finally:
                gen.close()  # runs _batches_numpy's pool cleanup
                put_bounded(sentinel)

        t = threading.Thread(target=producer, daemon=True)
        t.start()
        try:
            while True:
                t0 = time.perf_counter()
                item = q.get()
                wait_h.observe(time.perf_counter() - t0)
                depth_g.set(q.qsize())
                if item is sentinel:
                    break
                if isinstance(item, BaseException):
                    raise item
                yield item
        finally:
            stop.set()  # consumer broke early: unblock + clean up producer
            while True:
                try:
                    q.get_nowait()
                except queue.Empty:
                    break
            t.join(timeout=5)
            if t.is_alive():
                # producer wedged (worker fetch stuck past the join budget):
                # don't leak THIS iteration's worker pool until process exit
                # — tear it down from here.  The producer's own cleanup then
                # finds dead queues and exits; its exception is swallowed by
                # put_bounded's stop check.
                from ..utils.monitor import stat_add
                stat_add("STAT_dataloader_forced_pool_teardowns")
                for p in pool_box:
                    with self._pool_lock:
                        self._owned_pools.discard(p)
                    try:
                        p.shutdown()
                    except Exception:
                        pass


class ResumableLoader:
    """Iteration cursor over a DataLoader (or any iterable of batches).

    The missing piece of crash-consistent resume: params/optimizer/rng ride
    in the checkpoint, but without the data position a resumed run replays
    batches it already trained on.  Wrap the loader, checkpoint
    `state_dict()` (TrainStep.save_checkpoint(data_cursor=...)), and after
    `load_state_dict` the first epoch fast-forwards past the already-
    consumed batches — drawing and discarding them, so any deterministic
    sampler (seeded shuffles included) lands on exactly the batch the
    interrupted run would have seen next.

        cursor = ResumableLoader(loader)
        meta = step.restore_checkpoint(ckpt)
        if meta and "data_cursor" in meta:
            cursor.load_state_dict(meta["data_cursor"])
        for batch in cursor:
            ...
    """

    def __init__(self, loader):
        self.loader = loader
        self.epoch = 0
        self.index = 0  # batches consumed in the current epoch
        self._skip = 0

    def state_dict(self) -> dict:
        return {"epoch": self.epoch, "index": self.index}

    def load_state_dict(self, state: dict):
        self.epoch = int(state.get("epoch", 0))
        self.index = 0
        self._skip = int(state.get("index", 0))

    def __iter__(self):
        from ..utils.monitor import stat_add
        # each iteration restarts the loader from batch 0, so the cursor
        # restarts too (a broken-off epoch must not leave a stale index
        # that a later checkpoint would fast-forward past); the load_
        # state_dict fast-forward belongs to the FIRST iteration only
        skip, self._skip = self._skip, 0
        self.index = 0
        for b in self.loader:
            if skip > 0:
                skip -= 1
                self.index += 1
                stat_add("STAT_dataloader_resume_skipped_batches")
                continue
            self.index += 1
            yield b
        self.epoch += 1
        self.index = 0

    def __len__(self):
        return len(self.loader)
