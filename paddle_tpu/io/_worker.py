"""DataLoader worker-side code.

Kept in its own module with NO framework imports at module scope: under the
"forkserver"/"spawn" start methods the worker process imports this module to
unpickle its target, and it must not drag jax (or the whole paddle_tpu
package) into every worker — numpy is all the hot path needs.  The fork
start method shares this code too.

Reference: the worker half of python/paddle/fluid/reader.py:412
(_worker_loop + shared-memory tensor transfer).
"""
from __future__ import annotations

import numpy as np

SHM_MIN_BYTES = 1 << 14  # small arrays go through the pickle queue


def default_collate_fn(batch):
    """Stack samples into batched numpy arrays (reference: reader.py
    default_collate).  Framework Tensors are detected lazily so this module
    stays importable without jax."""
    sample = batch[0]
    if isinstance(sample, (list, tuple)):
        return tuple(default_collate_fn([b[i] for b in batch])
                     for i in range(len(sample)))
    if isinstance(sample, dict):
        return {k: default_collate_fn([b[k] for b in batch]) for k in sample}
    if type(sample).__name__ == "Tensor" and hasattr(sample, "_data"):
        return np.stack([np.asarray(b._data) for b in batch])
    if isinstance(sample, np.ndarray):
        return np.stack(batch)
    if isinstance(sample, (int, float, np.integer, np.floating)):
        return np.asarray(batch)
    return batch


def fetch(dataset, indices, collate_fn):
    return collate_fn([dataset[i] for i in indices])


class ShmRef:
    __slots__ = ("name", "shape", "dtype")

    def __init__(self, name, shape, dtype):
        self.name = name
        self.shape = shape
        self.dtype = dtype


def encode(obj, use_shm):
    from multiprocessing import shared_memory
    if isinstance(obj, tuple):
        return tuple(encode(o, use_shm) for o in obj)
    if isinstance(obj, list):
        return [encode(o, use_shm) for o in obj]
    if isinstance(obj, dict):
        return {k: encode(v, use_shm) for k, v in obj.items()}
    if (use_shm and isinstance(obj, np.ndarray)
            and obj.nbytes >= SHM_MIN_BYTES):
        shm = shared_memory.SharedMemory(create=True, size=obj.nbytes)
        view = np.ndarray(obj.shape, obj.dtype, buffer=shm.buf)
        view[...] = obj
        ref = ShmRef(shm.name, obj.shape, str(obj.dtype))
        shm.close()
        # ownership transfers to the consumer (which unlinks after copying);
        # drop this process's resource-tracker claim so its exit cleanup
        # doesn't race a block the parent already removed
        try:
            from multiprocessing import resource_tracker
            resource_tracker.unregister(shm._name, "shared_memory")
        except Exception:
            pass
        return ref
    return obj


def decode(obj):
    from multiprocessing import shared_memory
    if isinstance(obj, tuple):
        return tuple(decode(o) for o in obj)
    if isinstance(obj, list):
        return [decode(o) for o in obj]
    if isinstance(obj, dict):
        return {k: decode(v) for k, v in obj.items()}
    if isinstance(obj, ShmRef):
        shm = shared_memory.SharedMemory(name=obj.name)
        try:
            view = np.ndarray(obj.shape, np.dtype(obj.dtype), buffer=shm.buf)
            out = np.array(view)  # own the data before releasing the block
        finally:
            shm.close()
            shm.unlink()
        return out
    return obj


_worker_info = None  # set inside worker processes (io.get_worker_info)


class WorkerInfo:
    """reference: paddle.io.get_worker_info — worker id / pool size /
    dataset handle (lives here so worker processes never import jax)."""

    def __init__(self, id, num_workers, dataset=None):  # noqa: A002
        self.id = id
        self.num_workers = num_workers
        self.dataset = dataset


def _maybe_crash(seq, raw):
    """Fault injection (mirror of utils.faults.maybe_crash_worker, parsed
    inline so worker processes never import the framework): `raw` is the
    PDTPU_FAULT_WORKER_CRASH config string, read by the PARENT at worker
    spawn time and passed as a worker arg — a forkserver's cached
    environment must not decide whether a fault is armed.
    "kill:S[:/path/once]" hard-exits this worker when it picks up batch seq
    S (mode "exc" raises instead); the optional `once` sentinel file limits
    the fault to a single firing so the respawned worker survives the
    retried batch."""
    import os
    if not raw:
        return
    parts = raw.split(":", 2)
    if parts[0] in ("kill", "exc"):
        mode, target = parts[0], int(parts[1])
        once = parts[2] if len(parts) == 3 else None
    else:
        mode, target, once = "kill", int(parts[0]), None
    if seq != target:
        return
    if once is not None:
        if os.path.exists(once):
            return
        open(once, "w").close()
    if mode == "exc":
        raise RuntimeError(f"injected worker exception at seq {seq}")
    os._exit(17)  # hard crash: no result, no cleanup — the real thing


def worker_loop(dataset, collate_fn, task_q, result_conn, worker_id,
                use_shm, worker_init_fn, num_workers=0, crash_cfg=None):
    """`result_conn` is this worker's own pipe to the parent: `send` writes
    in this thread and takes no lock another worker could die holding."""
    global _worker_info
    _worker_info = WorkerInfo(worker_id, num_workers, dataset)
    if worker_init_fn is not None:
        worker_init_fn(worker_id)
    while True:
        item = task_q.get()
        if item is None:
            break
        epoch, seq, indices = item
        try:
            _maybe_crash(seq, crash_cfg)
            batch = encode(fetch(dataset, indices, collate_fn), use_shm)
            result_conn.send((epoch, seq, batch, None))
        except Exception as e:  # surface worker errors to the parent
            result_conn.send((epoch, seq, None,
                              f"{type(e).__name__}: {e}"))
