"""Fault injection for resilience testing.

Reference: the reference framework's fault tolerance (EDL preemption,
checkpoint_notify, reader worker restarts) ships with no way to *prove* the
recovery paths work — they are only exercised by real production failures.
This module gives every recovery path in paddle_tpu a deterministic trigger,
driven by env vars (so subprocess / worker-process faults inherit them) or
the in-process API, and the `faults`-marked test suite + resilience probe
use it to demonstrate end-to-end recovery in CI.

Injection points (consumed elsewhere in the framework):

  nan_grads       step k (or [a, b) range): compiled train steps poison the
                  gradients with NaN when step_no hits the window.  The
                  *presence* of the injection is decided at trace time, so
                  the production compiled step carries zero overhead.
                  Env: PDTPU_FAULT_NAN_GRADS="k" or "a:b".
  worker_crash    DataLoader worker hard-exits (mode "kill", exercising the
                  death-detect + respawn path) or raises (mode "exc",
                  exercising error propagation) when it picks up batch seq
                  S.  A `once` sentinel file makes the fault fire a single
                  time so the respawned worker can finish the batch.
                  Env: PDTPU_FAULT_WORKER_CRASH="kill:S[:/path/once]".
  kill_mid_save   checkpoint writer SIGKILLs its own process right before
                  the atomic rename of save number N (1-based), proving a
                  kill mid-save never corrupts the latest checkpoint.
                  Env: PDTPU_FAULT_KILL_MID_SAVE="N".
  backend_down    the bench backend probe reports the accelerator
                  unreachable without waiting out a real timeout.
                  Env: PDTPU_FAULT_BACKEND_DOWN="1".
  prefetch_stall  the host-embedding-table prefetch worker sleeps `ms`
                  milliseconds before every `every_n`-th row fetch
                  (default every fetch) — a slow host memory system /
                  storage tier.  Purely host-side and consulted live per
                  fetch, so it can be armed on a running pipeline.  The
                  async prefetch pipeline must degrade to synchronous-
                  fetch throughput (the consumer waits; prefetch misses
                  climb) WITHOUT changing any training result.
                  Env: PDTPU_FAULT_PREFETCH_STALL="ms[:every_n]".
  row_corrupt     poison ONE row (NaN) of the N-th (1-based) fetched row
                  slab AFTER it leaves the host table — a torn DMA /
                  bit-flipped transfer.  The pipeline's consume-side
                  finiteness verify must detect the poisoned copy and
                  refetch from the host table (the source of truth is
                  untouched), so training stays bit-identical to a clean
                  run.  Env: PDTPU_FAULT_ROW_CORRUPT="N".
  nan_logits      the serving engine's compiled decode step poisons the
                  logits of the request with submission sequence number N
                  (0-based) with NaN, exercising the engine's per-slot
                  non-finite guard: the poisoned request must error and
                  free its slot while the other slots keep decoding.  The
                  *presence* of the injection is decided at decode TRACE
                  time (engine construction), so the production decode
                  program carries zero overhead; which slot is poisoned is
                  a dynamic input.  Env: PDTPU_FAULT_NAN_LOGITS="N".
  draft_diverge   the speculative-decoding verify program poisons the
                  DRAFT model's logits (negation: the draft proposes its
                  least-likely token) on every N-th speculative tick,
                  driving the accept rate toward zero.  Proves the
                  accept/reject path degrades gracefully to target-only
                  throughput: streams stay bit-identical (greedy) /
                  distribution-preserving (sampling) because rejected
                  proposals never commit — only tokens/sec drops.  The
                  *presence* of the injection is decided at verify TRACE
                  time (engine construction); whether the current tick
                  diverges is a dynamic input.
                  Env: PDTPU_FAULT_DRAFT_DIVERGE="N".
  kv_exhaust      the paged KV-cache block allocator pretends the pool
                  only holds N blocks (capacity capped live, host-side —
                  nothing is baked into any trace), forcing the
                  exhaustion paths on CPU without a big pool: admission
                  backpressure, mid-decode preemption of the newest
                  low-priority run, and the typed KVPoolExhaustedError
                  terminal state.  Arm/disarm takes effect on the next
                  allocator call.  Env: PDTPU_FAULT_KV_EXHAUST="N".
  prefix_evict    the serving prefix cache caps the number of RESIDENT
                  refcount-0 cached blocks at N (consulted live on every
                  release/insert, host-side only — nothing is baked into
                  any trace), forcing LRU eviction and copy-on-write
                  churn on CPU without filling a real pool.  N=0 means
                  nothing stays cached after its last reference drops —
                  every warm request becomes a cold one.
                  Env: PDTPU_FAULT_PREFIX_EVICT="N".
  slow_decode     the serving engine sleeps `ms` milliseconds on the host
                  before every `every_n`-th decode call (default every
                  call).  Purely host-side — the compiled decode program
                  is untouched and the injection is consulted live per
                  call, so it can be armed/disarmed on a running engine.
                  Makes overload, SLO-miss, and mid-decode-deadline paths
                  testable on CPU without a big model.
                  Env: PDTPU_FAULT_SLOW_DECODE="ms[:every_n]".
  replica_crash   the fleet replica with index `replica` dies abruptly at
                  its `tick`-th step (0-based) — the SIGKILL-equivalent
                  for in-process replicas: the step raises mid-loop, the
                  engine gets no chance to fail its runs, and the
                  ReplicaManager must fence the replica and fail over
                  every resident stream (resubmit or typed terminal;
                  never a hang).  Live-read per replica step, like
                  slow_decode.  Env: PDTPU_FAULT_REPLICA_CRASH=
                  "replica:tick".
  replica_slow    a fleet replica's step loop sleeps `ms` milliseconds on
                  the host before every `every_n`-th step — the brownout:
                  a browned-out replica serves, just far too slowly, and
                  the ReplicaManager's step-time health tracking must
                  fence it and migrate its residents to fast replicas.
                  The optional third field targets one replica index
                  (default: every replica).  Live-read per step, nothing
                  baked into any trace.  Env: PDTPU_FAULT_REPLICA_SLOW=
                  "ms[:every_n[:replica]]".
  net_delay       the fleet RPC's frame sender trickles every `every_n`-th
                  frame byte-chunk-by-byte with `ms` milliseconds between
                  chunks (default every frame) — the slowloris peer: a
                  frame that takes arbitrarily long to ASSEMBLE on the
                  receiving side while the socket stays healthy.  The
                  receiver's per-frame assembly deadline
                  (worker._FrameConn) must fence it with the typed
                  WireFormatError instead of holding the drive loop
                  hostage.  Consulted live per frame send, host-side
                  only.  Env: PDTPU_FAULT_NET_DELAY="ms[:every_n]".
  net_drop        the `n`-th RPC frame sent by this process (1-based,
                  counted across every connection) is cut MID-FRAME: half
                  its bytes go out, then the socket is hard-closed — a
                  connection reset in the middle of a length-prefixed
                  frame.  Fires once.  The receiver must fail typed
                  (WorkerDiedError on the closed peer / WireFormatError
                  on the torn frame), never decode garbage.  Env:
                  PDTPU_FAULT_NET_DROP="n".
  net_partition   a hard network partition against the replica with index
                  `replica`, lasting `secs` seconds from the first
                  consult after arming: every frame SENT to/from that
                  replica is silently blackholed and every receive sees
                  nothing, in BOTH directions, while both processes and
                  their sockets stay alive — the split-brain drill.  The
                  manager must fence on beat age and resubmit elsewhere;
                  the isolated worker must self-abort its residents after
                  the manager-silence timeout; a healed worker presenting
                  the stale epoch must be told to abort, never resume.
                  Arm it on BOTH sides (faults.enable locally + the
                  worker's `fault` RPC verb).  Env:
                  PDTPU_FAULT_NET_PARTITION="replica:secs".
  replica_wedge   the subprocess fleet worker with index `replica` blocks
                  INDEFINITELY inside its `tick`-th step (0-based) — a
                  hang, not a crash: the worker process stays alive, its
                  RPC socket stays connected, no exception ever raises.
                  The one failure mode PDTPU_FAULT_REPLICA_CRASH cannot
                  model, and exactly what the out-of-band heartbeat
                  exists to catch: the worker's heartbeat file goes
                  stale, the ReplicaManager fences the replica on
                  heartbeat AGE (no in-band call ever returns), SIGKILLs
                  the wedged process after the grace period, and the
                  supervisor restarts it under the backoff budget.
                  Consulted by the worker drive loop (serving/worker.py)
                  — in-process replicas share one driving thread, so
                  wedging one would wedge the fleet (the limitation that
                  motivates subprocess isolation).  Env:
                  PDTPU_FAULT_REPLICA_WEDGE="replica:tick".
  publish_corrupt the n-th weight artifact PUBLISHED by this process
                  (1-based, counted per process) is corrupted in place
                  POST-rename — truncated and bit-flipped AFTER the
                  atomic publish already made it visible, so the watch
                  signal fires on garbage bytes while the manifest
                  (written pre-rename) still names the good sha256.
                  The continuous-refresh pipeline must catch it at one
                  of its verify gates — the refresher's whole-file sha
                  check, the artifact channel's chunk verify, or the
                  post-flip canary — and keep serving the OLD weights;
                  corrupt weights must never reach a stream.  Consulted
                  by serving/refresh.py's WeightPublisher.  Env:
                  PDTPU_FAULT_PUBLISH_CORRUPT="n".
  adapter_corrupt the n-th LoRA adapter artifact READ by this process
                  (1-based, counted per process) is poisoned in memory
                  — a byte in the raw npz bytes is flipped AFTER the
                  file read but BEFORE any verification, so the loader
                  sees exactly what a torn ship / bad disk would hand
                  it.  The read path (lora.read_adapter) must reject
                  with a typed AdapterIntegrityError — never deliver
                  garbage factors to a slot — and the supervised caller
                  (worker load_adapter RPC, fleet.load_adapter)
                  re-ships/re-reads: the counter has advanced, so the
                  retry sees clean bytes.  Env:
                  PDTPU_FAULT_ADAPTER_CORRUPT="n".
  canary_diverge  while armed, the FleetRefresher's post-flip canary
                  gate reports a stream mismatch regardless of the real
                  comparison — the model-regressed-but-mechanically-
                  valid publish (bad training step, wrong checkpoint):
                  every byte verifies, yet the outputs changed.  The
                  refresher must roll the canary replica back to the
                  previous weights_sha, quarantine the publish, and
                  leave the whole fleet converged on the old weights.
                  Env: PDTPU_FAULT_CANARY_DIVERGE="1".

Deliberately import-light (no jax at module scope): DataLoader worker
processes and the bench orchestrator consult it before any backend exists.
"""
from __future__ import annotations

import os
import signal
import threading
from typing import Optional, Tuple

__all__ = ["enable", "disable", "reset", "get", "nan_grads_window",
           "poison_grads", "worker_crash_config", "maybe_crash_worker",
           "maybe_kill_mid_save", "backend_down", "nan_logits_request",
           "poison_logits", "slow_decode_config", "maybe_slow_decode",
           "draft_diverge_every", "poison_draft_logits", "kv_exhaust_cap",
           "prefix_evict_cap",
           "prefetch_stall_config", "maybe_stall_prefetch",
           "row_corrupt_fetch", "replica_crash_config",
           "replica_slow_config", "maybe_slow_replica",
           "replica_wedge_config", "maybe_wedge_replica",
           "net_delay_config", "net_drop_frame", "maybe_net_drop",
           "net_partition_config", "net_partition_active",
           "publish_corrupt_n", "maybe_corrupt_publish",
           "adapter_corrupt_n", "maybe_corrupt_adapter_read",
           "canary_diverge"]

_ENV = {
    "nan_grads": "PDTPU_FAULT_NAN_GRADS",
    "worker_crash": "PDTPU_FAULT_WORKER_CRASH",
    "kill_mid_save": "PDTPU_FAULT_KILL_MID_SAVE",
    "backend_down": "PDTPU_FAULT_BACKEND_DOWN",
    "nan_logits": "PDTPU_FAULT_NAN_LOGITS",
    "slow_decode": "PDTPU_FAULT_SLOW_DECODE",
    "draft_diverge": "PDTPU_FAULT_DRAFT_DIVERGE",
    "kv_exhaust": "PDTPU_FAULT_KV_EXHAUST",
    "prefix_evict": "PDTPU_FAULT_PREFIX_EVICT",
    "prefetch_stall": "PDTPU_FAULT_PREFETCH_STALL",
    "row_corrupt": "PDTPU_FAULT_ROW_CORRUPT",
    "replica_crash": "PDTPU_FAULT_REPLICA_CRASH",
    "replica_slow": "PDTPU_FAULT_REPLICA_SLOW",
    "replica_wedge": "PDTPU_FAULT_REPLICA_WEDGE",
    "net_delay": "PDTPU_FAULT_NET_DELAY",
    "net_drop": "PDTPU_FAULT_NET_DROP",
    "net_partition": "PDTPU_FAULT_NET_PARTITION",
    "publish_corrupt": "PDTPU_FAULT_PUBLISH_CORRUPT",
    "adapter_corrupt": "PDTPU_FAULT_ADAPTER_CORRUPT",
    "canary_diverge": "PDTPU_FAULT_CANARY_DIVERGE",
}

_lock = threading.Lock()
_registry = {}          # point -> raw config string (authoritative mirror)
_save_counter = {"n": 0}  # kill_mid_save is counted per process
_publish_counter = {"n": 0}  # publish_corrupt is counted per process
_adapter_counter = {"n": 0}  # adapter_corrupt is counted per process
_net_state = {"frames": 0, "drop_fired": False, "partitions": {}}


def enable(point: str, value="1"):
    """Arm a fault.  Mirrors into os.environ so worker subprocesses (fork /
    forkserver started after this call) and checkpoint subprocesses inherit
    it.  `value` is the point's config string (see module docstring)."""
    if point not in _ENV:
        raise ValueError(f"unknown fault point {point!r}; "
                         f"known: {sorted(_ENV)}")
    with _lock:
        _registry[point] = str(value)
        os.environ[_ENV[point]] = str(value)


def disable(point: str):
    with _lock:
        _registry.pop(point, None)
        os.environ.pop(_ENV[point], None)


def reset():
    """Disarm every fault (test teardown)."""
    for point in _ENV:
        disable(point)
    with _lock:
        _save_counter["n"] = 0
        _publish_counter["n"] = 0
        _adapter_counter["n"] = 0
        _net_state["frames"] = 0
        _net_state["drop_fired"] = False
        _net_state["partitions"] = {}


def get(point: str) -> Optional[str]:
    """Live config string for a point, or None.  Reads the registry first,
    then the env — so faults armed via the environment (subprocess tests)
    are seen without any enable() call in this process."""
    with _lock:
        v = _registry.get(point)
    if v is not None:
        return v
    return os.environ.get(_ENV[point])


# -- nan_grads ---------------------------------------------------------------

def nan_grads_window() -> Optional[Tuple[int, int]]:
    """[a, b) step window to poison, or None when disarmed.  Consulted at
    TRACE time by the compiled train steps: the window bounds are baked as
    constants, the comparison against step_no stays dynamic."""
    raw = get("nan_grads")
    if not raw:
        return None
    if ":" in raw:
        a, b = raw.split(":", 1)
        return int(a), int(b)
    k = int(raw)
    return k, k + 1


def poison_grads(grads, step_no):
    """Multiply every gradient leaf by NaN inside the poison window (traced;
    identity outside it).  RowSparseGrad leaves are poisoned through their
    .values so the sparse path is exercised too."""
    import jax.numpy as jnp
    from ..core.selected_rows import RowSparseGrad
    window = nan_grads_window()
    if window is None:
        return grads
    a, b = window
    bad = (step_no >= a) & (step_no < b)

    def leaf(g):
        if isinstance(g, RowSparseGrad):
            return RowSparseGrad(g.rows, leaf(g.values), g.dense_shape)
        factor = jnp.where(bad, jnp.asarray(float("nan"), g.dtype),
                           jnp.asarray(1.0, g.dtype))
        return g * factor
    return {k: leaf(g) for k, g in grads.items()}


# -- worker_crash ------------------------------------------------------------

def worker_crash_config() -> Optional[Tuple[str, int, Optional[str]]]:
    """(mode, seq, once_path) or None.  mode: "kill" | "exc"."""
    raw = get("worker_crash")
    if not raw:
        return None
    parts = raw.split(":", 2)
    if len(parts) == 1:  # bare seq -> kill
        return "kill", int(parts[0]), None
    mode = parts[0] if parts[0] in ("kill", "exc") else "kill"
    seq = int(parts[1] if parts[0] in ("kill", "exc") else parts[0])
    once = parts[2] if len(parts) == 3 else None
    return mode, seq, once


def maybe_crash_worker(seq: int):
    """Called by the DataLoader worker loop per task.  Fires at most once
    when a `once` sentinel path is configured (the sentinel is created
    BEFORE dying so the respawned worker survives the retried batch)."""
    cfg = worker_crash_config()
    if cfg is None:
        return
    mode, target, once = cfg
    if seq != target:
        return
    if once is not None:
        if os.path.exists(once):
            return
        open(once, "w").close()
    if mode == "exc":
        raise RuntimeError(f"injected worker exception at seq {seq}")
    os._exit(17)  # hard crash: no result, no cleanup — the real thing


# -- kill_mid_save -----------------------------------------------------------

def maybe_kill_mid_save():
    """Called by the checkpoint writer after the shard/manifest files are on
    disk but BEFORE the atomic rename publishes them.  SIGKILL — not
    sys.exit — so no finally/atexit softens the crash."""
    raw = get("kill_mid_save")
    if not raw:
        return
    with _lock:
        _save_counter["n"] += 1
        n = _save_counter["n"]
    if n >= int(raw):
        os.kill(os.getpid(), signal.SIGKILL)


# -- publish_corrupt ---------------------------------------------------------

def publish_corrupt_n() -> Optional[int]:
    """Which publish (1-based, per process) to corrupt, or None."""
    raw = get("publish_corrupt")
    if not raw:
        return None
    return int(raw)


def maybe_corrupt_publish(path: str) -> bool:
    """Called by the WeightPublisher AFTER the atomic rename made the
    weight artifact at `path` visible.  Counts publishes per process; on
    the n-th, the artifact is truncated and bit-flipped IN PLACE — the
    manifest written pre-rename still names the good sha256, so the
    corruption is exactly what a torn write / bad disk after the rename
    looks like to a watcher.  Returns True when it fired.  One of the
    refresh pipeline's verify gates (whole-file sha check, chunked ship
    verify, canary) must catch it; corrupt weights must never serve."""
    n = publish_corrupt_n()
    if n is None:
        return False
    with _lock:
        _publish_counter["n"] += 1
        cnt = _publish_counter["n"]
    if cnt != n:
        return False
    try:
        size = os.path.getsize(path)
        keep = max(1, int(size * 0.7))
        with open(path, "r+b") as f:
            f.truncate(keep)
            pos = max(0, keep // 2)
            f.seek(pos)
            b = f.read(1)
            f.seek(pos)
            f.write(bytes([b[0] ^ 0xFF]) if b else b"\xff")
    except OSError:
        pass  # a vanished file corrupts even harder
    return True


# -- adapter_corrupt ---------------------------------------------------------

def adapter_corrupt_n() -> Optional[int]:
    """Which adapter artifact read (1-based, per process) to poison, or
    None."""
    raw = get("adapter_corrupt")
    if not raw:
        return None
    return int(raw)


def maybe_corrupt_adapter_read(raw: bytes, path: str = "") -> bytes:
    """Called by `lora.read_adapter` on the raw artifact bytes BEFORE
    verification.  Counts reads per process; on the n-th, flips one byte
    in the middle of the buffer — the loader's integrity checks must
    turn this into a typed AdapterIntegrityError (garbage factors must
    never reach a device slot), and the supervised caller re-ships.  The
    file on disk is untouched, so the retry succeeds."""
    n = adapter_corrupt_n()
    if n is None:
        return raw
    with _lock:
        _adapter_counter["n"] += 1
        cnt = _adapter_counter["n"]
    if cnt != n:
        return raw
    if not raw:
        return b"\xff"
    buf = bytearray(raw)
    pos = len(buf) // 2
    buf[pos] ^= 0xFF
    return bytes(buf)


# -- canary_diverge ----------------------------------------------------------

def canary_diverge() -> bool:
    """True while armed: the post-flip canary gate must report a stream
    mismatch regardless of the real comparison, exercising auto-rollback
    end to end (serving/refresh.py)."""
    return bool(get("canary_diverge"))


# -- nan_logits --------------------------------------------------------------

def nan_logits_request() -> Optional[int]:
    """Submission sequence number (0-based) of the serving request whose
    decode logits get poisoned, or None when disarmed.  Consulted at decode
    TRACE time for presence (so the clean decode program has zero fault
    branches); the engine maps the sequence number to a per-slot poison
    mask passed as a dynamic input."""
    raw = get("nan_logits")
    if not raw:
        return None
    return int(raw)


def poison_logits(logits, poison_mask):
    """Multiply each poisoned row of (S, V) logits by NaN (traced; identity
    rows elsewhere).  Only ever traced into the decode program when
    nan_logits is armed at engine-construction time."""
    import jax.numpy as jnp
    factor = jnp.where(poison_mask, jnp.float32(float("nan")),
                       jnp.float32(1.0))
    return logits * factor[:, None]


# -- draft_diverge -----------------------------------------------------------

def draft_diverge_every() -> Optional[int]:
    """Tick stride N (poison the draft every N-th speculative tick,
    0-based: ticks 0, N, 2N, ...), or None when disarmed.  Consulted at
    verify TRACE time for presence (the clean verify program carries zero
    fault branches); which tick diverges is a dynamic input the engine
    computes host-side per call."""
    raw = get("draft_diverge")
    if not raw:
        return None
    return max(1, int(raw))


def poison_draft_logits(logits, diverge):
    """Negate the draft logits when `diverge` (traced bool) is set: the
    draft proposes its LEAST-likely token, which the target all but
    certainly rejects — a finite corruption (never NaN) so the engine's
    non-finite guard stays out of the picture and the degradation under
    test is purely accept-rate -> throughput.  Only ever traced into the
    verify program when draft_diverge is armed at engine construction."""
    import jax.numpy as jnp
    return jnp.where(diverge, -logits, logits)


# -- slow_decode -------------------------------------------------------------

def slow_decode_config() -> Optional[Tuple[float, int]]:
    """(sleep_ms, every_n) or None when disarmed.  Consulted live per
    decode call (host-side only — nothing is baked into any trace), so a
    running engine reacts to arm/disarm immediately."""
    raw = get("slow_decode")
    if not raw:
        return None
    parts = raw.split(":", 1)
    ms = float(parts[0])
    every = int(parts[1]) if len(parts) == 2 else 1
    return ms, max(1, every)


def maybe_slow_decode(call_no: int) -> float:
    """Host-side sleep before decode call number `call_no` (0-based) when
    slow_decode is armed and call_no hits the every_n stride.  Returns the
    seconds slept (0.0 when disarmed / off-stride)."""
    cfg = slow_decode_config()
    if cfg is None:
        return 0.0
    ms, every = cfg
    if call_no % every:
        return 0.0
    import time
    secs = ms / 1000.0
    time.sleep(secs)
    return secs


# -- kv_exhaust --------------------------------------------------------------

def kv_exhaust_cap() -> Optional[int]:
    """Forced block-pool capacity (the allocator pretends only N blocks
    exist), or None when disarmed.  Consulted LIVE on every allocator
    call — pure host bookkeeping, no trace ever sees it — so a running
    engine reacts to arm/disarm immediately."""
    raw = get("kv_exhaust")
    if not raw:
        return None
    return max(0, int(raw))


# -- prefix_evict ------------------------------------------------------------

def prefix_evict_cap() -> Optional[int]:
    """Forced cap on RESIDENT refcount-0 prefix-cache blocks, or None
    when disarmed.  Consulted LIVE on every cache release/insert — pure
    host bookkeeping, no trace ever sees it — so a running engine reacts
    to arm/disarm immediately.  N=0 disables retention entirely."""
    raw = get("prefix_evict")
    if not raw:
        return None
    return max(0, int(raw))


# -- prefetch_stall ----------------------------------------------------------

def prefetch_stall_config() -> Optional[Tuple[float, int]]:
    """(sleep_ms, every_n) or None when disarmed.  Consulted live per host
    row fetch (host-side only; nothing baked into any trace), so a running
    prefetch pipeline reacts to arm/disarm immediately."""
    raw = get("prefetch_stall")
    if not raw:
        return None
    parts = raw.split(":", 1)
    ms = float(parts[0])
    every = int(parts[1]) if len(parts) == 2 else 1
    return ms, max(1, every)


def maybe_stall_prefetch(fetch_no: int) -> float:
    """Host-side sleep before fetch number `fetch_no` (0-based) when
    prefetch_stall is armed and the stride hits.  Returns seconds slept."""
    cfg = prefetch_stall_config()
    if cfg is None:
        return 0.0
    ms, every = cfg
    if fetch_no % every:
        return 0.0
    import time
    secs = ms / 1000.0
    time.sleep(secs)
    return secs


# -- row_corrupt -------------------------------------------------------------

def row_corrupt_fetch() -> Optional[int]:
    """1-based fetch number whose prefetched row slab gets one row
    poisoned with NaN (the fetched COPY, never the host table), or None
    when disarmed.  The pipeline's consume-side verify must detect the
    poison and refetch."""
    raw = get("row_corrupt")
    if not raw:
        return None
    return int(raw)


# -- replica_crash / replica_slow --------------------------------------------

def replica_crash_config() -> Optional[Tuple[int, int]]:
    """(replica_index, tick) at which the targeted fleet replica dies
    abruptly, or None when disarmed.  Consulted live per replica step by
    the ReplicaManager (host-side only), so it can be armed on a running
    fleet."""
    raw = get("replica_crash")
    if not raw:
        return None
    replica, tick = raw.split(":", 1)
    return int(replica), int(tick)


def replica_slow_config() -> Optional[Tuple[float, int, Optional[int]]]:
    """(sleep_ms, every_n, replica_or_None) — the brownout knob, or None
    when disarmed.  A None replica field slows EVERY replica; an index
    slows only that one (the probe's targeted brownout).  Consulted live
    per replica step, nothing baked into any trace."""
    raw = get("replica_slow")
    if not raw:
        return None
    parts = raw.split(":", 2)
    ms = float(parts[0])
    every = int(parts[1]) if len(parts) >= 2 else 1
    replica = int(parts[2]) if len(parts) == 3 else None
    return ms, max(1, every), replica


def maybe_slow_replica(replica_idx: int, step_no: int) -> float:
    """Host-side sleep before step `step_no` (0-based) of replica
    `replica_idx` when replica_slow is armed, the stride hits, and the
    replica matches (or no replica is targeted).  Returns seconds
    slept."""
    cfg = replica_slow_config()
    if cfg is None:
        return 0.0
    ms, every, target = cfg
    if target is not None and target != replica_idx:
        return 0.0
    if step_no % every:
        return 0.0
    import time
    secs = ms / 1000.0
    time.sleep(secs)
    return secs


def replica_wedge_config() -> Optional[Tuple[int, int]]:
    """(replica_index, tick) at which the targeted subprocess worker's
    step BLOCKS forever (hang, not crash), or None when disarmed.
    Consulted live per worker step by the worker drive loop — the
    injection that proves OUT-OF-BAND heartbeat detection: the process
    stays alive and connected, so only heartbeat age can see it."""
    raw = get("replica_wedge")
    if not raw:
        return None
    replica, tick = raw.split(":", 1)
    return int(replica), int(tick)


def maybe_wedge_replica(replica_idx: int, step_no: int):
    """Block FOREVER when replica_wedge is armed for (replica_idx,
    step_no) — the wedged-worker hang.  Never returns once it fires;
    the manager's SIGKILL is the only way out (which is the point)."""
    cfg = replica_wedge_config()
    if cfg is None or cfg[0] != replica_idx or step_no < cfg[1]:
        # >= not ==: the knob is usually armed over RPC against a live,
        # fast-stepping worker — an exact-tick match could slip past
        # between the arm and the next step, and a wedge that never
        # fires is a vacuous chaos test
        return
    import time
    while True:  # pragma: no cover — exits only via SIGKILL
        time.sleep(3600)


# -- net_delay / net_drop / net_partition ------------------------------------

def net_delay_config() -> Optional[Tuple[float, int]]:
    """(chunk_sleep_ms, every_n) or None when disarmed — the slowloris
    knob.  Consulted live per frame SEND by the fleet RPC
    (worker._FrameConn): a matched frame is dribbled out in small byte
    chunks with `ms` sleeps between them, so its assembly on the peer
    takes arbitrarily long while the socket stays healthy."""
    raw = get("net_delay")
    if not raw:
        return None
    parts = raw.split(":", 1)
    ms = float(parts[0])
    every = int(parts[1]) if len(parts) == 2 else 1
    return ms, max(1, every)


def net_drop_frame() -> Optional[int]:
    """1-based frame number (counted across every connection in this
    process) to cut mid-frame, or None when disarmed."""
    raw = get("net_drop")
    if not raw:
        return None
    return int(raw)


def maybe_net_drop() -> bool:
    """Count one frame send; True exactly once, on the armed frame
    number — the caller sends HALF the frame and hard-closes the socket
    (a mid-frame connection cut).  Single-shot per process until
    reset()."""
    target = net_drop_frame()
    if target is None:
        return False
    with _lock:
        _net_state["frames"] += 1
        if _net_state["drop_fired"] or _net_state["frames"] != target:
            return False
        _net_state["drop_fired"] = True
    return True


def net_partition_config() -> Optional[Tuple[int, float]]:
    """(replica_index, seconds) or None when disarmed."""
    raw = get("net_partition")
    if not raw:
        return None
    replica, secs = raw.split(":", 1)
    return int(replica), float(secs)


def net_partition_active(replica_idx: Optional[int]) -> bool:
    """True while the partition window against `replica_idx` is open.
    The window starts at the FIRST consult after arming (each process
    starts its own clock — arm both sides near-simultaneously: the
    manager via enable(), the worker via its `fault` RPC verb) and
    closes `secs` later: the partition HEALS, with both processes still
    alive — the split-brain reconciliation this knob exists to force."""
    cfg = net_partition_config()
    if cfg is None or replica_idx is None or cfg[0] != int(replica_idx):
        return False
    raw = get("net_partition")
    import time
    now = time.monotonic()
    with _lock:
        start = _net_state["partitions"].get(raw)
        if start is None:
            start = now
            _net_state["partitions"][raw] = start
    return (now - start) < cfg[1]


# -- backend_down ------------------------------------------------------------

def backend_down() -> bool:
    return bool(get("backend_down"))
