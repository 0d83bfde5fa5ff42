"""paddle_tpu.serving — continuous-batching inference engine.

The ROADMAP's "serves heavy traffic from millions of users" surface: where
`inference.Predictor` runs one whole-batch program per call and
`generation.generate` owns a compiled `(batch, prompt_len, max_new)` loop
per shape, the ServingEngine keeps ONE resident slot-based KV-cache pool
and exactly two compiled program families — bucketed prefill and a single
all-slots decode step — that requests join and leave between iterations.
This is the TPU-native equivalent of the reference's AnalysisPredictor +
dynamic_decode deployment path, redesigned for continuous batching.

Model protocol contract
-----------------------
Any model can be served if it implements the fixed-cache decode protocol
(`models/gpt.py:190,201` is the reference implementation):

- ``gen_fixed_cache(batch_size, max_length, dtype=None)`` returns the
  per-layer KV buffers as a list of ``(k, v)`` RAW jax arrays, each of
  shape ``(batch_size, max_length, heads, head_dim)`` (any per-layer pytree
  with a leading batch axis on every leaf works — the engine only ever
  slices/maps axis 0 and axis 1 of each leaf).
- ``forward_fixed(input_ids, caches, pos)`` runs the model over
  ``input_ids`` (B, S) with the chunk's KV written into the fixed buffers
  at ``[pos, pos + S)`` (``pos`` may be a traced scalar), attention masked
  causally so query ``i`` sees buffer slots ``<= pos + i``, and returns
  ``(logits, new_caches)``.  Content of the buffers at positions
  ``> pos + S`` must never influence the output (the engine relies on this
  to reuse slots without scrubbing, and ADDITIONALLY overwrites the full
  slot range at prefill).

Engine lifecycle
----------------
::

    engine = ServingEngine(model, max_slots=8, max_len=256,
                           prefill_buckets=(16, 32, 64), max_queue_depth=64)
    engine.warmup()          # compile len(buckets) + 1 programs, the total
    engine.start()           # background loop (or drive step() yourself)
    resp = engine.submit(prompt_ids, max_new_tokens=64,
                         eos_token_id=eos, deadline=30.0)
    for tok in resp:         # streams as decoded; TTFT at first yield
        ...
    engine.close()

Guarantees: compilation count ≤ len(prefill_buckets) + 1 programs per
engine regardless of traffic mix (`compile_counts()` asserts it); greedy
requests are bit-identical to a solo `generation.generate` of the same
prompt; one poisoned/expired/cancelled request only ever costs its own
slot.

Speculative decoding
--------------------
``ServingEngine(model, draft_model=small_model, spec_tokens=K)`` swaps
the decode program for ONE verify program: the draft proposes K tokens
per tick (its own slot pool, same protocol), the target scores all K+1
positions in one batched forward, and the longest accepted prefix plus a
corrected token commits in-program (`generation.speculative` — greedy
argmax-equality accept, distribution-preserving rejection sampling for
sampling slots).  The program bound is unchanged; per-request
``submit(..., spec=False)`` opts out inside the same trace; greedy
streams stay bit-identical to solo generate at ANY draft quality.
Quantize the served weights with
``quantization.quantize_for_serving(model)`` (int8 weight-only,
dequant-at-use) — composable with speculation and with the gateway.  See
the README "Speculative + quantized decoding" section.

Distributed serving
-------------------
``ServingEngine(kv="paged", block_size=B, num_blocks=N)`` swaps the
slot-row pool for ONE block pool per layer (`PagedKVPool`,
serving/kv_pool.py): block-granular KV allocation with per-slot block
tables, so long and short requests share HBM instead of every slot
paying ``max_len`` — ≥2x resident slots in the same KV byte budget on
mixed traffic (probes/paged_serving_probe.py).  Recycled blocks are
scrubbed in-program at re-serve, exhaustion is backpressure (admission
waits, mid-decode shortfall preempts the newest low-priority run and
resumes it later; `KVPoolExhaustedError` is the typed terminal state;
``PDTPU_FAULT_KV_EXHAUST=N`` forces it all).  ``mesh=`` runs the whole
engine tensor-parallel over a `jax.sharding.Mesh` — Megatron param
layout, heads-sharded KV pool, same program count, streams bit-identical
to the single-device engine.  Both compose with the gateway,
speculation, and quantization.  See the README "Distributed serving"
section.

Gateway
-------
`ServingGateway` (gateway.py + slo.py) is the multi-tenant front door
over the engine: per-tenant token-bucket rate limits with stride-fair
weighted admission, priority lanes whose high-priority arrivals preempt
resumable low-priority decodes (slot KV rows + sampling state snapshotted
to host via `engine.preempt_slot`, restored bit-identical via
`engine.restore_run` — zero extra compiled programs), SLO-driven load
shedding (`ShedPolicy` over live lane depth / occupancy / TTFT-p99
signals), and an OpenAI-shaped streaming HTTP endpoint
(`GatewayServer`, port-free `gateway.handle()` for tests).  Every
admission outcome — shed, rate-limited, expired, preempted-then-cancelled
— is a terminal Response: no consumer ever hangs.  See the README
"Gateway" section.

Fleet
-----
`FleetRouter` + `ReplicaManager` (fleet.py) front N engine replicas:
least-loaded routing with session affinity, health from
warmup/step-time/heartbeat evidence, crash/brownout fencing with
failover — in-flight runs migrate between replicas bit-identical
through the run-transfer codec (transfer.py, the PR-6 preempt/restore
snapshot made replica-portable), runs whose snapshot died with a
crashed replica are re-prefilled from the prompt (``resubmit=True``,
greedy-only) or fail with the typed `ReplicaLostError` — and
`drain()`/`rollout()` give zero-downtime weight/program rollouts.
``ServingGateway(fleet, ...)`` turns the multi-tenant front door into a
cluster front door.  ``fleet.add_worker(spec)`` makes a replica its own
OS process (serving/worker.py): a subprocess engine worker booted from
a model-factory spec + AOT program set, spoken to over a
length-prefixed npz RPC, with OUT-OF-BAND heartbeat liveness (a wedged
step — the hang an in-process fleet cannot survive — fences on
heartbeat age, is SIGKILLed after a grace period, and is restarted by
the supervisor with backoff under a budget).  See the README "Fleet
serving" section.

Program lifecycle
-----------------
`engine.warmup()` precompiles the whole program family before traffic
(returns a compile report; `post_warmup_compiles()` asserts ZERO compiles
under any later traffic mix), `engine.save_program_set(path)` serializes
the family as one AOT artifact, and ``ServingEngine(program_set=path)`` /
``enable_serving(program_set=path)`` boots from it without retracing —
see `paddle_tpu.programs` and the README "Program lifecycle" section.

Metrics (all live under `metrics()`, the STAT_serving_* monitor counters,
and the predictor's profile report): ttft_p50_ms,
inter_token_ms, tokens_per_sec, queue_depth, slot_occupancy,
requests_completed/errored, STAT_serving_{requests,rejects,tokens,
prefills,decode_steps,compiles,queue_depth,slots_active,cancelled,
deadline_expired,nonfinite}.
"""
from __future__ import annotations

from .engine import ServingEngine, NonFiniteLogitsError, PreemptedRun
from .kv_pool import PagedKVPool, KVPoolExhaustedError
from .prefix_cache import PrefixCache
from .request import Request, Response, RequestCancelled
from .scheduler import (RequestScheduler, QueueFullError,
                        DeadlineExceededError)
from .slo import ShedPolicy, Signals, SLOTracker, TenantConfig, TokenBucket
from .gateway import (ServingGateway, GatewayServer, RateLimitedError,
                      SheddedError, serve_gateway, PRIORITY_HIGH,
                      PRIORITY_LOW)
from .fleet import (FleetRouter, ReplicaManager, Replica,
                    SubprocessReplica, RestartBackoff, ReplicaLostError)
from .transfer import (RunTransferError, encode_run, decode_run,
                       run_to_bytes, run_from_bytes, engine_config_hash)
from .worker import WorkerClient, WorkerDiedError, WireFormatError
from .refresh import WeightPublisher, FleetRefresher, latest_publish
from .autoscaler import Autoscaler

__all__ = [
    "ServingEngine", "Request", "Response", "RequestScheduler",
    "QueueFullError", "DeadlineExceededError", "RequestCancelled",
    "NonFiniteLogitsError", "PreemptedRun",
    # distributed serving (paged KV pool + tensor-parallel engine)
    "PagedKVPool", "KVPoolExhaustedError", "PrefixCache",
    # gateway (multi-tenant SLO-aware admission over the engine)
    "ServingGateway", "GatewayServer", "serve_gateway", "TenantConfig",
    "TokenBucket", "ShedPolicy", "Signals", "SLOTracker",
    "RateLimitedError", "SheddedError", "PRIORITY_HIGH", "PRIORITY_LOW",
    # fleet (multi-replica router: health-driven failover, run
    # migration, zero-downtime rollout, supervised subprocess workers)
    "FleetRouter", "ReplicaManager", "Replica", "SubprocessReplica",
    "RestartBackoff", "ReplicaLostError",
    "RunTransferError", "encode_run", "decode_run", "run_to_bytes",
    "run_from_bytes", "engine_config_hash",
    # subprocess worker replicas (process isolation + heartbeat)
    "WorkerClient", "WorkerDiedError", "WireFormatError",
    # train->serve loop (continuous weight refresh + elastic capacity)
    "WeightPublisher", "FleetRefresher", "latest_publish", "Autoscaler",
]
