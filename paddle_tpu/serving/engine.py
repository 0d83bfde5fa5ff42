"""ServingEngine: continuous batching over a slot-based KV-cache pool.

Three program bodies serve every engine configuration and request mix:

- **prefill** (one trace per prompt-length bucket): the prompt,
  right-padded to the bucket, runs through `model.forward_fixed` against
  a bucket-sized scratch cache; the resulting KV is written into the
  assigned slot of the engine-lifetime pool, overwriting the slot's FULL
  [0, max_len) range (stale KV from the slot's previous occupant can
  never leak).  The first generated token is sampled inside the same
  program from the prompt's last-position logits.
- **decode** (a single trace, ever): `model.forward_fixed` is vmapped
  over the slot axis so every slot advances one token per step with its
  OWN write position, `decode_chunk` steps a call, and every sampling
  knob — temperature, top-k, top-p, greedy flag, RNG key — is a per-slot
  dynamic input (`generation.process_logits_dynamic`), so heterogeneous
  requests share the trace.  Requests join and leave the resident batch
  between calls; nobody owns a compilation.
- **verify** (``draft_model=``, in decode's place): the speculative tick,
  below.

Compilation count is therefore bounded by len(prefill_buckets) + 1 per
engine, regardless of how many (prompt_len, max_new, sampling-param)
combinations the traffic mixes — asserted by `compile_counts()`.

What an engine is configured with never copies a body.  Each decision is
taken once, at trace time, by a piece the bodies share:

- **the cache view** (`self._cache`; `kv_pool.FixedKVView` /
  `PagedKVView`) decides the KV layout.  `open` turns the pools and the
  call's tables into the contiguous view the model runs against,
  `publish` writes the rows a call produced back, `prompt_cache` /
  `write_prompt` say what a prompt runs against and where its rows go,
  and `prompt_inputs` / `batch_inputs` name the host-side inputs that go
  with them.  For the fixed pool `open` and `publish` are the identity.
- **the row forward** (`_row_forward`; for a prompt `_prompt_forward`)
  decides what runs against the view: `forward_fixed` on one slot's
  cache leaves at its own position, under `jax.vmap`, for the model or a
  draft, inside the adapter context when the engine has adapters (the
  adapter id is then one more vmapped operand); a model that decodes the
  whole batch gets its `forward_decode` / `forward_prefill` in its
  place.  The decode chunk, the verify tick's draft scan and its target
  forward are all this one function.
- **one signature**: every program is ``fn(weights, pools, inputs) ->
  dict``.  `weights` holds the trees the engine has (``model``, and
  ``draft`` / ``lora`` only when configured), `pools` the DONATED KV
  pools (``model``, ``draft``), `inputs` the call's arrays by name
  (`_prefill_inputs`, `_decode_inputs`: the live call, `warmup()` and the
  program-set exporter all assemble them there), and the result names
  what it returns (``out["pools"]``, ``out["toks"]``, ...).

**Speculative decoding** (``draft_model=``): the decode program is
replaced by ONE verify program per engine that (a) runs ``spec_tokens``
sequential draft-model steps proposing K tokens per slot (the draft owns
its own slot pool, written with the same protocol), (b) scores
``[last_committed, d_1..d_K]`` — K+1 positions — in ONE batched target
forward, and (c) commits the longest accepted prefix plus one corrected
token entirely in-program (`generation.speculative`: greedy equality
accept, or distribution-preserving rejection sampling for sampling
slots), so a tick advances 1..K+1 tokens per slot with a single target
dispatch.  Per-bucket prefill additionally prefills the draft pool inside
the same program.  The program bound is UNCHANGED: len(prefill_buckets)
prefill programs (each covering target + draft) + 1 verify program —
spec on/off per request, greedy/sampling, and every sampling-param combo
share the single verify trace via dynamic per-slot inputs.  Greedy
speculative streams stay bit-identical to solo `generate` (acceptance is
argmax equality against the same logits rows the solo loop argmaxes);
spec-off slots inside a speculative engine reproduce the plain decode
step token-for-token (same key folds, same distributions).

**Paged KV pool** (``kv="paged"``): the slot-row pool is replaced by ONE
block pool per layer (``[num_blocks, block_size, heads, head_dim]`` —
serving/kv_pool.py) with a host-side allocator and per-slot block-table
indirection, and the engine holds the paged cache view.  The compiled
programs change shape but not count or semantics: prefill writes the
prompt's blocks through the slot's table (full-block overwrite — no
stale KV survives re-serving), the decode/verify step gathers each
slot's table into the contiguous view ONCE per call (the batched form of
`ops.paged_attention.gather_block_rows` — on CPU this reconstruction
keeps every float op identical to the fixed engine, so streams stay
bit-identical to solo generate) and scatters the tick's freshly written
rows back in one pass, zeroing any block it enters (scrub-on-recycle).
With ``prefix_cache=True`` a prompt runs at `cached_len` against its
slot's own gathered view and only the uncached suffix is computed and
written (`cached_len` is a dynamic input of the same per-bucket prefill;
0 IS the cold path).
Honest cost note: the gathered view is a TRANSIENT per-call working set
of up to fixed-pool size, so on an accelerator the density win is in
the PERSISTENT pool only until the pallas block-table kernel
(`ops.paged_attention.paged_attention`, which reads O(live blocks) and
never materializes the view) replaces the gather inside the decode
program — the ROADMAP's named next step on a live slot.  Block exhaustion
is backpressure: admission waits for free blocks, mid-decode shortfall
preempts the newest lowest-priority run into a host snapshot (the PR-6
preempt machinery) and resumes it when the pool drains, and a run that
can no longer fit at all fails with the typed `KVPoolExhaustedError`.
``PDTPU_FAULT_KV_EXHAUST=N`` caps the live pool to force every path.

**Tensor parallelism** (``mesh=``): the whole engine runs SPMD over a
`jax.sharding.Mesh` — params laid out by `parallel.sharding.param_specs`
(column-parallel qkv/ffn_in, row-parallel proj/ffn_out, vocab-sharded
embeddings), the KV pool sharded over heads on the ``tp`` axis, and the
same prefill/decode/verify programs compiled ONCE under the mesh (XLA
GSPMD inserts the collectives).  The 8-virtual-device CPU mesh makes the
whole thing tier-1 testable: streams match the single-device engine
token-for-token for the same seeds.

**Models that decode the whole batch** (``model.serving_batch_decode``,
e.g. `models.CohereMoEForCausalLM`): the same constructor and the same
bodies, but the prompt's forward is `model.forward_prefill(ids,
prompt_len)` (the last position's logits alone) and the decode step
hands `model.forward_decode(tokens, pools, pos, active)` ALL slots at
once instead of vmapping a batch of one — a routed layer routes once a
step and its experts see every slot's token in one grouped product;
rows of empty slots are routed nowhere.  **The cache protocol is a tuple
of leaves a layer:** `gen_fixed_cache(B, rows)` returns, a layer, a tuple
of arrays ``(B, rows', *rest)``, each with its OWN ``rows'``, `rest` and
dtype.  `(k, v)` of ``(…, heads, head_dim)`` is one case (GPT-2, Cohere); a
latent-attention layer holds two leaves that are no pair, ``(…, 512)`` the
normalised latent and ``(…, 64)`` the rotated key numbers of a row
(`models.DeepseekV3ForCausalLM`; one leaf of 576 ran slower on the chip); a
layer of one leaf or of three serves alike.  The fixed view, `build_pools`,
`pool_bytes`, `_leaf_rows` and `_gauge_kv_rows` go over a layer's leaves
without knowing their number or rank.  **Leaves may differ in length and
in clock, within a layer too.**  A leaf as long as the pool holds a row a
token at ``pos``.  A shorter one is its model's: a window layer's RING,
written at ``pos % rows`` (Cohere's window layers), or a SUMMARY LEAF, one
pooled row a chunk of tokens written at ``pos // chunk`` beside a ring of
exact rows (`models.EvaByteForCausalLM`: four leaves a layer, ring keys and
values of 2048 rows and summary keys and values of ``max_len / 16``).  Who
writes where in a decode step is the model's `forward_decode`; the fixed
view's `write_prompt` overwrites each leaf over ITS OWN length with what
the prompt's forward hands back for it: a leaf no longer than the pool's is
taken as it lies (the model has laid a ring out as a ring, by `prompt_len`),
a token-indexed leaf longer than the pool's is a bucket longer than a ring
and leaves the prompt's last ``rows`` positions in it.  Both programs
return the model's int32 counts with the tokens (``out["counts"]``), summed
over layers (and a decode call's steps) on the device.  **A model names
them** (`serving_count_names`, e.g. ``("kv_rows_live", "kv_rows_pool",
"kv_rows_summary")``) and the engine carries each into the `serving_admit`
/ `serving_decode` span's args under its name, unread.  A model that names
nothing is of the routed family and its counts are, by position, ``[picks
on held experts, picks in all, held experts hit, grouped products made,
rows those products went over]`` (a prompt's routed layer walks the picks
held here in chunks, so how many products it made is known only on the
device), which a model may follow with two of its cache: ``[rows the
call's requests hold, rows its attention went over]``.  Those ride in the
spans' args as `routed_here`, `routed_all`, `experts_hit`;
`expert_products` and `expert_rows` where the call made a grouped product,
absent where its routed layers took the batched form; `kv_rows_live`,
`kv_rows_pool`; and feed the counters `moe_routed_picks_total{where}`,
`moe_experts_hit_total` and `moe_expert_rows_total` (rows through the
grouped products, to set against the picks held here and in all).
`serving_kv_rows{kind}` gauges the rows held: what a slot holds at a
position is the model's to say (`serving_rows_held(pos)`: `window`, the
ring's live rows, and `summary`), else a row a token a layer, a ring at
most its length (`window`, `full`, or the model's own word,
`serving_cache_kind`: `latent`).  ``kv="paged"``,
``prefix_cache``, ``draft_model``, ``mesh``, ``lora``, `preempt_slot`
and `restore_run` raise for such a model, naming the piece that is
missing (the paged view, snapshots, transfer and the prefix cache still
take equal `(k, v)` pairs: no ring, no latent leaf, no summary leaf).

Greedy requests are bit-identical to a solo
`generation.generate(decode_strategy='greedy_search')` run of the same
prompt: prefill logits at the prompt's last position are unaffected by
right-padding (causal mask), and decode attends exactly the
[0, pos] prefix of the slot, the same masked-buffer attention the solo
loop runs.
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.errors import FatalError, InvalidArgumentError, UnavailableError
from ..generation import process_logits_dynamic
from ..observability import metrics as _obs_m, track
from ..observability.tracer import span as _span
from ..utils import faults
from ..utils.monitor import stat_add
from .kv_pool import (FixedKVView, KVPoolExhaustedError, PagedKVPool,
                      PagedKVView)
from .request import Request, Response, RequestCancelled
from .scheduler import RequestScheduler, DeadlineExceededError

__all__ = ["ServingEngine", "NonFiniteLogitsError", "PreemptedRun",
           "KVPoolExhaustedError"]


class NonFiniteLogitsError(FatalError):
    """Decode produced NaN/Inf logits for this request's slot; the request
    is errored individually and its slot recycled."""
    code = "Fatal"


# a decode call's per-slot sampling inputs, in `_sample_step`'s order (a
# prefill's are one request's: `key`, then the same names)
_SAMPLING = ("keys", "temp", "top_k", "top_p", "greedy")
# what a decode call returns that stays on the device: the pools and the
# next call's carry
_RESIDENT = ("pools", "tokens", "pos")


def _first_token_at(logits, idx, fold_pos, key, temp, top_k, top_p,
                    greedy):
    """Sample the first generated token from the logits row at `idx` —
    the prompt's last position, which right padding never touches (causal
    mask), so this matches the solo generate prefill — folding the key at
    the ABSOLUTE position `fold_pos` = prompt_len - 1 (decode step j
    folds at prompt_len + j — counters never collide).  The cached-prefix
    prefill computes only the prompt's uncached suffix, so its
    last-position logits sit at the RELATIVE index (prompt_len - 1 -
    cached_len) while the key must still fold at the absolute position
    for stream parity with the cold path."""
    last = jax.lax.dynamic_index_in_dim(
        logits[0].astype(jnp.float32), idx, axis=0, keepdims=False)
    finite = jnp.isfinite(last).all()
    proc = process_logits_dynamic(
        last[None], temp[None], top_k[None], top_p[None], greedy[None])[0]
    sampled = jax.random.categorical(
        jax.random.fold_in(key, fold_pos), proc)
    tok = jnp.where(greedy, jnp.argmax(proc, axis=-1),
                    sampled).astype(jnp.int32)
    logp = jax.nn.log_softmax(proc)[tok]
    return tok, logp, finite


def _sample_step(last, pos, keys, temp, top_k, top_p, greedy):
    """One per-slot sampling decision over (S, V) logits — the single
    implementation site of the bit-identical-stream contract.  All-greedy fast path: the full dynamic
    sampling pipeline (two (S, V) sorts + threefry draw) costs real time
    per iteration; a pure-greedy batch — the common serving mix — skips
    it at runtime via lax.cond, INSIDE the single decode trace (no extra
    program, identical tokens: with greedy all-True
    process_logits_dynamic returns the raw logits, so both branches
    argmax the same array)."""
    def mixed(last):
        proc = process_logits_dynamic(last, temp, top_k, top_p, greedy)
        folded = jax.vmap(jax.random.fold_in)(keys, pos)
        sampled = jax.vmap(jax.random.categorical)(folded, proc)
        tok = jnp.where(greedy, jnp.argmax(proc, axis=-1),
                        sampled).astype(jnp.int32)
        logp = jnp.take_along_axis(
            jax.nn.log_softmax(proc, axis=-1), tok[:, None],
            axis=-1)[:, 0]
        return tok, logp

    def all_greedy(last):
        tok = jnp.argmax(last, axis=-1).astype(jnp.int32)
        logp = jnp.take_along_axis(
            jax.nn.log_softmax(last, axis=-1), tok[:, None],
            axis=-1)[:, 0]
        return tok, logp

    return jax.lax.cond(jnp.all(greedy), all_greedy, mixed, last)


def _draft_propose(dlast, pos, i, keys, temp, top_k, top_p, greedy):
    """Draft step `i`'s per-slot proposal from (S, V) draft logits (same
    single-site rationale and all-greedy fast path as _sample_step).
    Returns (proposal, q)."""
    from ..generation.speculative import draft_proposal_key

    def mixed(dlast):
        proc = process_logits_dynamic(dlast, temp, top_k, top_p, greedy)
        kd = jax.vmap(lambda kk, pp: draft_proposal_key(kk, pp, i))(
            keys, pos)
        sampled = jax.vmap(jax.random.categorical)(kd, proc)
        prop = jnp.where(greedy, jnp.argmax(proc, axis=-1),
                         sampled).astype(jnp.int32)
        return prop, jax.nn.softmax(proc, axis=-1)

    def all_greedy(dlast):
        return (jnp.argmax(dlast, axis=-1).astype(jnp.int32),
                jax.nn.softmax(dlast, axis=-1))

    return jax.lax.cond(jnp.all(greedy), all_greedy, mixed, dlast)


class _CachedPlan:
    """Host-side warm-admission plan (see `_cached_plan`)."""

    __slots__ = ("chain", "matched", "cow", "cached_len", "bucket",
                 "new_live")

    def __init__(self, chain, matched, cow, cached_len, bucket, new_live):
        self.chain = chain            # cached block ids to adopt
        self.matched = matched        # rows covered by the chain
        self.cow = cow                # last chain block needs a COW copy
        self.cached_len = cached_len  # dynamic prefill input
        self.bucket = bucket          # SUFFIX bucket (plen - cached_len)
        self.new_live = new_live      # fresh live blocks this admit costs


def _default_buckets(max_len: int):
    """Powers of two from 16 up to max_len (prompt lengths round up)."""
    buckets, b = [], 16
    while b < max_len:
        buckets.append(b)
        b *= 2
    buckets.append(max_len)
    return tuple(buckets)


class _SlotRun:
    """Host-side per-slot decode state."""
    __slots__ = ("req", "resp", "pos", "produced", "last_token",
                 "last_token_at", "key", "aid")

    def __init__(self, req: Request, resp: Response, pos: int,
                 first_token: int, key: np.ndarray, aid: int = 0):
        self.req = req
        self.resp = resp
        self.pos = pos              # kv length so far (write offset)
        self.produced = 1           # first token came from prefill
        self.last_token = first_token
        self.last_token_at = time.perf_counter()
        self.key = key
        self.aid = aid              # pinned adapter slot id (0 = base)


class PreemptedRun:
    """Host snapshot of a preempted in-flight decode — everything needed
    to resume the stream, bit-identical, in ANY free slot later.

    The same snapshot/publish split `distributed.checkpoint` uses: the
    live KV rows are copied device->host NOW (so the pool stays free to be
    donated to the next compiled call), and "publish" is the later
    `restore_run` writing them back.  `kv_rows` holds per-layer
    ``(k_rows, v_rows)`` numpy arrays of shape ``(pos, ...)``; sampling
    state (RNG key, write position, produced count, last token) rides
    along so decode step `pos` folds the same key it would have folded
    uninterrupted."""

    __slots__ = ("req", "resp", "pos", "produced", "last_token", "key",
                 "kv_rows", "draft_kv_rows", "preempted_at",
                 "source_config_hash")

    def __init__(self, run: _SlotRun, kv_rows, draft_kv_rows=None):
        self.req = run.req
        self.resp = run.resp
        self.pos = run.pos
        self.produced = run.produced
        self.last_token = run.last_token
        self.key = run.key
        self.kv_rows = kv_rows
        # speculative engines snapshot the draft pool rows too: resuming
        # with a coherent draft context preserves the accept rate (output
        # correctness never depends on draft KV — rejected proposals are
        # free — but garbage draft context would decay a resumed stream
        # to target-only throughput)
        self.draft_kv_rows = draft_kv_rows
        self.preempted_at = time.monotonic()
        # the source engine's transfer-identity digest
        # (transfer.engine_config_hash), stamped by preempt_slot so the
        # hash survives every manager-side re-encode hop of a migration
        # — a cross-manifest restore must be refused typed no matter how
        # many times the snapshot was decoded and re-encoded in between
        self.source_config_hash: Optional[str] = None

    @classmethod
    def from_state(cls, req, resp, pos: int, produced: int,
                   last_token: int, key, kv_rows, draft_kv_rows=None):
        """Build a PreemptedRun from raw snapshot state instead of a live
        _SlotRun — the run-transfer codec's decode side
        (serving/transfer.py): a snapshot that crossed a replica (or, via
        its byte form, a process) boundary restores through the SAME
        `restore_run` contract a locally preempted run uses."""
        paused = cls.__new__(cls)
        paused.req = req
        paused.resp = resp
        paused.pos = int(pos)
        paused.produced = int(produced)
        paused.last_token = int(last_token)
        paused.key = np.asarray(key)
        paused.kv_rows = kv_rows
        paused.draft_kv_rows = draft_kv_rows
        paused.preempted_at = time.monotonic()
        paused.source_config_hash = None
        return paused


class ServingEngine:
    """Continuous-batching engine over a model implementing the
    `gen_fixed_cache` / `forward_fixed` protocol (see the serving package
    docstring and models/gpt.py:190,201)."""

    def __init__(self, model, max_slots: int = 8, max_len: int = 256,
                 prefill_buckets=None, max_queue_depth: int = 64,
                 pad_token_id: int = 0, dtype=None,
                 decode_chunk: int = 4, draft_model=None,
                 spec_tokens: int = 4, kv: str = "fixed",
                 block_size: int = 16, num_blocks: Optional[int] = None,
                 mesh=None, program_set=None, prefix_cache: bool = False,
                 share_policy=None, lora=None):
        from ..generation import _model_fns
        self.model = model
        self.max_slots = int(max_slots)
        self.max_len = int(max_len)
        self.pad_token_id = int(pad_token_id)
        self.buckets = tuple(sorted(set(
            int(b) for b in (prefill_buckets or _default_buckets(max_len)))))
        if self.buckets[-1] > self.max_len:
            raise InvalidArgumentError(
                f"prefill bucket {self.buckets[-1]} exceeds max_len "
                f"{self.max_len}")
        self._dtype = dtype
        # a model that asks for it (`serving_batch_decode`) gets the WHOLE
        # batch of slots in one `forward_decode` call, a position a slot,
        # and a pool whose leaves differ by layer and within one
        # (`gen_fixed_cache` gives a window layer a ring, a summary leaf a
        # row a chunk); what is not built for such a model refuses
        self._batched = bool(getattr(model, "serving_batch_decode", False))
        if self._batched:
            for given, what, missing in (
                    (kv == "paged", "kv='paged'",
                     "the paged view (kv_pool.PagedKVView) gathers and "
                     "scatters `(k, v)` pairs through one block table for "
                     "every layer: it has no ring of a window's rows and "
                     "takes no layer of other leaves (a latent layer's, or "
                     "a summary leaf's row a chunk)"),
                    (prefix_cache, "prefix_cache=True",
                     "prefix reuse shares blocks of the paged pool (which "
                     "holds `(k, v)` pairs alone), a ring overwrites "
                     "the rows a later request would share, and a summary "
                     "leaf could be shared at a window's boundary alone"),
                    (draft_model is not None, "draft_model=",
                     "the verify program scores K+1 positions a slot "
                     "through `forward_fixed`, which this model has not"),
                    (mesh is not None, "mesh=",
                     "`_init_mesh` lays out dense layers by name and "
                     "shards no expert axis"),
                    (lora is not None, "lora=",
                     "adapter hooks attach to `Linear` sublayers and enter "
                     "per vmapped row; the batched program has neither")):
                if given:
                    raise InvalidArgumentError(
                        f"{what} is not built for a model that decodes the "
                        f"whole batch ({type(model).__name__}): {missing}. "
                        f"Drop {what} on this engine.")
        # tokens decoded per compiled decode call (an internal lax.scan):
        # amortizes the per-call host+dispatch cost across chunk tokens per
        # slot.  Tokens stream in bursts of `chunk`; admission, deadline
        # and cancel sweeps run between calls.  A slot finishing mid-chunk
        # wastes its tail iterations (its post-finish tokens are discarded
        # on the host and its KV garbage is overwritten by the slot's next
        # prefill) — with budgets >> chunk the waste is marginal and the
        # dispatch amortization dominates on every backend.
        self.decode_chunk = max(1, int(decode_chunk))
        self.scheduler = RequestScheduler(self.max_slots, max_queue_depth)
        # batched LoRA adapters (paddle_tpu.lora): per-slot adapter ids
        # are DYNAMIC inputs to the same program family and the factor
        # stacks ride as ordinary program arguments, so heterogeneous
        # adapters batch in one tick at the unchanged compile bound.
        # The hooks are armed BEFORE _model_fns so every traced program
        # sees them; they add no state keys (swap_weights / refresh /
        # transfer are untouched).
        self.lora = lora
        self._lora_reg = None
        self._lora_keys: Tuple[str, ...] = ()
        if lora is not None:
            if draft_model is not None:
                raise InvalidArgumentError(
                    "lora=LoRAConfig(...) and draft_model= (speculative "
                    "decoding) cannot be combined on one engine yet: the "
                    "verify program's draft proposals would need their "
                    "own per-slot adapter gathers.  Drop draft_model= on "
                    "this engine (adapters usually matter more than spec "
                    "speedup for multi-tenant traffic), or route "
                    "speculative traffic to a separate non-LoRA engine "
                    "until spec-decode composition lands.")
            if prefix_cache:
                raise InvalidArgumentError(
                    "lora=LoRAConfig(...) and prefix_cache=True cannot "
                    "be combined on one engine yet: cached KV blocks are "
                    "computed under ONE adapter's projections, so a warm "
                    "hit served to a different adapter would be silently "
                    "wrong.  Drop prefix_cache=True on this engine, or "
                    "serve prefix-heavy base-model traffic from a "
                    "separate non-LoRA engine until per-adapter cache "
                    "partitioning lands.")
            from ..lora.layers import attach_serving_lora
            from ..lora.registry import AdapterRegistry
            from ..lora.train import base_weights_hash
            shapes = attach_serving_lora(model, lora.targets)
            base_sha = (base_weights_hash(model)
                        if lora.check_base_hash and lora.base_sha is None
                        else None)
            self._lora_reg = AdapterRegistry(lora, shapes,
                                             base_sha=base_sha)
            self._lora_keys = self._lora_reg.keys
        self._state, self._apply = _model_fns(model)
        self.draft_model = draft_model
        self.spec_tokens = int(spec_tokens)
        if draft_model is not None:
            if self.spec_tokens < 1:
                raise InvalidArgumentError(
                    f"spec_tokens must be >= 1, got {self.spec_tokens}")
            if self.spec_tokens >= self.max_len:
                raise InvalidArgumentError(
                    f"spec_tokens {self.spec_tokens} must be < max_len "
                    f"{self.max_len}")
        # pool length: speculative engines get spec_tokens rows of
        # HEADROOM beyond max_len — a verify tick writes K+1 rows at
        # pos..pos+K even when only one commits, and pos legitimately
        # reaches plen+max_new-2 <= max_len-2; without the headroom the
        # final ticks of a full-budget request would have
        # dynamic_update_slice CLAMP the write start and silently
        # overwrite committed KV (breaking greedy parity).  Request
        # validation stays at plen+max_new <= max_len.
        self._pool_len = self.max_len + (
            self.spec_tokens if draft_model is not None else 0)
        # tensor parallelism: lay the params out over the mesh BEFORE any
        # program traces — prefill/decode/verify then compile once under
        # the mesh and XLA GSPMD owns the collectives
        self.mesh = mesh
        self._kv_put = None
        if mesh is not None:
            self._init_mesh(mesh)
            self._state = self._shard_state(self._state)
        if kv not in ("fixed", "paged"):
            raise InvalidArgumentError(
                f"kv must be 'fixed' or 'paged', got {kv!r}")
        self.kv = kv
        # prefix-aware KV reuse (serving/prefix_cache.py): opt-in so the
        # plain paged engine keeps its exact PR-8 allocation behavior
        if prefix_cache and kv != "paged":
            raise InvalidArgumentError(
                f"prefix_cache=True cannot be combined with kv={kv!r}: "
                "prefix reuse shares immutable KV BLOCKS between "
                "requests, and only the paged layout has blocks to "
                "share.  Pass kv='paged' on this engine, or drop "
                "prefix_cache=True to keep the fixed layout.")
        if prefix_cache and draft_model is not None:
            raise InvalidArgumentError(
                "prefix_cache=True and draft_model= (speculative "
                "decoding) cannot be combined on one engine yet: the "
                "draft pool shares the target's block tables, but the "
                "cached-prefill half of the draft path is "
                "unimplemented, so a warm hit would leave the draft KV "
                "incoherent.  Drop one of the two knobs on this engine "
                "— keep prefix_cache=True for prompt-template traffic, "
                "or keep draft_model= for long-decode traffic — or "
                "split the traffic across two engines until the "
                "composition lands.")
        self.prefix_cache = None
        self._share_policy = share_policy
        self._share_groups: Dict[str, str] = {}
        self._cow_fn = None
        self.block_size = int(block_size)
        if kv == "paged" and self.block_size < 1:
            raise InvalidArgumentError(
                f"block_size must be >= 1, got {self.block_size}")
        # rows one compiled tick may write per slot (capacity ensured
        # host-side before each paged call)
        self._rows_per_tick = (self.spec_tokens + 1
                               if draft_model is not None
                               else self.decode_chunk)
        if kv == "paged":
            if num_blocks is None:
                # default capacity parity with the fixed pool: paged is
                # opt-in HBM shaping, not a silent budget cut
                num_blocks = self.max_slots * (
                    -(-self._pool_len // self.block_size))
            # THE pool: one [num_blocks, block_size, heads, dim] block
            # pool per layer + the host-side allocator (kv_pool.py)
            self.kv_pool = PagedKVPool(int(num_blocks), self.block_size,
                                       self._pool_len)
            self._pools = self.kv_pool.build_pools(model, dtype,
                                                   put=self._kv_put)
            self._cache = PagedKVView(self.kv_pool, self.max_slots)
            # OOM preemption state: runs parked when the block pool runs
            # dry mid-decode, resumed as it drains (bounded — overflow is
            # the typed KVPoolExhaustedError path)
            self._oom_paused: List[PreemptedRun] = []
            self._max_oom_paused = max(2, 2 * self.max_slots)
            self._oom_preempts = 0
            self._oom_failed = 0
            if prefix_cache:
                from .prefix_cache import PrefixCache
                self.prefix_cache = PrefixCache(self.kv_pool)

                # copy-on-write device copy: ONE jitted block copy
                # (src/dst are dynamic scalars — a single compile),
                # precompiled at warmup against the sentinel dst so the
                # zero-post-warmup-compiles contract holds under COW
                def _cow(pools, src, dst):
                    return [(kp.at[dst].set(kp[src], mode="drop"),
                             vp.at[dst].set(vp[src], mode="drop"))
                            for kp, vp in pools]

                self._cow_fn = jax.jit(_cow, donate_argnums=(0,))
        else:
            self.kv_pool = None
            self._cache = FixedKVView()
            # THE pool: one gen_fixed_cache(max_slots, pool_len)
            # allocation, reused for the engine's lifetime
            self._pools = model.gen_fixed_cache(self.max_slots,
                                                self._pool_len, dtype)
            if self._kv_put is not None:
                self._pools = [(self._kv_put(k), self._kv_put(v))
                               for k, v in self._pools]
        self._assert_kv_sharded(self._pools, "KV pool")
        self._warm = False
        self._slots: Dict[int, _SlotRun] = {}
        # device-resident decode batch (`_rebuild_batch`)
        self._dev = None
        self._batch_dirty = True
        self._rid = 0
        self._submit_lock = threading.Lock()
        # nan_logits fault: presence decided NOW (trace time) — the clean
        # decode program carries zero fault branches
        self._poison_target = faults.nan_logits_request()
        self._key_width = len(np.asarray(jax.random.PRNGKey(0)))
        self._compiles = {"decode": 0, "prefill": {b: 0 for b in self.buckets}}
        self._decode_calls = 0  # slow_decode fault stride counter
        self._steps = 0  # steps that had work: the `serving_step` span's id
        # speculative decoding: a draft model swaps the decode program for
        # the single verify program and adds a draft slot pool + draft
        # prefill folded into the per-bucket prefill programs — the
        # compiled-program bound stays len(buckets) + 1
        if draft_model is not None:
            self._dstate, self._dapply = _model_fns(draft_model)
            if mesh is not None:
                self._dstate = self._shard_state(self._dstate)
            if self.kv == "paged":
                # the draft pool pages too, SHARING the target's block
                # tables (one allocator): a slot's draft KV lives at the
                # same block ids in the draft leaf arrays
                self._draft_pools = self.kv_pool.build_pools(
                    draft_model, dtype, put=self._kv_put)
            else:
                self._draft_pools = draft_model.gen_fixed_cache(
                    self.max_slots, self._pool_len, dtype)
                if self._kv_put is not None:
                    self._draft_pools = [(self._kv_put(k), self._kv_put(v))
                                         for k, v in self._draft_pools]
            self._assert_kv_sharded(self._draft_pools, "draft KV pool")
            # draft_diverge fault: presence decided NOW (trace time); the
            # per-tick flag is a dynamic input
            self._diverge_every = faults.draft_diverge_every()
            self._spec_ticks = 0
            self._h_accept = _obs_m.histogram(
                "serving_spec_accept_rate",
                "accepted draft proposals / spec_tokens, per slot per tick")
            self._spec_proposed = 0
            self._spec_accepted = 0
            self._decode_fn = self._build_verify()
        else:
            if self._batched:
                self._init_batched()
            self._decode_fn = self._build_decode()
        self._prefill_fns = {b: self._build_prefill(b) for b in self.buckets}
        # AOT program set (paddle_tpu.programs.program_set): swap the
        # freshly built — but never yet traced — program family for
        # deserialized ones.  'exe' programs are already-compiled native
        # executables (zero trace + zero compile at warmup); 'stablehlo'
        # ones compile their portable module on first call.  A manifest
        # mismatch or corrupt artifact raises ProgramSetError here —
        # the predictor layer catches it and falls back to tracing.
        self.program_set_info = None
        self._warm_marks = None
        if program_set is not None:
            from ..programs.program_set import load_program_set
            loaded = load_program_set(program_set, self)
            self._decode_fn = loaded["decode"]
            for b in self.buckets:
                self._prefill_fns[b] = loaded[f"prefill_b{b}"]
            self.program_set_info = {
                "path": program_set if isinstance(program_set, str)
                else None,
                "kinds": {k: v.kind for k, v in loaded.items()}}
        # observability: latency histograms shared with the unified
        # report / Prometheus endpoint (handles cached; registry.reset()
        # zeroes values in place)
        self._h_ttft = _obs_m.histogram(
            "serving_ttft_seconds", "submit -> first streamed token")
        self._h_itl = _obs_m.histogram(
            "serving_inter_token_seconds",
            "gap between consecutive tokens of one request")
        # metrics accumulators
        self._m_lock = threading.Lock()
        self._tokens_out = 0
        self._completed = 0
        self._errored = 0
        self._started_at = time.monotonic()
        # background loop
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self._work = threading.Event()
        self._closed = False
        # close() is idempotent and safe under concurrent double-close:
        # the fleet's replica manager fences and closes aggressively
        # (drain completion, crash handling, rollout teardown and the
        # user's own close can race), so exactly ONE caller runs the
        # join + abort sequence and everyone else returns once it's done
        self._close_lock = threading.Lock()
        self._dead: Optional[BaseException] = None
        # continuous weight refresh (serving/refresh.py): which published
        # artifact this engine currently serves (None = constructor
        # weights) and how many swaps it has absorbed.  Both are
        # host-side bookkeeping only — the compiled programs take the
        # state dict as a per-call argument, so swapping never retraces.
        self.weights_sha: Optional[str] = None
        self.refresh_epoch = 0

    # ------------------------------------------------------------------
    # continuous weight refresh
    # ------------------------------------------------------------------
    def swap_weights(self, state: Dict, weights_sha: Optional[str] = None):
        """Rebind the served weights to `state` with ZERO recompiles.

        Every compiled prefill/decode/verify program takes the state
        dict as an explicit call argument (never a closed-over
        constant), so a shape/dtype-stable swap reuses the loaded
        program set untouched — the next engine step simply passes the
        new arrays.  The caller (fleet flip choreography) guarantees the
        engine is idle or between steps on the driving thread; any
        in-progress compiled call keeps the OLD dict it was handed.

        Validates the exact key set + per-leaf shape/dtype against the
        current state and raises InvalidArgumentError on any mismatch —
        a wrong-architecture publish must never half-apply.  Under a
        mesh every leaf is re-placed with the incumbent leaf's sharding.
        A prefix cache is flushed: cached KV embeds the old weights'
        activations and would break new-weights bit-identity.
        """
        old = self._state
        missing = set(old) - set(state)
        unexpected = set(state) - set(old)
        if missing or unexpected:
            raise InvalidArgumentError(
                f"swap_weights state-dict key mismatch: missing "
                f"{sorted(missing)[:4]}, unexpected "
                f"{sorted(unexpected)[:4]}")
        for k, cur in old.items():
            new = state[k]
            if tuple(np.shape(new)) != tuple(np.shape(cur)):
                raise InvalidArgumentError(
                    f"swap_weights shape mismatch for {k!r}: "
                    f"{tuple(np.shape(new))} != {tuple(np.shape(cur))}")
        if self.mesh is not None:
            state = {k: jax.device_put(np.asarray(v, dtype=np.asarray(
                old[k]).dtype), old[k].sharding)
                for k, v in state.items()}
        else:
            state = {k: jnp.asarray(np.asarray(v), dtype=jnp.asarray(
                old[k]).dtype) for k, v in state.items()}
        # atomic rebind: one reference assignment — readers see either
        # the complete old dict or the complete new one
        self._state = state
        self.weights_sha = weights_sha
        self.refresh_epoch += 1
        if self.prefix_cache is not None:
            # old-weights KV must never seed a new-weights stream
            self.prefix_cache.evict(self.prefix_cache.resident_nodes())
        if self._lora_reg is not None and self.lora.check_base_hash:
            # loaded adapters SURVIVE the flip (the factor stacks are
            # registry state, not engine state, and the forward hooks
            # live on the layer objects) — but the registry's base pin
            # must follow the weights: a FUTURE register() now checks
            # artifacts against the base actually being served, not the
            # boot-time one
            from ..lora.train import state_hash
            self._lora_reg.base_sha = state_hash(self._state)

    def load_adapter(self, name: str, path: str) -> str:
        """Page a tenant's exported LoRA artifact into the adapter
        registry under `name` — hot: ZERO recompiles (the factor stacks
        are program ARGUMENTS; the slot write reuses the registry's
        pre-traced scatter) and safe while the engine loop is serving
        (no donation, see AdapterRegistry).  Idempotent for identical
        artifact bytes.  Returns the artifact's file sha256 (the fleet's
        re-attach cache key).  Typed failures: AdapterIntegrityError
        (corrupt / wrong base), InvalidArgumentError (rank/target
        mismatch), AdapterExhaustedError (every slot pinned)."""
        if self.lora is None:
            raise InvalidArgumentError(
                "load_adapter requires an engine constructed with "
                "lora=LoRAConfig(...) — this engine serves the base "
                "model only")
        idx = self._lora_reg.register(name, path)
        return self._lora_reg.file_sha(idx)

    # ------------------------------------------------------------------
    # tensor parallelism over the mesh
    # ------------------------------------------------------------------
    def _init_mesh(self, mesh):
        """Resolve the KV-pool placement for `mesh`: KV leaves are
        (*, rows, heads, head_dim)-shaped, so the heads axis (axis 2)
        shards over ``tp`` — each device holds its heads' slice of every
        slot/block, the layout heads-sharded attention consumes with zero
        collectives.  A single leaf whose head count does not divide tp
        stays replicated; if EVERY leaf ends up replicated, __init__
        raises (the no-silent-full-replication guard)."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        tp = mesh.shape.get("tp", 1)
        self._mesh_tp = int(tp)

        def place_kv(leaf):
            if leaf.ndim >= 3 and tp > 1 and leaf.shape[2] % tp == 0:
                spec = P(*((None, None, "tp") + (None,) * (leaf.ndim - 3)))
            else:
                spec = P()
            return jax.device_put(leaf, NamedSharding(mesh, spec))

        self._kv_put = place_kv

    def _assert_kv_sharded(self, pools, what: str):
        """The loud no-silent-replication guard: a head count that does
        not divide tp would otherwise replicate the whole pool on every
        device (tp x the HBM) without a word.  Applied to the target AND
        draft pools."""
        if (self._kv_put is not None and self._mesh_tp > 1
                and all(k.sharding.is_fully_replicated
                        and v.sharding.is_fully_replicated
                        for k, v in pools)):
            raise InvalidArgumentError(
                f"tensor-parallel {what} fully replicated: no KV leaf's "
                f"head axis divides tp={self._mesh_tp} — fix the head "
                "count or the mesh (a replicated pool costs tp x the "
                "HBM and defeats the sharding)")

    def _shard_state(self, state):
        """Megatron layout via parallel.sharding.param_specs: column-
        parallel qkv/ffn_in, row-parallel proj/ffn_out, vocab-sharded
        embeddings; anything unmatched (norms, biases of row layers)
        replicates."""
        from jax.sharding import NamedSharding
        from ..parallel.sharding import param_specs
        specs = param_specs(
            {k: tuple(np.shape(v)) for k, v in state.items()},
            self.mesh, tensor_parallel=True)
        return {k: jax.device_put(v, NamedSharding(self.mesh, specs[k]))
                for k, v in state.items()}

    # ------------------------------------------------------------------
    # compiled programs: three bodies over the cache view and the row
    # forward, one signature (module docstring)
    # ------------------------------------------------------------------
    def _lora_ctx(self, weights, aid=None):
        """Trace-time adapter context for program bodies: on an engine
        with adapters it rebinds the `lora` weights ((A,B) per key +
        scales) to the engine's static key tuple and scopes the (traced)
        adapter id so the forward hooks installed by
        `attach_serving_lora` see it; any other engine enters nothing.
        Entered per vmapped row in decode (aid is the row's scalar) and
        once per prefill (aid is the request's scalar)."""
        if self.lora is None:
            return contextlib.nullcontext()
        from ..lora.layers import adapter_context
        pairs, scales = weights["lora"]
        return adapter_context(dict(zip(self._lora_keys, pairs)),
                               scales, aid)

    def _row_forward(self, who, weights, inputs, every=False):
        """`step(ids, view, pos) -> (logits, view)` over all slots, for
        the model or the draft (`who`): `forward_fixed` on ONE slot's
        cache leaves at the slot's own position, under `jax.vmap` over
        the slot axis, giving the last position's logits (with `every`,
        all of them) in float32 and the slot's new leaves.  The decode
        chunk, the verify tick's draft scan and its target forward are
        all this one function.  On an engine with adapters each row
        enters the adapter context under its own id (one more vmapped
        operand): heterogeneous adapters batch in ONE tick, one program.
        A model that decodes the whole batch (`serving_batch_decode`)
        gets its `forward_decode` over all slots in its place — no vmap:
        a routed layer routes once and its experts see every slot's
        token, rows of empty slots routed nowhere — which returns the
        model's counts as a third value."""
        state = weights[who]
        if self._batched:
            return lambda tokens, view, pos: self._apply_decode(
                state, tokens, view, pos, inputs["active"])
        apply = self._apply if who == "model" else self._dapply
        aids = (inputs["aids"],) if self.lora is not None else ()

        def row(ids, caches, p, *aid):
            c = [(k[None], v[None]) for (k, v) in caches]
            with self._lora_ctx(weights, *aid):
                # a token or a slot's K+1 of them -> (1, n)
                logits, new = apply(state, ids[(None,) * (2 - ids.ndim)],
                                    c, p)
            logits = logits[0] if every else logits[0, -1]
            return (logits.astype(jnp.float32),
                    [(k[0], v[0]) for (k, v) in new])

        return lambda ids, view, pos: jax.vmap(row)(ids, view, pos, *aids)

    def _prompt_forward(self, who, weights, pools, inputs):
        """The prompt's forward for the model or the draft (`who`) and
        its rows written into that pool: -> (logits, the new pool).  The
        prompt, right-padded to the bucket, runs through `forward_fixed`
        against what the cache view gives it — a bucket-sized scratch
        cache at 0, or with a prefix cache the slot's own view at
        `cached_len` — under the request's adapter where the engine has
        adapters.  A model that decodes the whole batch gets its
        `forward_prefill` in its place, which returns the last position's
        logits alone (a bucket's logits over the whole vocabulary are not
        needed) and the model's counts as a third value."""
        state, ids = weights[who], inputs["ids"]
        if self._batched:
            logits, kv, counts = self._apply_prefill(state, ids,
                                                     inputs["prompt_len"])
            return (logits,
                    self._cache.write_prompt(pools[who], kv, inputs), counts)
        model, apply = ((self.model, self._apply) if who == "model"
                        else (self.draft_model, self._dapply))
        caches, at = self._cache.prompt_cache(
            pools[who], inputs,
            lambda: model.gen_fixed_cache(1, ids.shape[1], self._dtype))
        with self._lora_ctx(weights, inputs.get("aid")):
            logits, kv = apply(state, ids, caches, at)
        return logits, self._cache.write_prompt(pools[who], kv, inputs)

    def _build_prefill(self, bucket: int):
        """One per-bucket prefill program: the prompt's forward, its rows
        written into the slot (the fixed view overwrites the slot's FULL
        range, the paged one every block the bucket covers: no stale KV
        survives re-serving), and the first generated token sampled from
        the prompt's last-position logits.  On a speculative engine the
        SAME program additionally prefills the draft pool (one draft
        forward over the same padded ids) — the first token still comes
        from the target's logits, so greedy parity is identical with and
        without a draft.  Adapters and a cached prefix add dynamic inputs
        (`aid`, adapter id 0 = base model; `cached_len`, 0 IS the cold
        path), never a program: the bound stays one per bucket."""
        def prefill(weights, pools, inputs):
            self._compiles["prefill"][bucket] += 1  # trace-count (host)
            stat_add("STAT_serving_compiles")
            prompt_len = inputs["prompt_len"]
            new_pools = {}
            logits, new_pools["model"], *counts = self._prompt_forward(
                "model", weights, pools, inputs)
            if "draft" in pools:
                _, new_pools["draft"] = self._prompt_forward(
                    "draft", weights, pools, inputs)
            # where the prompt's last position lies in the logits the
            # forward gave: a batched model returns that position alone,
            # and a cached prefix was not computed
            if self._batched:
                idx = 0
            else:
                idx = prompt_len - 1
                if "cached_len" in inputs:
                    idx = idx - inputs["cached_len"]
            tok, logp, finite = _first_token_at(
                logits, idx, prompt_len - 1,
                *(inputs[k] for k in ("key",) + _SAMPLING[1:]))
            out = {"tok": tok, "logp": logp, "finite": finite,
                   "pools": new_pools}
            if counts:
                out["counts"] = counts[0]
            return out

        kind = ("prefill_spec" if self.draft_model is not None
                else "prefill_cached" if self.prefix_cache is not None
                else "prefill")
        return track(f"serving_{kind}_b{bucket}",
                     jax.jit(prefill, donate_argnums=(1,)))

    def _build_decode(self):
        """THE decode step: `decode_chunk` iterations of the row forward
        and `_sample_step` in one `lax.scan` over the cache view — the
        pool itself, or every slot's block table gathered ONCE per call,
        the chunk's rows scattered back in one pass after it.  The
        per-call host+dispatch cost is amortized across chunk * slots
        tokens.  The final (tokens, pos) carry is exactly the next call's
        input while batch membership is unchanged: the engine feeds the
        device arrays straight back, so a steady-state decode call
        uploads nothing."""
        poison_armed = self._poison_target is not None
        chunk = self.decode_chunk
        cache = self._cache

        def decode(weights, pools, inputs):
            self._compiles["decode"] += 1  # trace-count (host side effect)
            stat_add("STAT_serving_compiles")
            forward = self._row_forward("model", weights, inputs)
            sampling = [inputs[k] for k in _SAMPLING]
            pos0 = inputs["pos"]

            def one(carry, _):
                tokens, pos, view = carry
                last, view, *counts = forward(tokens, view, pos)
                if poison_armed:
                    last = faults.poison_logits(last, inputs["poison"])
                finite = jnp.isfinite(last).all(axis=-1)
                tok, logp = _sample_step(last, pos, *sampling)
                return (tok, pos + 1, view), (tok, logp, finite, *counts)

            (tokens, pos, view), (toks, logps, finites, *counts) = \
                jax.lax.scan(one, (inputs["tokens"], pos0,
                                   cache.open(pools["model"], inputs)),
                             None, length=chunk)
            out = {"toks": toks, "logps": logps, "finites": finites,
                   "tokens": tokens, "pos": pos,
                   "pools": {"model": cache.publish(
                       pools["model"], view, inputs, pos0, chunk)}}
            if counts:   # the model's, summed over the chunk's steps
                out["counts"] = jnp.sum(counts[0], axis=0)
            return out

        return track("serving_decode",
                     jax.jit(decode, donate_argnums=(1,)))

    def _init_batched(self):
        """What an engine over a `serving_batch_decode` model adds: the two
        entries of the model's protocol, which pool leaves are rings, and
        the routed layers' counters."""
        from ..jit import functional_call
        model = self.model

        def apply_prefill(state, ids, prompt_len):
            return functional_call(model, state, ids, prompt_len,
                                   training=False, method="forward_prefill")

        def apply_decode(state, tokens, caches, pos, active):
            return functional_call(model, state, tokens, caches, pos, active,
                                   training=False, method="forward_decode")

        self._apply_prefill, self._apply_decode = apply_prefill, apply_decode
        # what the programs' int32 counts are: a model may name them
        # (`serving_count_names`) and they ride into the spans' args under
        # those names, unread; else they are the routed family's five, with
        # two of the cache behind them (`_count_routed`)
        self._count_names = getattr(model, "serving_count_names", None)
        # and what a slot holds at a position, where the model says it
        self._rows_held = getattr(model, "serving_rows_held", None)
        # rows of each leaf of each layer: shorter than the pool = a
        # window's ring, or rows on a clock of the model's own
        self._leaf_rows = [tuple(int(leaf.shape[1]) for leaf in layer)
                           for layer in self._pools]
        # what `serving_kv_rows{kind}` calls them: the model's word for its
        # cache (`serving_cache_kind`, e.g. "latent"), else by length
        named = getattr(model, "serving_cache_kind", None)
        self._leaf_kinds = [
            tuple(named or ("window" if rows < self._pool_len else "full")
                  for rows in layer) for layer in self._leaf_rows]
        self._c_picks = _obs_m.counter(
            "moe_routed_picks_total",
            "picks of the routed layers (tokens x experts a token x "
            "layers), by whether the expert is held here", ("where",))
        self._c_hit = _obs_m.counter(
            "moe_experts_hit_total",
            "held experts that got at least one token, a layer a step")
        self._c_rows = _obs_m.counter(
            "moe_expert_rows_total",
            "rows the grouped expert products went over (a chunk's rows x "
            "chunks walked, a layer a step)")
        self._g_kv_rows = _obs_m.gauge(
            "serving_kv_rows",
            "cache rows the running requests hold, summed over layers, by "
            "kind of layer", ("kind",))

    def _count_model(self, span_args: dict, counts):
        """A program's int32 counts into its span's args.  A model that
        names them (`serving_count_names`) is carried unread: each count
        under its name, nothing else known of it.  A model that does not
        is of the routed family, whose counts feed counters too."""
        if self._count_names is None:
            return self._count_routed(span_args, counts)
        span_args.update(zip(self._count_names, (int(c) for c in counts)))

    def _count_routed(self, span_args: dict, counts):
        """A program's routed counts [here, all, experts hit, grouped
        products made, rows they went over] into its span's args and the
        counters; a model that counts its cache too (two more: rows the
        call's requests hold, rows its attention went over) gets those
        into the args as `kv_rows_live` and `kv_rows_pool`.  A call that
        made no grouped product (its routed layers took the batched form)
        leaves `expert_products` and `expert_rows` out: a reader of the
        device trace takes every span that carries them as a call whose
        grouped products' events it must find."""
        here, total, hit, products, rows = (int(c) for c in counts[:5])
        span_args.update(routed_here=here, routed_all=total,
                         experts_hit=hit)
        if products:
            span_args.update(expert_products=products, expert_rows=rows)
        if len(counts) > 5:
            span_args.update(kv_rows_live=int(counts[5]),
                             kv_rows_pool=int(counts[6]))
        self._c_picks.labels(where="here").inc(here)
        self._c_picks.labels(where="elsewhere").inc(total - here)
        self._c_hit.inc(hit)
        self._c_rows.inc(rows)

    def _refuse_batched(self, what: str):
        if self._batched:
            raise InvalidArgumentError(
                f"{what} is not built for a model that decodes the whole "
                f"batch ({type(self.model).__name__}): a snapshot takes "
                "rows [0, pos) of every layer's `(k, v)` pair; a window "
                "layer's ring holds positions pos - rows .. pos at pos % "
                "rows, a latent layer's leaves (512 and 64 wide) are "
                "no `(k, v)` pair, and a summary leaf holds a pooled row a "
                "chunk at pos // chunk, not a token's")

    def _gauge_kv_rows(self):
        """Rows the running requests hold as the decode call read them,
        summed over layers, by kind.  What a slot holds at a position is
        the model's to say (`serving_rows_held(pos) -> {kind: rows}`: a
        ring wraps, a summary row stands for a chunk); a model that says
        nothing holds a row a token in every layer, a ring at most its own
        length (a layer's leaves of one length are the parts of one row)."""
        if self._rows_held is not None:
            held = {}
            for run in self._slots.values():
                for kind, n in self._rows_held(run.pos).items():
                    held[kind] = held.get(kind, 0) + n
        else:
            held = {kind: 0 for layer in self._leaf_kinds for kind in layer}
            for run in self._slots.values():
                for rows, kinds in zip(self._leaf_rows, self._leaf_kinds):
                    for n, kind in dict(zip(rows, kinds)).items():
                        held[kind] += min(run.pos, n)
        for kind, n in held.items():
            self._g_kv_rows.labels(kind=kind).set(n)

    def _build_verify(self):
        """THE speculative tick (draft_model engines): K sequential draft
        proposals, one batched target forward over [last_committed,
        d_1..d_K] (K+1 positions), in-program accept/reject + commit
        (generation.speculative).  Draft and target each run against
        their pool's cache view and publish the K+1 rows they wrote (a
        paged draft pool pages through the SAME tables).  One trace,
        ever: sampling params, spec on/off, poison and diverge are all
        dynamic per-slot/per-tick inputs."""
        from ..generation.speculative import (commit_speculative_greedy,
                                              commit_speculative_sampled)
        poison_armed = self._poison_target is not None
        diverge_armed = self._diverge_every is not None
        k_spec = self.spec_tokens
        pad = self.pad_token_id
        cache = self._cache

        def verify(weights, pools, inputs):
            self._compiles["decode"] += 1  # trace-count (host side effect)
            stat_add("STAT_serving_compiles")
            drow = self._row_forward("draft", weights, inputs)
            trow = self._row_forward("model", weights, inputs, every=True)
            tokens, pos, spec_on = (inputs["tokens"], inputs["pos"],
                                    inputs["spec_on"])
            sampling = keys, temp, top_k, top_p, greedy = [
                inputs[k] for k in _SAMPLING]

            def dstep(carry, i):
                cur, dview = carry
                dlast, dview = drow(cur, dview, pos + i)
                if diverge_armed:
                    dlast = faults.poison_draft_logits(dlast,
                                                       inputs["diverge"])
                dfin = jnp.isfinite(dlast).all(axis=-1)
                prop, q = _draft_propose(dlast, pos, i, *sampling)
                return (prop, dview), (prop, q, dfin)

            # K+1 draft steps, not K: step K feeds the LAST proposal d_K
            # at pos+K so a fully-accepted tick leaves the draft pool
            # dense (d_K commits when everything accepts; without this
            # row every all-accept tick would punch a permanent zero-KV
            # hole the draft attends over forever, decaying the accept
            # rate cumulatively — worst exactly when the draft is good).
            # Step K's proposal/q outputs are discarded; on a rejection
            # its KV row is beyond the committed prefix and the next
            # tick overwrites it before any query can attend it.
            (_, dview), (props, qs, dfins) = jax.lax.scan(
                dstep, (tokens, cache.open(pools["draft"], inputs)),
                jnp.arange(k_spec + 1))
            dpools = cache.publish(pools["draft"], dview, inputs, pos,
                                   k_spec + 1)
            props = props[:k_spec].T             # (S, K)
            qs = jnp.swapaxes(qs[:k_spec], 0, 1)  # (S, K, V)
            dfin = dfins.all(axis=0)             # (S,)

            # target scores all K proposals + the bonus position in ONE
            # forward of K+1 tokens per slot
            ids = jnp.concatenate([tokens[:, None], props], axis=1)
            tlog, tview = trow(ids, cache.open(pools["model"], inputs),
                               pos)              # (S, K+1, V)
            tpools = cache.publish(pools["model"], tview, inputs, pos,
                                   k_spec + 1)
            if poison_armed:
                factor = jnp.where(inputs["poison"],
                                   jnp.float32(float("nan")),
                                   jnp.float32(1.0))
                tlog = tlog * factor[:, None, None]
            # draft non-finiteness only matters for slots actually
            # speculating — a spec-off slot must never die for garbage in
            # a pool it does not consume
            finite = (jnp.isfinite(tlog).all(axis=(1, 2))
                      & (dfin | ~spec_on))

            def proc_all(t):
                flat = t.reshape(-1, t.shape[-1])

                def rep(a):
                    return jnp.repeat(a, k_spec + 1, axis=0)
                return process_logits_dynamic(
                    flat, rep(temp), rep(top_k), rep(top_p),
                    rep(greedy)).reshape(t.shape)

            plog = jax.lax.cond(jnp.all(greedy), lambda t: t, proc_all,
                                tlog)
            ops = (props, qs, plog, keys, pos, greedy, spec_on)
            out, count, accepted, last, logps = jax.lax.cond(
                jnp.all(greedy),
                lambda o: commit_speculative_greedy(*o, pad),
                lambda o: commit_speculative_sampled(*o, pad), ops)
            return {"toks": out, "logps": logps, "finites": finite,
                    "commits": count, "accepts": accepted, "tokens": last,
                    "pos": pos + count,
                    "pools": {"model": tpools, "draft": dpools}}

        return track("serving_verify",
                     jax.jit(verify, donate_argnums=(1,)))

    def _program_args(self, inputs: Dict):
        """`(weights, pools, inputs)`, the arguments of every program of
        the engine: `weights` the trees it has (`model`, and `draft` /
        `lora` only when configured; passed on every call, never closed
        over, so `swap_weights` and `load_adapter` never retrace),
        `pools` the donated KV pools (`model`, `draft`), `inputs` the
        call's arrays from `_prefill_inputs` / `_decode_inputs`."""
        weights, pools = {"model": self._state}, {"model": self._pools}
        if self.draft_model is not None:
            weights["draft"], pools["draft"] = (self._dstate,
                                                self._draft_pools)
        if self.lora is not None:
            weights["lora"] = self._lora_reg.device_args()
        return weights, pools, inputs

    def _run(self, fn, inputs: Dict) -> Dict:
        """Call a program and take the pools it returns in place of the
        ones it was handed.  They are DONATED to every call: XLA updates
        the slots in place instead of copying max_slots * max_len of KV
        per call (measured 166x on a CPU pool-passthrough update; the
        same aliasing TPU donation does)."""
        out = fn(*self._program_args(inputs))
        self._pools = out["pools"]["model"]
        if self.draft_model is not None:
            self._draft_pools = out["pools"]["draft"]
        return out

    # ------------------------------------------------------------------
    # submission
    # ------------------------------------------------------------------
    def make_request(self, prompt, max_new_tokens: int,
                     decode_strategy: str = "greedy_search", temperature=1.0,
                     top_k=0, top_p=1.0, eos_token_id: Optional[int] = None,
                     seed: Optional[int] = None,
                     deadline: Optional[float] = None, priority: int = 0,
                     tenant: Optional[str] = None,
                     spec: Optional[bool] = None,
                     session: Optional[str] = None,
                     resubmit: bool = False,
                     adapter: Optional[str] = None):
        """Validate + build one (Request, Response) pair WITHOUT enqueuing
        it — the gateway's admission layer owns its own lanes and hands
        requests to `try_admit` directly.  Raises InvalidArgumentError for
        a prompt/budget the engine can never serve.

        `session` is the fleet router's affinity key; `resubmit=True`
        (greedy-only) opts into re-prefill-from-prompt recovery when the
        serving replica crashes and the run's KV snapshot dies with it —
        greedy decode is deterministic in the prompt alone, so the
        replayed stream is bit-identical and the fleet forwards only the
        not-yet-delivered suffix.  A sampled resubmit is rejected here,
        typed: a sampled replay is only reproducible through the engine's
        internal per-position key-fold schedule, which is not a contract —
        greedy-only keeps "the delivered prefix never changes" a property
        of the model, not of an implementation detail."""
        if self._closed:
            raise UnavailableError("serving engine is closed")
        if self._dead is not None:
            raise UnavailableError(
                f"serving engine loop died: {self._dead!r}")
        if decode_strategy not in ("greedy_search", "sampling"):
            raise InvalidArgumentError(
                f"serving supports 'greedy_search' or 'sampling', got "
                f"{decode_strategy!r} (beam search holds k hypotheses per "
                "slot — use generation.generate)")
        # spec=None -> the engine default: speculate whenever a draft
        # model is configured.  Explicit spec=True on a draftless engine
        # is a caller error, not a silent downgrade.
        if spec is None:
            spec = self.draft_model is not None
        elif spec and self.draft_model is None:
            raise InvalidArgumentError(
                "spec=True requires the engine to be built with a "
                "draft_model (speculative decoding)")
        if resubmit and decode_strategy != "greedy_search":
            raise InvalidArgumentError(
                "resubmit=True (re-prefill-from-prompt crash recovery) is "
                "greedy-only: a replayed sampled stream is not covered by "
                "any engine contract — drop resubmit or use greedy_search")
        # LoRA: reject unknown adapters NOW, typed — a consumer must
        # never hang on an adapter that was never (or is no longer)
        # loaded.  The slot is pinned later, at admission; if the
        # adapter is evicted while the request queues, admission fails
        # the request with the same typed error.
        if adapter is not None and self.lora is None:
            stat_add("STAT_serving_rejects")
            raise InvalidArgumentError(
                f"adapter={adapter!r} requires the engine to be built "
                "with lora=LoRAConfig(...)")
        if self.lora is not None and adapter is not None:
            try:
                self._lora_reg.resolve(adapter)
            except Exception:
                stat_add("STAT_serving_rejects")
                stat_add("STAT_lora_rejects")
                raise
        with self._submit_lock:
            rid = self._rid
            self._rid += 1
        req = Request(rid, prompt, max_new_tokens,
                      greedy=decode_strategy == "greedy_search",
                      temperature=temperature, top_k=top_k, top_p=top_p,
                      eos_token_id=eos_token_id,
                      seed=seed if seed is not None else rid,
                      deadline=deadline, priority=priority, tenant=tenant,
                      spec=bool(spec), session=session,
                      resubmit=resubmit, adapter=adapter)
        plen = req.prompt.shape[0]
        if plen > self.buckets[-1]:
            stat_add("STAT_serving_rejects")
            raise InvalidArgumentError(
                f"prompt length {plen} exceeds the largest prefill bucket "
                f"{self.buckets[-1]} (engine max_len={self.max_len})")
        if plen + req.max_new_tokens > self.max_len:
            stat_add("STAT_serving_rejects")
            raise InvalidArgumentError(
                f"prompt ({plen}) + max_new_tokens ({req.max_new_tokens}) "
                f"exceeds the engine's max_len {self.max_len}")
        if self.kv == "paged":
            # a request whose full budget can never fit the pool EVEN
            # ALONE is a caller error, not backpressure (with the default
            # num_blocks — fixed-capacity parity — this cannot trip).
            # The static need is the LARGER of the prefill bucket (what
            # admission actually allocates — plen rounds UP to it) and
            # the full row budget, so anything accepted here is
            # admittable by the gate once the pool drains.
            need = self._static_blocks_needed(req)
            if need > self.kv_pool.num_blocks:
                stat_add("STAT_serving_rejects")
                raise InvalidArgumentError(
                    f"request needs {need} KV blocks but the pool holds "
                    f"{self.kv_pool.num_blocks} "
                    f"(block_size={self.block_size}); raise num_blocks or "
                    "shrink the request")
        if self._poison_target is not None and rid == self._poison_target:
            req.poison = True
        resp = Response(req)
        stat_add("STAT_serving_requests")
        return req, resp

    def submit(self, prompt, max_new_tokens: int,
               decode_strategy: str = "greedy_search", temperature=1.0,
               top_k=0, top_p=1.0, eos_token_id: Optional[int] = None,
               seed: Optional[int] = None, deadline: Optional[float] = None,
               block: bool = False, timeout: Optional[float] = None,
               spec: Optional[bool] = None,
               tenant: Optional[str] = None,
               adapter: Optional[str] = None) -> Response:
        """Enqueue one request; returns its streaming Response.

        `tenant` scopes prefix-cache sharing (the gateway sets it from
        its auth context; direct engine callers may pass it for the
        same isolation).  Raises InvalidArgumentError for a
        prompt/budget the engine can never serve (prompt longer than
        the largest prefill bucket, or prompt + max_new_tokens past
        max_len), QueueFullError at max_queue_depth (backpressure).
        """
        req, resp = self.make_request(
            prompt, max_new_tokens, decode_strategy=decode_strategy,
            temperature=temperature, top_k=top_k, top_p=top_p,
            eos_token_id=eos_token_id, seed=seed, deadline=deadline,
            spec=spec, tenant=tenant, adapter=adapter)
        self.scheduler.submit(req, resp, block=block, timeout=timeout)
        self._work.set()
        return resp

    # ------------------------------------------------------------------
    # the engine loop
    # ------------------------------------------------------------------
    def step(self) -> bool:
        """One engine iteration: sweep deadlines/cancels, admit waiting
        requests into free slots (one bucketed prefill each), then advance
        every occupied slot one token with the single decode program.
        Returns whether any work was done.

        Each phase is a span of the one tracer and an annotation in any
        profiler session (names in PERF.md section 3, a contract with the
        benchmark): `serving_step` > `serving_sweep`, `serving_admit` >
        (`serving_prefill_dispatch`, `serving_prefill_wait`),
        `serving_decode` | `serving_verify` > (`serving_batch_rebuild`,
        `serving_decode_dispatch`, `serving_token_pull`,
        `serving_deliver`).  None per token and none per slot: counts
        ride in the spans' `args`."""
        if not self.has_work():
            # an idle loop polls every 2 ms: it does nothing below, and
            # must not fill the tracer's ring with empty steps
            return False
        self._steps += 1
        with _span("serving_step", args={"step": self._steps}):
            return self._step()

    def _step(self) -> bool:
        did = False
        with _span("serving_sweep"):
            self._sweep()
            dropped = self.scheduler.sweep_pending(
                drop=((self._queued_never_fits, self._queued_exhausted_exc)
                      if self.kv == "paged" else None))
            if dropped:
                with self._m_lock:
                    self._errored += dropped
            if self.kv == "paged":
                did = self._sweep_oom_paused()
        gate = None
        if self.kv == "paged":
            # OOM-parked runs hold progress and arrived earlier: they get
            # first claim on freed slots + blocks, before new admissions
            did = self._restore_oom_paused() or did
            gate = self._admission_gate
        while True:
            adm = self.scheduler.next_admission(gate=gate)
            if adm is None:
                break
            self._admit(*adm)
            did = True
        if self._slots:
            self._decode_step()
            did = True
        return did

    def _static_blocks_needed(self, req: Request) -> int:
        """Blocks the request is GUARANTEED to need: its prefill bucket
        (admission allocates bucket rows, plen rounds up) or the rows
        the runtime will actually BACK (`_rows_needed` — the ensure
        target; chunk/spec tail writes past it drop via the sentinel and
        never allocate), whichever is larger.  Using anything bigger
        here would spuriously reject requests the engine can serve."""
        return max(
            self.kv_pool.blocks_for(self._bucket_for(req.prompt.shape[0])),
            self.kv_pool.blocks_for(self._rows_needed(req)))

    def _queued_never_fits(self, req: Request) -> bool:
        """True when the queued request's prefill bucket cannot fit the
        pool even ALONE under the LIVE capacity (the fault cap) — it can
        never admit, so waiting is a hang, not backpressure; the sweep
        fails it with the typed KVPoolExhaustedError."""
        return (self.kv_pool.blocks_for(
                    self._bucket_for(req.prompt.shape[0]))
                > self.kv_pool.capacity())

    def _queued_exhausted_exc(self, req: Request) -> BaseException:
        # runs INSIDE the scheduler lock (sweep_pending's drop callback):
        # must not take _m_lock — metrics() holds _m_lock while reading
        # scheduler depths, so that order would be an ABBA deadlock; the
        # errored count is applied by step() from sweep's return value
        stat_add("STAT_serving_kv_exhausted")
        return KVPoolExhaustedError(
            f"request {req.id}: prompt bucket needs "
            f"{self.kv_pool.blocks_for(self._bucket_for(req.prompt.shape[0]))} "
            f"KV blocks but only {self.kv_pool.capacity()} are usable "
            "(PDTPU_FAULT_KV_EXHAUST or an undersized pool) — the "
            "request can never admit")

    def _admission_gate(self, req: Request) -> bool:
        """Paged admission is block-aware backpressure: a request stays
        queued until the pool can hold its prompt's bucket (the decode
        growth is handled per tick by ensure/preempt).  Runs parked on
        pool pressure hold FIRST claim on freed capacity — their resume
        blocks are RESERVED, and new work only admits from the surplus
        (work-conserving: a small request may still fill an idle slot,
        but never at the price of starving a parked run).  With a prefix
        cache the gate counts reusable blocks as free-for-this-request:
        a warm prefix only charges the pool for its uncached suffix."""
        reserve = (self.kv_pool.blocks_for(self._oom_paused[0].pos)
                   if self._oom_paused else 0)
        if self.prefix_cache is not None:
            plan = self._cached_plan(req)
            return self.kv_pool.free_blocks() >= plan.new_live + reserve
        bucket = self._bucket_for(req.prompt.shape[0])
        return (self.kv_pool.free_blocks()
                >= self.kv_pool.blocks_for(bucket) + reserve)

    # ------------------------------------------------------------------
    # prefix cache: share policy + admission planning
    # ------------------------------------------------------------------
    def _share_key(self, req: Request) -> str:
        """The cache partition this request may share KV with.  Default:
        tenant-private (anonymous requests form one 'default' group);
        gateway tenancy maps tenants into explicit share groups
        (TenantConfig.kv_share_group); an engine-level `share_policy`
        callable overrides both."""
        if self._share_policy is not None:
            return str(self._share_policy(req))
        tenant = req.tenant if req.tenant is not None else "default"
        return self._share_groups.get(tenant, tenant)

    def set_share_groups(self, groups: Dict[str, str]):
        """Tenant -> share-group mapping (gateway wiring)."""
        self._share_groups = dict(groups)

    def _cached_plan(self, req: Request, record: bool = False):
        """Host-side warm-admission plan: the longest usable cached
        chain, the dynamic `cached_len` the prefill program gets, the
        SUFFIX bucket, and the block cost.  Two invariants are enforced
        here rather than in-program: (1) `cached_len + bucket` never
        exceeds the gathered view width, so the model's write offset
        never clamps (a clamped write would land suffix KV over cached
        rows) — chains trim from the tail until it holds; (2) a fully
        block-aligned cached prompt recomputes its LAST token inside the
        final cached block, which is therefore COW'd to a private copy
        so shared blocks are never written."""
        plen = int(req.prompt.shape[0])
        bs = self.block_size
        view_rows = self.kv_pool.max_blocks_per_slot * bs
        chain = self.prefix_cache.match(self._share_key(req), req.prompt,
                                        record=record)

        def shape(chain):
            matched = len(chain) * bs
            cow = bool(chain) and matched == plen
            cached_len = plen - 1 if cow else matched
            return matched, cow, cached_len, self._bucket_for(
                plen - cached_len)

        matched, cow, cached_len, bucket = shape(chain)
        while chain and cached_len + bucket > view_rows:
            chain = chain[:-1]
            matched, cow, cached_len, bucket = shape(chain)
        total_blocks = min(self.kv_pool.blocks_for(cached_len + bucket),
                           self.kv_pool.max_blocks_per_slot)
        revive = sum(1 for b in chain
                     if self.kv_pool.block_ref(b) == 0)
        new_live = (max(0, total_blocks - len(chain))
                    + (1 if cow else 0) + revive)
        return _CachedPlan(chain, matched, cow, cached_len, bucket,
                           new_live)

    def _sweep(self):
        for slot in list(self._slots):
            run = self._slots[slot]
            if run.resp.cancelled:
                stat_add("STAT_serving_cancelled")
                run.resp._fail(RequestCancelled(
                    f"request {run.req.id} cancelled mid-decode"))
                self._release(slot)
            else:
                self._expired(slot, run)

    def _expired(self, slot: int, run: _SlotRun) -> bool:
        """Fail and release a seated run whose deadline has passed."""
        deadline = run.req.deadline
        if deadline is None or not deadline.expired():
            return False
        stat_add("STAT_serving_deadline_expired")
        run.resp._fail(DeadlineExceededError(
            f"request {run.req.id} deadline ({deadline.seconds}s) expired "
            "mid-decode"))
        self._release(slot)
        return True

    def _release(self, slot: int):
        run = self._slots.pop(slot, None)
        self.scheduler.release(slot)
        if self.kv == "paged":
            # blocks return to the free-list; their content is scrubbed
            # in-program the moment they are re-served (kv_pool docstring)
            self.kv_pool.free(slot)
        if (self._lora_reg is not None and run is not None
                and run.aid):
            # unpin: a ref-0 adapter becomes evictable again
            self._lora_reg.release(run.aid)
        self._batch_dirty = True

    def _bucket_for(self, plen: int) -> int:
        for b in self.buckets:
            if b >= plen:
                return b
        raise InvalidArgumentError(f"no bucket fits prompt length {plen}")

    def _request_key(self, req: Request) -> np.ndarray:
        # any well-mixed bits work as a raw PRNG key; host-only derivation
        # keeps submit()/admission free of device round-trips
        rs = np.random.RandomState(np.uint32(req.seed))
        return rs.randint(0, 2 ** 32, size=self._key_width, dtype=np.uint64
                          ).astype(np.uint32)

    def _admit(self, req: Request, resp: Response, slot: int):
        """One admission: the bucketed prefill enqueued, its first token
        pulled (the pull blocks on the chip), the run seated in its slot."""
        plen = int(req.prompt.shape[0])
        with _span("serving_admit", args={"request": req.id, "slot": slot,
                                          "plen": plen}) as sp:
            with _span("serving_prefill_dispatch"):
                called = (self._prefill_cached(req, resp, slot, sp.args)
                          if self.prefix_cache is not None
                          else self._prefill(req, resp, slot, sp.args))
            if called is None:  # failed terminally before any program ran
                return
            out, key, aid = called
            stat_add("STAT_serving_prefills")
            with _span("serving_prefill_wait"):
                ok = bool(out["finite"])
                if ok:
                    tok = int(out["tok"])
                if self._batched:   # the program has ended: no new wait
                    self._count_model(sp.args, np.asarray(out["counts"]))
            if not ok:
                # the run is not in _slots yet — _release won't see the
                # pin, drop it here
                if self._lora_reg is not None and aid:
                    self._lora_reg.release(aid)
                self._fail_slot(slot, resp, "prefill")
                return
            if self.prefix_cache is not None:
                self.prefix_cache.insert(
                    self._share_key(req), req.prompt,
                    self.kv_pool.block_ids(slot)[:plen // self.block_size])
            run = _SlotRun(req, resp, pos=plen, first_token=tok, key=key,
                           aid=aid)
            self._slots[slot] = run
            self._batch_dirty = True
            self._emit(run, tok, float(out["logp"]))
            self._count_tokens(1)
            self._maybe_finish(slot, run, tok)

    def _prefill_inputs(self, req: Request, key, bucket: int,
                        slot: Optional[int], aid: int = 0,
                        cached_len: int = 0) -> Dict:
        """A prefill call's `inputs` — the one place they are assembled,
        for a live admission, for warm-up and for the program-set
        exporter (`slot=None`: the cache view's sentinel form, so the
        call writes nothing that lasts).  The prompt past `cached_len`
        is right-padded to the bucket; `aid` and `cached_len` exist only
        on an engine with adapters / a prefix cache, as ordinary dynamic
        inputs: a new adapter or a warm prefix NEVER means a new
        program."""
        plen = int(req.prompt.shape[0])
        ids = np.full((1, bucket), self.pad_token_id, np.int32)
        ids[0, :plen - cached_len] = req.prompt[cached_len:]
        inputs = {"ids": jnp.asarray(ids), "prompt_len": jnp.int32(plen),
                  "key": jnp.asarray(key),
                  "temp": jnp.float32(req.temperature),
                  "top_k": jnp.int32(req.top_k),
                  "top_p": jnp.float32(req.top_p),
                  "greedy": jnp.asarray(req.greedy),
                  **self._cache.prompt_inputs(slot)}
        if self.lora is not None:
            inputs["aid"] = jnp.int32(aid)
        if self.prefix_cache is not None:
            inputs["cached_len"] = jnp.int32(cached_len)
        return inputs

    def _call_prefill(self, req: Request, bucket: int, slot: int,
                      aid: int = 0, cached_len: int = 0):
        """Enqueue the bucket's prefill program for the request.
        -> (the program's result, still on the device; the request's key;
        its adapter id)."""
        key = self._request_key(req)
        out = self._run(self._prefill_fns[bucket], self._prefill_inputs(
            req, key, bucket, slot, aid, cached_len))
        return out, key, aid

    def _prefill(self, req: Request, resp: Response, slot: int,
                 span_args: dict):
        """Host preparation and the enqueue of the cold prefill program
        (`_call_prefill`'s result), or None when the request failed
        terminally before the call."""
        bucket = span_args["bucket"] = self._bucket_for(req.prompt.shape[0])
        aid = 0
        if self.lora is not None:
            # resolve + PIN the adapter for the life of the slot (the
            # registry cannot evict a pinned adapter).  The request
            # was validated at make_request, but the adapter may have
            # been evicted while it queued — typed terminal failure,
            # never a hung consumer.
            try:
                aid = self._lora_reg.acquire(req.adapter)
            except Exception as e:
                stat_add("STAT_lora_rejects")
                with self._m_lock:
                    self._errored += 1
                resp._fail(e)
                self.scheduler.release(slot)
                return None
        # a block pool: claim the prompt's blocks; only reachable without
        # them when PDTPU_FAULT_KV_EXHAUST moved the cap between the
        # admission gate and here — typed terminal, never a hang
        if self.kv_pool is not None and not self.kv_pool.alloc(slot, bucket):
            if self._lora_reg is not None and aid:
                self._lora_reg.release(aid)
            return self._fail_exhausted(req, resp, slot, "admission")
        return self._call_prefill(req, bucket, slot, aid)

    def _fail_exhausted(self, req: Request, resp: Response, slot: int,
                        stage: str):
        """The block pool could not seat an admitted request: typed
        terminal failure, the scheduler's slot given back."""
        stat_add("STAT_serving_kv_exhausted")
        with self._m_lock:
            self._errored += 1
        resp._fail(KVPoolExhaustedError(
            f"request {req.id}: KV block pool exhausted at {stage} "
            f"({self.kv_pool.free_blocks()} free of "
            f"{self.kv_pool.capacity()} usable)"))
        self.scheduler.release(slot)

    def _prefill_cached(self, req: Request, resp: Response, slot: int,
                        span_args: dict):
        """Warm-path `_prefill`: adopt the longest cached prefix chain
        into the slot's table, COW the final block when the whole prompt
        is cached, and prefill ONLY the uncached suffix (per-slot
        dynamic `cached_len` into the same per-bucket program family —
        `cached_len == 0` IS the cold path, so a miss costs nothing
        extra and the compile bound is unchanged).  Buckets are chosen
        by SUFFIX length, so a warm prefix pays a near-zero prefill."""
        plan = self._cached_plan(req, record=True)
        span_args["bucket"] = plan.bucket

        def exhausted(stage):
            return self._fail_exhausted(req, resp, slot,
                                        f"admission/{stage}")

        if plan.chain and not self.kv_pool.adopt(slot, plan.chain):
            return exhausted("adopt")
        if plan.cow:
            pair = self.kv_pool.cow_last(slot)
            if pair is None:
                self.kv_pool.free(slot)
                return exhausted("cow")
            src, dst = pair
            # device copy BEFORE any program can write the new block
            self._pools = self._cow_fn(self._pools, jnp.int32(src),
                                       jnp.int32(dst))
            self.prefix_cache.note_cow()
        if not self.kv_pool.ensure(slot, plan.cached_len + plan.bucket):
            self.kv_pool.free(slot)
            return exhausted("suffix")
        return self._call_prefill(req, plan.bucket, slot,
                                  cached_len=plan.cached_len)

    # ------------------------------------------------------------------
    # gateway admission: direct placement, preemption, restore
    # ------------------------------------------------------------------
    def try_admit(self, req: Request, resp: Response) -> bool:
        """Place the request into a free slot NOW (one bucketed prefill),
        bypassing the FIFO queue — the gateway's admission path, which
        keeps its own priority lanes and only hands a request over once a
        slot is actually available.  Returns False when every slot is
        occupied (or, paged, when the block pool cannot hold the prompt —
        the gateway retries as the pool drains).  Must be called from the
        thread driving step() (the engine loop is single-threaded by
        design)."""
        if self.kv == "paged" and not self._admission_gate(req):
            return False
        slot = self.scheduler.acquire(req, resp)
        if slot is None:
            return False
        self._admit(req, resp, slot)
        return True

    def preempt_slot(self, slot: int) -> PreemptedRun:
        """Evict the run occupying `slot`, snapshotting its live KV rows +
        sampling state to host, and free the slot.  The response stream
        stays OPEN (paused); `restore_run` later continues it bit-identical
        to an uninterrupted run.

        Zero new compiled programs: the snapshot is a plain
        `jax.device_get` of the pool (host copy, same donation-safe move
        the async checkpointer's snapshot phase makes) and the row slices
        are numpy.  Known cost: the transfer is O(pool), not O(victim
        rows) — free on CPU (aliased memory), two full-pool copies per
        preempt/restore pair on an accelerator (four on a speculative
        engine, whose draft pool rides along); a device-side row
        gather/scatter would shrink it at the price of extra compiled
        programs — and slicing `[slot, :pos]` before the device_get
        would compile one tiny gather per distinct pos, which is worse.
        Must be called between engine steps from the driving thread."""
        self._refuse_batched("preempt_slot")
        run = self._slots.get(slot)
        if run is None:
            raise InvalidArgumentError(f"slot {slot} holds no active run")
        if self.kv == "paged":
            # the snapshot format is IDENTICAL to the fixed engine's —
            # per-layer (pos, ...) row arrays — so PreemptedRun stays
            # pool-layout-agnostic and a run preempted paged restores
            # through the same restore_run contract.  Unlike the fixed
            # path's documented O(pool) device_get, this moves only the
            # slot's OWN blocks: paged OOM backpressure preempts
            # routinely, so the snapshot gathers ids on device first and
            # pulls O(slot blocks) to host (one cached eager gather per
            # distinct block count, bounded by max_blocks_per_slot)
            ids = np.asarray(self.kv_pool.block_ids(slot), np.int32)
            ids_dev = jnp.asarray(ids) if ids.size else None

            def rows_of(leaf):
                if ids_dev is None:
                    return np.zeros((0,) + tuple(leaf.shape[2:]),
                                    leaf.dtype)
                r = np.asarray(jax.device_get(
                    jnp.take(leaf, ids_dev, axis=0)))
                return np.array(r.reshape((-1,) + r.shape[2:])[:run.pos])

            def snapshot(pools):
                return [(rows_of(k), rows_of(v)) for k, v in pools]
        else:
            def snapshot(pools):
                return [(np.array(k[slot, :run.pos]),
                         np.array(v[slot, :run.pos]))
                        for k, v in jax.device_get(pools)]
        paused = PreemptedRun(
            run, snapshot(self._pools),
            snapshot(self._draft_pools) if self.draft_model is not None
            else None)
        from .transfer import engine_config_hash
        paused.source_config_hash = engine_config_hash(self)
        run.req.preempts += 1
        # the slot, its blocks and the adapter's pin (unpinned while
        # parked: the adapter NAME travels with the request; restore
        # re-resolves, and may fail typed if it was evicted meanwhile)
        self._release(slot)
        stat_add("STAT_serving_preemptions")
        return paused

    def restore_run(self, paused: PreemptedRun) -> bool:
        """Resume a preempted run into any free slot: the saved KV rows are
        written back into the pool (host-side copy + upload — no compiled
        program) and decode continues from the saved position with the
        saved RNG key, so the remaining stream is bit-identical to a run
        that was never preempted.  Returns False when no slot is free —
        or, paged, when the block pool cannot hold the saved rows yet
        (the caller retries as it drains)."""
        self._refuse_batched("restore_run")
        slot = self.scheduler.acquire(paused.req, paused.resp)
        if slot is None:
            return False
        if self.kv == "paged":
            if self.prefix_cache is not None:
                if not self._restore_paged_prefix(slot, paused):
                    self.scheduler.release(slot)
                    return False
                return self._finish_restore(slot, paused)
            if not self.kv_pool.alloc(slot, paused.pos):
                self.scheduler.release(slot)
                return False
            upload = self._paged_upload
        else:
            upload = self._fixed_upload
        self._pools = upload(self._pools, slot, paused.kv_rows, paused.pos)
        if self.draft_model is not None and paused.draft_kv_rows is not None:
            self._draft_pools = upload(self._draft_pools, slot,
                                       paused.draft_kv_rows, paused.pos)
        return self._finish_restore(slot, paused)

    def _fixed_upload(self, pools, slot: int, rows, pos: int):
        """A fixed pool with a snapshot's rows [0, pos) written back into
        `slot`."""
        new_pools = []
        for (hk, hv), (rk, rv) in zip(jax.device_get(pools), rows):
            # device_get may alias backend memory on CPU: copy before
            # the in-place row write, then re-upload (rows beyond
            # `pos` may hold garbage from the slot's idle decode
            # passes — the model protocol guarantees positions > pos
            # never influence output, and decode overwrites them as
            # it advances)
            hk = np.array(hk)
            hv = np.array(hv)
            hk[slot, :pos] = rk
            hv[slot, :pos] = rv
            nk, nv = jnp.asarray(hk), jnp.asarray(hv)
            if self._kv_put is not None:
                # mesh engines must re-place the uploaded pool with
                # its heads sharding — a default-device array here
                # would silently de-shard the pool and retrace the
                # decode program on the next call
                nk, nv = self._kv_put(nk), self._kv_put(nv)
            new_pools.append((nk, nv))
        return new_pools

    def _restore_paged_prefix(self, slot: int, paused: PreemptedRun) -> bool:
        """Re-pin a restored run's shared prefix instead of re-uploading
        it: re-match the prompt against the LOCAL cache (the run may
        have migrated from another replica, or its blocks may have been
        evicted while parked), adopt whatever chain is still resident,
        and upload only the snapshot rows past it.  A fully cached
        prompt drops its last chain block — the prefill recomputed that
        block's final row in a private COW copy which was freed with the
        slot, so its snapshot rows upload into a fresh block instead —
        preserving the never-write-shared-blocks invariant.  On failure
        nothing is held (the caller releases the scheduler slot)."""
        req = paused.req
        plen = int(req.prompt.shape[0])
        bs = self.block_size
        chain = self.prefix_cache.match(self._share_key(req), req.prompt)
        if chain and len(chain) * bs >= plen:
            chain = chain[:-1]
        if chain and not self.kv_pool.adopt(slot, chain):
            return False
        if not self.kv_pool.ensure(slot, paused.pos):
            self.kv_pool.free(slot)
            return False
        shared_rows = len(chain) * bs
        self._pools = self._paged_upload(self._pools, slot,
                                         paused.kv_rows, paused.pos,
                                         start_row=shared_rows)
        return True

    def _finish_restore(self, slot: int, paused: PreemptedRun) -> bool:
        """Resume bookkeeping shared by both KV layouts: one copy, so a
        future lifecycle counter cannot diverge between them."""
        aid = 0
        if self.lora is not None:
            # the pin was dropped at preempt; re-resolve by NAME against
            # THIS engine's registry (the run may have migrated).  An
            # adapter evicted/never-loaded here is a typed terminal
            # failure — returning True because the paused run is
            # consumed, not parked for retry.
            try:
                aid = self._lora_reg.acquire(paused.req.adapter)
            except Exception as e:
                stat_add("STAT_lora_rejects")
                with self._m_lock:
                    self._errored += 1
                paused.resp._fail(e)
                self._release(slot)
                return True
        run = _SlotRun(paused.req, paused.resp, pos=paused.pos,
                       first_token=paused.last_token, key=paused.key,
                       aid=aid)
        run.produced = paused.produced
        paused.req.resumes += 1
        paused.req.paused_seconds += time.monotonic() - paused.preempted_at
        self._slots[slot] = run
        self._batch_dirty = True
        stat_add("STAT_serving_resumes")
        return True

    def _paged_upload(self, pools, slot: int, rows, pos: int,
                      start_row: int = 0):
        """Publish snapshot rows into the slot's freshly allocated blocks
        (host build + one eager scatter per leaf; block tails past `pos`
        zero-filled, so the upload is also the scrub).  `start_row`
        (block-aligned) skips leading rows whose blocks were ADOPTED
        from the prefix cache — their device content is already the
        snapshot's, and a shared block must never be written."""
        bs = self.block_size
        skip = start_row // bs
        ids_np = np.asarray(self.kv_pool.block_ids(slot), np.int32)[skip:]
        nb_used = int(ids_np.shape[0])
        if nb_used == 0:
            return pools
        ids = jnp.asarray(ids_np)
        new_pools = []
        for (kp, vp), (rk, rv) in zip(pools, rows):
            def blocks_of(r, pool):
                buf = np.zeros((nb_used * bs,) + tuple(pool.shape[2:]),
                               pool.dtype)
                tail = r[start_row:]
                buf[:tail.shape[0]] = tail
                return jnp.asarray(
                    buf.reshape((nb_used, bs) + tuple(pool.shape[2:])))
            kp = kp.at[ids].set(blocks_of(rk, kp), mode="drop")
            vp = vp.at[ids].set(blocks_of(rv, vp), mode="drop")
            if self._kv_put is not None:
                kp, vp = self._kv_put(kp), self._kv_put(vp)
            new_pools.append((kp, vp))
        return new_pools

    # ------------------------------------------------------------------
    # paged block-pool pressure: ensure-or-preempt, park, resume
    # ------------------------------------------------------------------
    def _ensure_decode_blocks(self):
        """Before a paged tick: grow every active slot's table to cover
        the rows the compiled call may write.  A shortfall preempts the
        newest lowest-priority run (its blocks return to the pool and it
        parks host-side, resuming as the pool drains) — exhaustion is
        backpressure, not a crash.  Runs that can no longer fit at all,
        or overflow the parking budget, fail with the typed
        KVPoolExhaustedError."""
        for slot in sorted(self._slots):
            run = self._slots.get(slot)
            if run is None:
                continue
            target = self._oom_target(run.pos, run.req)
            guard = 0
            while (slot in self._slots
                   and not self.kv_pool.ensure(slot, target)):
                victim = self._pick_oom_victim(slot)
                if victim is None:
                    # nothing below the needy run to evict: park (or
                    # fail) the needy run itself
                    self._oom_evict(slot)
                    break
                self._oom_evict(victim)
                guard += 1
                if guard > self.max_slots + 2:
                    break  # defensive: cannot loop forever

    def _rows_needed(self, req: Request) -> int:
        """Pool rows that must be BACKED for every consumed token of the
        request: the final emitted token's logits come from in-program
        ctx, so backing ends at plen + max_new - 1; chunk-tail writes
        past it route through sentinel table entries and drop (their
        tokens are discarded by the host anyway)."""
        return min(self._pool_len,
                   int(req.prompt.shape[0]) + int(req.max_new_tokens) - 1)

    def _oom_target(self, pos: int, req: Request) -> int:
        """Rows the next tick actually requires for this run."""
        return min(pos + self._rows_per_tick,
                   max(self._rows_needed(req), pos))

    def _pick_oom_victim(self, needy_slot: int):
        """The NEWEST run in the LOWEST priority class at or below the
        needy run's priority (least progress lost, the PR-6 eviction
        intuition), excluding the needy slot itself."""
        needy = self._slots[needy_slot]
        best_slot, best_key = None, None
        for slot, run in self._slots.items():
            if slot == needy_slot:
                continue
            if run.req.priority > needy.req.priority:
                continue
            key = (run.req.priority, -run.req.id)
            if best_key is None or key < best_key:
                best_key, best_slot = key, slot
        return best_slot

    def _oom_evict(self, slot: int):
        run = self._slots.get(slot)
        if run is None:
            return
        if (len(self._oom_paused) >= self._max_oom_paused
                or not self.kv_pool.can_ever_fit(
                    self._oom_target(run.pos, run.req))):
            # parking would never end: the run's next tick cannot fit the
            # pool even ALONE (the fault cap or a tiny pool) — the typed
            # terminal state, not a silent hang
            self._oom_fail(slot, run)
            return
        paused = self.preempt_slot(slot)
        self._oom_paused.append(paused)
        self._oom_preempts += 1
        stat_add("STAT_serving_kv_oom_preempts")

    def _oom_fail(self, slot: int, run: "_SlotRun"):
        stat_add("STAT_serving_kv_exhausted")
        self._oom_failed += 1
        with self._m_lock:
            self._errored += 1
        run.resp._fail(KVPoolExhaustedError(
            f"request {run.req.id}: KV block pool exhausted mid-decode "
            f"({self.kv_pool.used_blocks()} used of "
            f"{self.kv_pool.capacity()} usable blocks) and the run can "
            "no longer be parked or resumed"))
        self._release(slot)

    def _sweep_oom_paused(self) -> bool:
        """Parked runs still honor cancel/deadline, and one that can no
        longer EVER fit (the fault cap shrank the pool under it) fails
        typed instead of waiting forever."""
        keep, changed = [], False
        for p in self._oom_paused:
            if p.resp.cancelled:
                stat_add("STAT_serving_cancelled")
                p.resp._fail(RequestCancelled(
                    f"request {p.req.id} cancelled while parked on KV "
                    "pool pressure"))
                changed = True
            elif p.req.deadline is not None and p.req.deadline.expired():
                stat_add("STAT_serving_deadline_expired")
                p.resp._fail(DeadlineExceededError(
                    f"request {p.req.id} deadline "
                    f"({p.req.deadline.seconds}s) expired while parked "
                    "on KV pool pressure"))
                changed = True
            elif not self.kv_pool.can_ever_fit(
                    self._oom_target(p.pos, p.req)):
                stat_add("STAT_serving_kv_exhausted")
                self._oom_failed += 1
                with self._m_lock:
                    self._errored += 1
                p.resp._fail(KVPoolExhaustedError(
                    f"request {p.req.id}: parked on KV pool pressure and "
                    f"the pool ({self.kv_pool.capacity()} usable blocks) "
                    "can no longer hold it at all"))
                changed = True
            else:
                keep.append(p)
        self._oom_paused = keep
        return changed

    def _restore_oom_paused(self) -> bool:
        did = False
        while self._oom_paused and self.scheduler.free_slot_count() > 0:
            if not self.restore_run(self._oom_paused[0]):
                break
            self._oom_paused.pop(0)
            stat_add("STAT_serving_kv_oom_resumes")
            did = True
        return did

    def _batch_inputs(self, slots: Dict[int, _SlotRun]) -> Dict:
        """The per-slot inputs of a decode or verify call for the runs
        seated in `slots`, uploaded: each run's last token, position and
        sampling knobs, and only where the program has them `aids`
        (idle slots decode as adapter 0), `active` (a batched model's
        mask of occupied slots) and `spec_on`."""
        s = self.max_slots
        tokens = np.zeros((s,), np.int32)
        pos = np.zeros((s,), np.int32)
        keys = np.zeros((s, self._key_width), np.uint32)
        temp = np.ones((s,), np.float32)
        top_k = np.zeros((s,), np.int32)
        top_p = np.ones((s,), np.float32)
        greedy = np.ones((s,), bool)
        poison = np.zeros((s,), bool)
        spec_on = np.zeros((s,), bool)
        aids = np.zeros((s,), np.int32)
        active = np.zeros((s,), bool)
        for slot, run in slots.items():
            tokens[slot] = run.last_token
            pos[slot] = run.pos
            keys[slot] = run.key
            temp[slot] = run.req.temperature
            top_k[slot] = run.req.top_k
            top_p[slot] = run.req.top_p
            greedy[slot] = run.req.greedy
            poison[slot] = run.req.poison
            spec_on[slot] = run.req.spec
            aids[slot] = run.aid
            active[slot] = True
        batch = {"tokens": tokens, "pos": pos, "keys": keys, "temp": temp,
                 "top_k": top_k, "top_p": top_p, "greedy": greedy,
                 "poison": poison}
        if self.lora is not None:
            batch["aids"] = aids
        if self._batched:
            batch["active"] = active
        if self.draft_model is not None:
            batch["spec_on"] = spec_on
        return {name: jnp.asarray(a) for name, a in batch.items()}

    def _rebuild_batch(self):
        """The device-resident decode batch, rebuilt from host _SlotRun
        state only when membership changes (admission / slot release)."""
        self._dev = self._batch_inputs(self._slots)
        self._batch_dirty = False

    def _decode_inputs(self, batch: Dict, slots, diverge: bool = False):
        """A decode (or verify) call's `inputs` — the one place they are
        assembled: the device-resident `batch`, what the cache view adds
        for the runs in `slots` (nothing, or block tables and the
        occupancy mask) and a verify tick's fault flag.  The live tick
        hands it the resident batch; warm-up and the program-set exporter
        a batch over no runs, for which the view gives its sentinel
        forms."""
        inputs = dict(batch, **self._cache.batch_inputs(slots))
        if self.draft_model is not None:
            inputs["diverge"] = jnp.asarray(diverge)
        return inputs

    def _decode_step(self):
        """One tick of the resident batch: the decode program's chunk of
        tokens a slot or, on a speculative engine, the verify program's
        K draft proposals + one batched target verify, committing 1..K+1
        tokens a slot."""
        spec = self.draft_model is not None
        with _span("serving_verify" if spec else "serving_decode", args={
                "active": len(self._slots),
                "calls": self._decode_calls + 1}) as sp:
            if self.kv_pool is not None:
                # grow block tables for this tick's writes (may preempt
                # or fail runs under pool pressure — membership can
                # change, so this runs before the batch rebuild)
                self._ensure_decode_blocks()
                if not self._slots:
                    return
                sp.args["active"] = len(self._slots)
            if self._batch_dirty:
                with _span("serving_batch_rebuild"):
                    self._rebuild_batch()
            with _span("serving_decode_dispatch"):
                tick_no = self._decode_calls  # lifetime stride counter:
                # the diverge fault keys off it, NOT _spec_ticks, which is
                # a metrics-window counter reset_metrics() zeroes
                # PDTPU_FAULT_SLOW_DECODE: host-side latency injection,
                # read live per call — overload/SLO-miss paths become
                # testable on CPU without a big model
                faults.maybe_slow_decode(tick_no)
                self._decode_calls += 1
                out = self._run(self._decode_fn, self._decode_inputs(
                    self._dev, self._slots,
                    spec and self._diverge_every is not None
                    and tick_no % self._diverge_every == 0))
                self._dev["tokens"], self._dev["pos"] = (out["tokens"],
                                                         out["pos"])
            with _span("serving_token_pull"):
                # one device->host pull for the whole call's burst (and a
                # batched model's routed counts, a tick's commits with it)
                host = jax.device_get({k: v for k, v in out.items()
                                       if k not in _RESIDENT})
            stat_add("STAT_serving_decode_steps")
            if spec:
                self._spec_ticks += 1
                stat_add("STAT_spec_ticks")
                self._count_accepts(host["accepts"], host["finites"])
                # a tick holds commits[slot] ready tokens a slot, and one
                # non-finite flag a slot covers them all
                self._deliver(
                    host["toks"], host["logps"],
                    np.where(host["finites"], host["commits"], 1),
                    np.broadcast_to(host["finites"][:, None],
                                    host["toks"].shape), "verify")
                return
            if self._batched:
                self._count_model(sp.args, host["counts"])
                self._gauge_kv_rows()
            # the chunk's (step, slot) burst, a slot a row
            self._deliver(host["toks"].T, host["logps"].T,
                          np.full(self.max_slots, host["toks"].shape[0]),
                          host["finites"].T, "decode")

    def _count_accepts(self, accepts, finites):
        """The accept-rate accounts of a verify tick, over the slots that
        speculate and came back finite."""
        proposed = accepted = 0
        for slot, run in self._slots.items():
            if run.req.spec and finites[slot]:
                proposed += self.spec_tokens
                accepted += int(accepts[slot])
                self._h_accept.observe(int(accepts[slot]) / self.spec_tokens)
        if proposed:
            stat_add("STAT_spec_proposed", proposed)
            stat_add("STAT_spec_accepted", accepted)
            with self._m_lock:
                self._spec_proposed += proposed
                self._spec_accepted += accepted

    def _deliver(self, toks, logps, ready, finite, phase: str):
        """Emit every seated slot's ready tokens (the first `ready[slot]`
        of row `slot`), fail a slot at its first non-finite step, finish
        or release — the one loop behind the decode chunk and the
        speculative tick."""
        with _span("serving_deliver") as deliver:
            emitted, seated = 0, len(self._slots)
            for slot in list(self._slots):
                run = self._slots[slot]
                for j in range(int(ready[slot])):
                    # deadline enforcement on the tick itself, not only at
                    # the next sweep: a call may hold several ready tokens
                    # a slot (a chunk, or a tick's K+1 commits), but a
                    # budget that expired while it was computing stops the
                    # stream here — no post-expiry token is delivered, the
                    # slot recycles now (regression: deadline shorter than
                    # one chunk / one speculative tick)
                    if self._expired(slot, run):
                        break
                    if not finite[slot, j]:
                        self._fail_slot(slot, run.resp, phase)
                        break
                    t = int(toks[slot, j])
                    run.pos += 1
                    run.produced += 1
                    run.last_token = t
                    self._emit(run, t, float(logps[slot, j]))
                    emitted += 1
                    self._maybe_finish(slot, run, t)
                    if slot not in self._slots:
                        # finished mid-call: the slot's later tokens are
                        # discarded (their KV garbage dies with the slot's
                        # next prefill)
                        break
            self._count_tokens(emitted)
            deliver.args = {"tokens": emitted,
                            "finished": seated - len(self._slots)}

    def _fail_slot(self, slot: int, resp: Response, phase: str):
        stat_add("STAT_serving_nonfinite")
        with self._m_lock:
            self._errored += 1
        resp._fail(NonFiniteLogitsError(
            f"request {resp.request.id}: non-finite logits during {phase}; "
            "slot recycled, engine keeps serving"))
        self._release(slot)

    def _emit(self, run: _SlotRun, tok: int, logp: float):
        now = time.perf_counter()
        first = run.resp.first_token_at is None
        run.resp._push_token(tok, logp)
        if first:
            self._h_ttft.observe(run.resp.ttft)
        else:
            self._h_itl.observe(now - run.last_token_at)
        run.last_token_at = now

    def _count_tokens(self, n: int):
        """Tokens delivered by one admission or one decode call: counted
        once a call, not under a lock a token."""
        if n:
            stat_add("STAT_serving_tokens", n)
            with self._m_lock:
                self._tokens_out += n

    def _maybe_finish(self, slot: int, run: _SlotRun, tok: int):
        eos = run.req.eos_token_id
        if eos is not None and tok == eos:
            reason = "eos"
        elif run.produced >= run.req.max_new_tokens:
            reason = "length"
        else:
            return
        with self._m_lock:
            self._completed += 1
        run.resp._finish(reason)
        self._release(slot)

    # ------------------------------------------------------------------
    # driving
    # ------------------------------------------------------------------
    def has_work(self) -> bool:
        return (bool(self._slots) or self.scheduler.has_work()
                or bool(self.kv == "paged" and self._oom_paused))

    def run_until_drained(self, timeout: Optional[float] = None):
        """Drive the loop in the caller's thread until queue and slots are
        empty (tests / batch jobs).  Not for use while start() is live."""
        t0 = time.monotonic()
        while self.has_work():
            self.step()
            if timeout is not None and time.monotonic() - t0 > timeout:
                raise TimeoutError("serving engine did not drain in "
                                   f"{timeout}s")

    def _abort_all(self, make_exc):
        """Fail every in-flight and queued request (engine death/close):
        a consumer blocked in Response.__iter__ / tokens() must get an
        error, never hang."""
        for slot, run in list(self._slots.items()):
            self._release(slot)
            run.resp._fail(make_exc(run.req))
        for req, resp in self.scheduler.drain_pending():
            resp._fail(make_exc(req))
        if self.kv == "paged":
            paused, self._oom_paused = self._oom_paused, []
            for p in paused:
                p.resp._fail(make_exc(p.req))
        self._batch_dirty = True

    def start(self):
        """Background engine loop (streaming servers / the probe)."""
        if self._thread is not None:
            return
        if self._closed:
            raise UnavailableError("serving engine is closed")
        self._stop.clear()

        def loop():
            while not self._stop.is_set():
                try:
                    did = self.step()
                except BaseException as e:  # noqa: BLE001 — must not hang
                    # the loop thread dying silently would leave every
                    # consumer blocked forever: record the cause, fail all
                    # outstanding requests, refuse new ones
                    self._dead = e
                    self._abort_all(lambda req: UnavailableError(
                        f"request {req.id} aborted: serving engine loop "
                        f"died: {e!r}"))
                    return
                if not did:
                    self._work.wait(0.002)
                    self._work.clear()

        self._thread = threading.Thread(target=loop, name="serving-engine",
                                        daemon=True)
        self._thread.start()

    def close(self):
        """Stop the loop and fail any still-outstanding requests (a
        Response consumer must never be left blocked on a closed
        engine).  Idempotent and safe under concurrent double-close: the
        flag flips before the lock so racing submitters reject early, and
        the join/abort sequence runs under _close_lock so a second closer
        can never join a half-torn-down thread or re-abort a drain in
        progress."""
        self._closed = True
        self._stop.set()
        self._work.set()
        with self._close_lock:
            if self._thread is not None:
                self._thread.join(timeout=5.0)
                self._thread = None
            self._abort_all(lambda req: RequestCancelled(
                f"request {req.id} aborted: serving engine closed"))

    @property
    def warm(self) -> bool:
        """True once warmup() has precompiled every program the engine
        will ever run — the gateway's /healthz readiness signal."""
        return self._warm

    # ------------------------------------------------------------------
    # program lifecycle: example args, warmup, AOT program sets
    # ------------------------------------------------------------------
    def _programs(self):
        """[(name, fn, inputs)]: every compiled program this engine
        configuration will ever run, each with the `inputs` of a call
        that touches no run — a one-token prompt through the cache view's
        sentinel slot, a batch over no runs — at the avals of a live
        call.  Warm-up and the program-set exporter both read it, so
        neither keeps a signature of its own."""
        probe = Request(0, [self.pad_token_id], 1, seed=0)
        programs = [(f"prefill_b{b}", self._prefill_fns[b],
                     self._prefill_inputs(probe, self._request_key(probe),
                                          b, None))
                    for b in self.buckets]
        programs.append(("decode", self._decode_fn,
                         self._decode_inputs(self._batch_inputs({}), {})))
        return programs

    def _program_family(self):
        """[(name, fn, example_args, donate_argnums)] for `_programs()`,
        with the CURRENT weights and pools — the unit the program store
        and AOT program sets operate on.  Names are layout-agnostic
        (`prefill_b{bucket}`, `decode`) so a paged artifact can never be
        confused with a fixed one except through the manifest, which
        records the layout explicitly.  The donation indices ride along
        because `jax.export` does not preserve donation — the program-set
        loader re-applies them (losing them silently would turn every
        tick into a full KV-pool copy)."""
        return [(name, fn, self._program_args(inputs), (1,))
                for name, fn, inputs in self._programs()]

    def warmup(self) -> Dict:
        """Compile every program the engine will ever run (one prefill per
        bucket + the decode/verify step — on speculative engines the
        verify program and the draft halves of each bucket prefill ride
        the same calls; a paged view routes the writes through the
        sentinel table) so no request pays a trace — the program-lifecycle
        warmup the gateway calls before admitting traffic.  After it
        returns, `post_warmup_compiles()` must stay 0 under ANY traffic
        mix — spec on/off, greedy/sampling, preempt/restore.

        Programs preloaded from an AOT program set in the native 'exe'
        representation are already compiled and are NOT executed here
        (their first execution is the first real request); 'stablehlo'
        programs and freshly traced ones are invoked once to force the
        compile now.  Safe any time no request is in flight.  Returns a
        report: per-program compile source + wall seconds + store stats."""
        from ..programs.program_set import LoadedProgram
        t0 = time.perf_counter()
        sources = {}
        for name, fn, inputs in self._programs():
            if isinstance(fn, LoadedProgram) and fn.kind == "exe":
                sources[name] = "program_set:exe"
                continue
            self._run(fn, inputs)
            sources[name] = ("program_set:stablehlo"
                             if isinstance(fn, LoadedProgram) else "traced")
        if self._cow_fn is not None:
            # precompile the COW block copy with the sentinel dst (mode=
            # "drop" makes it a no-op) so the first real COW pays no trace
            self._pools = self._cow_fn(self._pools, jnp.int32(0),
                                       jnp.int32(self.kv_pool.num_blocks))
            sources["cow_copy"] = "traced"
        self._warm = True
        self._warm_marks = self._compile_marks()
        report = {"seconds": time.perf_counter() - t0,
                  "programs": sources,
                  "compile_counts": self.compile_counts()}
        try:
            from ..programs.store import store_stats
            report["store"] = store_stats()
        except Exception:
            pass
        return report

    def _compile_marks(self) -> Dict:
        """Snapshot of every serving-compile counter: the engine's own
        trace counts AND the observability program registry (loaded
        program sets never touch the former; TrackedJit programs report
        to the latter)."""
        try:
            from ..observability import get_program_registry
            reg = {name: rec["compiles"] for name, rec
                   in get_program_registry().snapshot().items()
                   if name.startswith("serving_")}
        except Exception:
            reg = {}
        return {"engine": (self._compiles["decode"]
                           + sum(self._compiles["prefill"].values())),
                "registry": reg}

    def post_warmup_compiles(self) -> int:
        """Compiles observed since warmup() finished — the fleet
        contract is that this stays 0 under ANY traffic mix (probes and
        tier-1 assert it).  Counts both engine trace counters and new
        `serving_*` registry compiles; returns -1 if warmup never ran."""
        if self._warm_marks is None:
            return -1
        now = self._compile_marks()
        extra = now["engine"] - self._warm_marks["engine"]
        base = self._warm_marks["registry"]
        for name, compiles in now["registry"].items():
            extra += compiles - base.get(name, 0)
        return extra

    def save_program_set(self, path: str,
                         extra_meta: Optional[dict] = None) -> str:
        """Export this engine's whole program family (+ config manifest)
        as one artifact loadable by ``ServingEngine(program_set=...)`` /
        ``enable_serving(program_set=...)`` — see
        paddle_tpu/programs/program_set.py.  Call after `warmup()` to
        reuse the already-compiled executables (saving then compiles
        nothing)."""
        from ..programs.program_set import save_program_set as _save
        return _save(self, path, extra_meta)

    # ------------------------------------------------------------------
    # observability
    # ------------------------------------------------------------------
    def compile_counts(self) -> Dict:
        """Traced-program counts: the ≤ len(buckets) + 1 guarantee.  For
        speculative engines the same bound holds — "decode" counts the one
        verify program (draft proposal scan + batched target verify +
        in-program commit) and each per-bucket prefill program covers
        target AND draft prefill, so spec on/off × greedy/sampling traffic
        never adds a program."""
        return {"decode": self._compiles["decode"],
                "prefill": dict(self._compiles["prefill"]),
                "total": (self._compiles["decode"]
                          + sum(self._compiles["prefill"].values())),
                "bound": len(self.buckets) + 1}

    def adapter_shas(self) -> Optional[Dict[str, str]]:
        """name -> artifact sha of every resident LoRA adapter, or None
        on a no-LoRA engine.  Cheaper than metrics(): fleet health
        snapshots call this per replica per tick."""
        if self._lora_reg is None:
            return None
        return self._lora_reg.shas() or None

    def metrics(self) -> Dict:
        """Serving metrics snapshot (also published as STAT_serving_*
        monitor counters and in the predictor's profile report)."""
        # the two latency figures come from the registry's histograms, the
        # one account `_emit` keeps: a bucket quantile and a mean over
        # every engine of the process since the registry was last reset
        p50 = self._h_ttft.quantile(0.5)
        gaps = self._h_itl.snapshot()
        itl = gaps["sum"] / gaps["count"] if gaps["count"] else None
        with self._m_lock:
            elapsed = time.monotonic() - self._started_at
            return {
                "requests_completed": self._completed,
                "requests_errored": self._errored,
                "tokens_out": self._tokens_out,
                "tokens_per_sec": (self._tokens_out / elapsed
                                   if elapsed > 0 else 0.0),
                "ttft_p50_ms": None if p50 is None else p50 * 1e3,
                "inter_token_ms": None if itl is None else itl * 1e3,
                "queue_depth": self.scheduler.queue_depth(),
                "slot_occupancy": self.scheduler.occupancy(),
                "max_slots": self.max_slots,
                "compile_counts": self.compile_counts(),
                "spec": self._spec_metrics(),
                "warm": self._warm,
                "post_warmup_compiles": (self.post_warmup_compiles()
                                         if self._warm else None),
                "program_set": self.program_set_info,
                "kv_pool": self._kv_pool_metrics(),
                "lora": (None if self._lora_reg is None
                         else self._lora_reg.stats()),
                "mesh": (None if self.mesh is None else {
                    "devices": int(self.mesh.devices.size),
                    "tp": int(self.mesh.shape.get("tp", 1))}),
            }

    def _kv_pool_metrics(self):
        if self.kv != "paged":
            return {"kind": "fixed", "max_slots": self.max_slots,
                    "pool_len": self._pool_len}
        out = {"kind": "paged", **self.kv_pool.stats(),
               "oom_preempts": self._oom_preempts,
               "oom_failed": self._oom_failed,
               "oom_paused": len(self._oom_paused)}
        if self.prefix_cache is not None:
            out["prefix_cache"] = self.prefix_cache.stats()
        return out

    def _spec_metrics(self):
        if self.draft_model is None:
            return {"enabled": False}
        return {
            "enabled": True,
            "spec_tokens": self.spec_tokens,
            "ticks": self._spec_ticks,
            "proposed": self._spec_proposed,
            "accepted": self._spec_accepted,
            "accept_rate": (self._spec_accepted / self._spec_proposed
                            if self._spec_proposed else None),
        }

    def reset_metrics(self):
        with self._m_lock:
            self._tokens_out = 0
            self._completed = 0
            self._errored = 0
            self._started_at = time.monotonic()
            if self.draft_model is not None:
                self._spec_ticks = 0
                self._spec_proposed = 0
                self._spec_accepted = 0

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
