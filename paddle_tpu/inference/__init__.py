"""paddle.inference — Config / Predictor deployment API.

Reference: paddle/fluid/inference/api/paddle_inference_api.h
(AnalysisConfig + AnalysisPredictor + ZeroCopyTensor): configure a saved
model, create a predictor, feed named input handles, run, read named
output handles.

TPU-native: the "engine" is the exported StableHLO program saved by
`paddle.jit.save` — deserialized once and executed by the JAX runtime.
The reference's pass/optimization knobs (ir_optim, memory_optim, mkldnn,
TensorRT) are accepted for API compatibility and recorded, but they are
subsumed by XLA compilation: there is no separate pass pipeline to
toggle.  `enable_profile` wires the paddle_tpu profiler around `run()`.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import numpy as np

__all__ = ["Config", "Predictor", "PredictorTensor", "ServingPredictor",
           "create_predictor"]


class Config:
    """AnalysisConfig equivalent (reference: paddle_inference_api.h)."""

    def __init__(self, model_path: Optional[str] = None,
                 params_path: Optional[str] = None):
        # paddle convention: prog file "model.pdmodel" + "model.pdiparams";
        # here one artifact prefix covers both (jit.save layout)
        self._prefix = None
        if model_path is not None:
            self.set_model(model_path, params_path)
        self._ir_optim = True
        self._memory_optim = False
        self._profile = False
        self._device = "tpu"
        self._threads = 1
        self._serving = None
        self._recsys = None

    # -- model ----------------------------------------------------------------
    def set_model(self, model_path: str, params_path: Optional[str] = None):
        for suffix in (".pdmodel", ".pdiparams", ".pdiparams.npz"):
            if model_path.endswith(suffix):
                model_path = model_path[:-len(suffix)]
                break
        if params_path is not None:
            # jit.save artifacts keep program+weights under one prefix; a
            # divergent params location cannot be honored — fail loudly
            # instead of silently loading from a path the user never gave
            expect = model_path + ".pdiparams"
            stripped = params_path[:-4] if params_path.endswith(".npz") \
                else params_path
            if stripped != expect:
                raise ValueError(
                    f"params_path {params_path!r} disagrees with the "
                    f"artifact prefix {model_path!r} (expected "
                    f"{expect}[.npz]); paddle_tpu artifacts store weights "
                    "next to the program")
        self._prefix = model_path

    def model_dir(self):
        return self._prefix

    # -- device ---------------------------------------------------------------
    def enable_use_gpu(self, memory_pool_init_size_mb=100, device_id=0):
        self._device = "gpu"  # recorded; execution uses the JAX backend

    def disable_gpu(self):
        self._device = "cpu"

    def enable_xpu(self, *a, **k):
        self._device = "xpu"

    def use_gpu(self):
        return self._device == "gpu"

    def set_cpu_math_library_num_threads(self, n: int):
        self._threads = int(n)

    # -- optimization knobs (XLA-subsumed, recorded for compat) --------------
    def switch_ir_optim(self, flag: bool = True):
        self._ir_optim = bool(flag)

    def ir_optim(self):
        return self._ir_optim

    def enable_memory_optim(self, flag: bool = True):
        self._memory_optim = bool(flag)

    def memory_optim_enabled(self):
        return self._memory_optim

    def enable_tensorrt_engine(self, *a, **k):
        pass  # XLA is the backend; accepted for API compat

    def enable_mkldnn(self):
        pass

    def switch_use_feed_fetch_ops(self, flag=False):
        pass

    def switch_specify_input_names(self, flag=True):
        pass

    # -- serving mode ---------------------------------------------------------
    def enable_serving(self, model=None, model_provider=None, **engine_opts):
        """Switch create_predictor() to the continuous-batching
        ServingEngine (paddle_tpu.serving).

        Exactly one of:
          model           an in-memory Layer implementing the
                          gen_fixed_cache/forward_fixed protocol
          model_provider  zero-arg callable building such a Layer; its
                          weights are then restored from this Config's
                          jit.save artifact (`<prefix>.pdiparams.npz`) —
                          the serving analogue of loading a saved model

        engine_opts pass through to ServingEngine (max_slots, max_len,
        prefill_buckets, max_queue_depth, pad_token_id, dtype,
        draft_model, spec_tokens, and the distributed-serving knobs:
        kv="paged" + block_size/num_blocks for the block-granular KV
        pool, mesh= for the tensor-parallel engine — see the README
        "Distributed serving" section).

        `quantize="int8"` converts the model (and the draft model, when
        one is configured) with `quantization.quantize_for_serving`
        before the engine is built: int8 weight-only Linears with
        per-channel fp scales, dequantized at use inside the UNCHANGED
        serving programs — no new compiled programs beyond the quantized
        set, half/quarter the weight HBM per decode step.

        `draft_model=` (a smaller Layer speaking the same fixed-cache
        protocol) turns on speculative decoding: the draft proposes
        `spec_tokens` tokens per tick and the target verifies them in one
        batched forward; greedy streams stay bit-identical to solo
        generate.  See the README "Speculative + quantized decoding"
        section.

        `gateway=` additionally fronts the engine with the multi-tenant
        SLO-aware ServingGateway (per-tenant rate limits + weighted
        fairness, priority preemption with KV save/restore, load
        shedding, OpenAI-shaped HTTP endpoint).  Pass True for defaults,
        or a dict of ServingGateway kwargs (tenants=, shed=, preempt=,
        model_name=, ...).  The predictor then routes submit() through
        the gateway (tenant=/priority= become available) and the gateway
        drives the engine loop.

        Program lifecycle (README "Program lifecycle"):
        `program_cache_dir=` enables the persistent program store for
        this process (same as PDTPU_PROGRAM_CACHE_DIR) so every compile
        — eager dispatch, warmup, serving programs — reads/writes the
        shared on-disk cache.  `program_set=` boots the engine from an
        AOT program-set artifact (`predictor.save_program_set(path)` /
        `ServingEngine.save_program_set`) WITHOUT retracing any model
        code; a stale or corrupt artifact is rejected with a warning
        (counted as ``program_set_fallback_total``) and the engine falls
        back to a fresh trace+compile — never silent reuse.
        """
        if (model is None) == (model_provider is None):
            raise ValueError(
                "enable_serving needs exactly one of model= (in-memory) or "
                "model_provider= (architecture factory for a saved "
                "artifact)")
        self._serving = {"model": model, "model_provider": model_provider,
                         **engine_opts}

    def serving_enabled(self) -> bool:
        return self._serving is not None

    def enable_recsys_serving(self, model=None, table=None, offsets=None,
                              **opts):
        """Switch create_predictor() to the batched deduped-lookup recsys
        scorer (embedding.RecsysPredictor).

        model    an external-embedding-mode Layer (e.g. models.DLRM with
                 embedding="external"): forward(dense, emb_rows)
        table    the row store — embedding.HostEmbeddingTable or a raw
                 (rows, dim) ndarray (host-resident: bigger than device
                 memory is the point)
        offsets  per-feature offsets into the concatenated table
                 (models.DLRMConfig.offsets)

        opts pass through to RecsysPredictor (max_batch, window_ms,
        max_queue, slab_bucket).  Concurrent submit()s are merged into one
        forward with ONE id-dedup + row fetch across all of them; a full
        queue rejects with a typed terminal response — the PR-6 gateway's
        admission contract applied to scoring traffic.
        """
        if model is None or table is None:
            raise ValueError(
                "enable_recsys_serving needs model= (external-embedding "
                "Layer) and table= (HostEmbeddingTable or ndarray)")
        self._recsys = {"model": model, "table": table, "offsets": offsets,
                        **opts}

    def recsys_enabled(self) -> bool:
        return self._recsys is not None

    # -- profiling ------------------------------------------------------------
    def enable_profile(self):
        self._profile = True

    def summary(self) -> str:
        return (f"Config(model={self._prefix!r}, device={self._device}, "
                f"ir_optim={self._ir_optim}, "
                f"memory_optim={self._memory_optim}, "
                f"threads={self._threads}, "
                f"serving={self.serving_enabled()})")


class PredictorTensor:
    """ZeroCopyTensor equivalent: a named input/output slot."""

    def __init__(self, name: str, shape=None, dtype=None):
        self.name = name
        self._shape = tuple(shape) if shape is not None else None
        self._dtype = dtype
        self._value: Optional[np.ndarray] = None

    def copy_from_cpu(self, arr: np.ndarray):
        arr = np.asarray(arr)
        if self._dtype is not None:
            arr = arr.astype(self._dtype, copy=False)
        self._value = arr

    def copy_to_cpu(self) -> np.ndarray:
        if self._value is None:
            raise RuntimeError(f"tensor {self.name!r} has no value yet "
                               "(run() first)")
        return np.asarray(self._value)

    def reshape(self, shape):
        self._shape = tuple(shape)

    def shape(self):
        if self._value is not None:
            return list(self._value.shape)
        return list(self._shape) if self._shape else []


class Predictor:
    """AnalysisPredictor equivalent over a jit.save artifact."""

    def __init__(self, config: Config):
        from ..jit import load as jit_load
        if config.model_dir() is None:
            raise ValueError("Config has no model path (set_model)")
        self._config = config
        self._layer = jit_load(config.model_dir())
        specs = (self._layer._meta or {}).get("input_spec") or []
        if not specs:
            raise RuntimeError(
                "artifact has no input_spec metadata; re-export it with "
                "paddle.jit.save(..., input_spec=[...])")
        self._inputs: Dict[str, PredictorTensor] = {
            f"x{i}": PredictorTensor(f"x{i}", shape, dtype)
            for i, (shape, dtype) in enumerate(specs)}
        self._outputs: Dict[str, PredictorTensor] = {}

    def get_input_names(self) -> List[str]:
        return list(self._inputs)

    def get_input_handle(self, name: str) -> PredictorTensor:
        return self._inputs[name]

    def run(self) -> bool:
        from ..utils.monitor import stat_add
        stat_add("STAT_predictor_runs")
        args = []
        for name, t in self._inputs.items():
            if t._value is None:
                raise RuntimeError(f"input {name!r} not set")
            args.append(t._value)
        prof = None
        if self._config._profile:
            from ..utils import profiler
            prof = profiler.RecordEvent("predictor_run")
            prof.__enter__()
        try:
            out = self._layer(*args)
        finally:
            if prof is not None:
                prof.__exit__(None, None, None)
        leaves = jax.tree_util.tree_leaves(out)
        self._outputs = {}
        for i, leaf in enumerate(leaves):
            t = PredictorTensor(f"out{i}")
            t.copy_from_cpu(np.asarray(
                leaf.numpy() if hasattr(leaf, "numpy") else leaf))
            self._outputs[t.name] = t
        return True

    def get_output_names(self) -> List[str]:
        return list(self._outputs)

    def get_output_handle(self, name: str) -> PredictorTensor:
        return self._outputs[name]

    def profile_report(self) -> Dict:
        """One coherent report for the one-shot predictor: the Config's
        accepted-but-recorded knobs (ir_optim, memory_optim, threads)
        alongside profiler op spans and monitor counters — the same shape
        ServingPredictor.profile_report() returns for serving mode."""
        return _profile_report(self._config)


def _profile_report(config: Config, serving_metrics=None) -> Dict:
    from .. import observability
    from ..utils import profiler
    from ..utils.monitor import stats
    rep = {
        "config": {"model": config._prefix, "device": config._device,
                   "ir_optim": config._ir_optim,
                   "memory_optim": config._memory_optim,
                   "threads": config._threads,
                   "profile": config._profile},
        "op_spans": profiler.summary(),
        "stats": {k: v for k, v in stats().items()
                  if k.startswith("STAT_serving_")
                  or k == "STAT_predictor_runs"},
        # the unified telemetry report (PR 5): dispatch cache, dataloader,
        # checkpoint, train, serving histograms, compiled programs — the
        # same shape observability.report() returns everywhere else
        "observability": observability.report(),
    }
    if serving_metrics is not None:
        rep["serving"] = serving_metrics
    return rep


class ServingPredictor:
    """Serving-mode predictor: create_predictor(config) returns this when
    `config.enable_serving(...)` was called.  Wraps a running
    paddle_tpu.serving.ServingEngine (background loop started, programs
    precompiled) behind the predictor surface."""

    def __init__(self, config: Config):
        from ..serving import ServingEngine
        opts = dict(config._serving)
        model = opts.pop("model", None)
        provider = opts.pop("model_provider", None)
        warmup = opts.pop("warmup", True)
        start = opts.pop("start", True)
        gateway = opts.pop("gateway", None)
        quantize = opts.pop("quantize", None)
        program_cache_dir = opts.pop("program_cache_dir", None)
        if program_cache_dir is not None:
            from ..programs import store as _pstore
            _pstore.enable(program_cache_dir)
        if model is None:
            model = provider()
            prefix = config.model_dir()
            if prefix is None:
                raise ValueError(
                    "serving with model_provider= needs a jit.save artifact "
                    "(Config.set_model) to restore weights from")
            data = np.load(prefix + ".pdiparams.npz")
            model.set_state_dict({k: data[k] for k in data.files})
        model.eval()
        draft = opts.get("draft_model")
        if draft is not None:
            draft.eval()
        if quantize is not None:
            # int8 weight-only conversion at deployment: the fp weights
            # (in-memory or restored from the artifact) become int8
            # buffers + scales BEFORE any serving program traces, so the
            # compiled set holds int8 from the first compile
            from ..quantization import quantize_for_serving
            model = quantize_for_serving(model, quantize)
            if draft is not None:
                opts["draft_model"] = quantize_for_serving(draft, quantize)
        self._config = config
        try:
            self.engine = ServingEngine(model, **opts)
        except Exception as e:
            from ..programs.program_set import ProgramSetError
            if not isinstance(e, ProgramSetError) or "program_set" not in opts:
                raise
            # a stale/corrupt AOT program set must cost a recompile, not
            # an outage: warn loudly, count it, trace fresh
            import warnings
            warnings.warn(
                f"enable_serving(program_set=...): artifact rejected "
                f"({e}); falling back to a fresh trace+compile")
            try:
                from ..observability.metrics import counter
                counter("program_set_fallback_total",
                        "serving boots that rejected their AOT program "
                        "set and fell back to tracing").inc()
            except Exception:
                pass
            opts.pop("program_set", None)
            self.engine = ServingEngine(model, **opts)
        if warmup:
            self.engine.warmup()
        self.gateway = None
        if gateway is not None and gateway is not False:
            from ..serving import ServingGateway
            gw_opts = {} if gateway is True else dict(gateway)
            self.gateway = ServingGateway(self.engine, **gw_opts)
        if start:
            # the gateway owns the engine loop when present (preemption
            # must interleave with engine steps on one thread)
            if self.gateway is not None:
                self.gateway.start()
            else:
                self.engine.start()

    def submit(self, prompt, max_new_tokens, **kwargs):
        """Enqueue a request; returns the streaming serving.Response.
        With a gateway configured, kwargs additionally accept tenant= and
        priority= and every admission outcome is a terminal Response
        (shed/rate-limited requests come back already failed instead of
        raising)."""
        if self.gateway is not None:
            return self.gateway.submit(prompt, max_new_tokens, **kwargs)
        return self.engine.submit(prompt, max_new_tokens, **kwargs)

    def metrics(self):
        if self.gateway is not None:
            return self.gateway.metrics()
        return self.engine.metrics()

    def save_program_set(self, path: str,
                         extra_meta: Optional[dict] = None) -> str:
        """Export the engine's whole compiled-program family as one AOT
        artifact (see README "Program lifecycle"); other replicas boot
        from it via ``enable_serving(..., program_set=path)`` without
        retracing."""
        return self.engine.save_program_set(path, extra_meta)

    def serve_http(self, port: int = 8000, addr: str = "127.0.0.1"):
        """Start the OpenAI-shaped streaming endpoint over the gateway
        (requires gateway= in enable_serving); returns the server."""
        if self.gateway is None:
            raise ValueError(
                "serve_http needs a gateway: enable_serving(..., "
                "gateway=True) or gateway={...}")
        from ..serving import serve_gateway
        return serve_gateway(self.gateway, port=port, addr=addr)

    def profile_report(self) -> Dict:
        """Config knobs + profiler spans (the engine's `serving_*` phases
        among them, always recorded) + live serving metrics in one
        report."""
        rep = _profile_report(self._config, self.engine.metrics())
        if self.gateway is not None:
            gm = self.gateway.metrics()
            gm.pop("engine", None)  # already under rep["serving"]
            rep["gateway"] = gm
        return rep

    def close(self):
        if self.gateway is not None:
            self.gateway.close()  # closes the engine too
        else:
            self.engine.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


def create_predictor(config: Config):
    if config.recsys_enabled():
        from ..embedding import RecsysPredictor
        return RecsysPredictor(**config._recsys)
    if config.serving_enabled():
        return ServingPredictor(config)
    return Predictor(config)
