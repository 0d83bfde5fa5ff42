"""AOT program sets: a serving engine's entire compiled-program family as
one on-disk artifact.

`jit.save` exports ONE model forward; a serving engine runs a FAMILY —
one prefill per prompt-length bucket (covering target + draft on a
speculative engine), plus the single decode (or verify) step, in fixed or
paged KV layout.  `save_program_set` captures the whole family with its
configuration manifest; `ServingEngine(..., program_set=path)` /
`Config.enable_serving(..., program_set=path)` boots from it WITHOUT
retracing any model code.

Each program is stored in two representations, tried in order at load:

- ``exe`` — the serialized native XLA executable
  (`jax.experimental.serialize_executable`): zero tracing AND zero XLA
  compilation on load — the fastest possible boot.  Valid only for the
  exact jax version, backend and device topology recorded in the
  manifest (a compiled binary is not an interchange format).
- ``stablehlo`` — the portable `jax.export` serialization: survives
  jax-version drift within jax's export-compat window, and — for
  UNMESHED engines — backend/device-count drift too (mesh engines bake
  a device assignment, so their manifest gates topology for BOTH
  representations); loading compiles the StableHLO (accelerated by the
  persistent program store).  `jax.export` does not carry buffer
  donation, so the loader re-applies each program's recorded
  ``donate_argnums`` through an outer `jax.jit` — without it every
  serving tick would silently copy the whole KV pool.

Staleness can never be silent: the manifest embeds the paddle_tpu
version, the full `utils/op_version` snapshot, hashes of the target (and
draft) weight shapes/dtypes, and every engine knob that shapes a program
(buckets, slots, lengths, decode chunk, spec_tokens, kv layout,
block_size/num_blocks, mesh axes, pool dtype).  Any mismatch — or a
byte-corrupted artifact (sha256-checked before unpickling) — raises the
typed `ProgramSetError`; `inference.ServingPredictor` catches it, warns,
counts it (``program_set_fallback_total``) and falls back to a fresh
trace+compile.
"""
from __future__ import annotations

import hashlib
import os
import pickle
from typing import Dict, Optional

__all__ = ["ProgramSetError", "save_program_set", "load_program_set",
           "read_manifest", "LoadedProgram", "engine_manifest",
           "PROGRAM_SET_SUFFIX"]

# 2: every program is `fn(weights, pools, inputs) -> dict` (ServingEngine.
# _program_args); a format-1 artifact holds positional signatures and must
# never be called with these arguments
PROGRAM_SET_FORMAT = 2
PROGRAM_SET_SUFFIX = ".pdprograms"


class ProgramSetError(RuntimeError):
    """Typed load/save failure: manifest mismatch, corrupt artifact,
    unloadable programs.  Callers may catch it and fall back to a fresh
    trace+compile — the one thing they must never do is reuse a stale
    artifact silently."""


class LoadedProgram:
    """One deserialized program: `fn(*args)` runs it.  ``kind`` records
    which representation loaded — 'exe' programs are ALREADY compiled
    (warmup can skip executing them), 'stablehlo' programs compile on
    their first call."""

    __slots__ = ("name", "kind", "fn")

    def __init__(self, name: str, kind: str, fn):
        self.name = name
        self.kind = kind
        self.fn = fn

    def __call__(self, *args, **kwargs):
        return self.fn(*args, **kwargs)


def _state_sig(state: Dict) -> str:
    import numpy as np
    items = sorted((k, tuple(int(d) for d in np.shape(v)),
                    str(getattr(v, "dtype", type(v).__name__)))
                   for k, v in state.items())
    return hashlib.sha256(repr(items).encode()).hexdigest()[:16]


def engine_manifest(engine) -> dict:
    """Every config axis that shapes a compiled serving program.  Two
    engines with equal manifests trace byte-identical programs; any
    difference (weight dtype/shape, quantize, spec, mesh, kv layout, op
    semantics) MUST miss."""
    import jax
    from .. import version
    from ..utils import op_version
    mesh = None
    if engine.mesh is not None:
        mesh = {"axes": {k: int(v) for k, v in engine.mesh.shape.items()},
                "devices": int(engine.mesh.devices.size)}
    return {
        "paddle_tpu_version": version.full_version,
        "op_versions": op_version.snapshot(),
        "jax_version": jax.__version__,
        "backend": jax.default_backend(),
        "device_count": int(jax.device_count()),
        "model_class": type(engine.model).__name__,
        "state_sig": _state_sig(engine._state),
        "draft_state_sig": (_state_sig(engine._dstate)
                            if engine.draft_model is not None else None),
        "max_slots": engine.max_slots,
        "max_len": engine.max_len,
        "pool_len": engine._pool_len,
        "buckets": tuple(engine.buckets),
        "decode_chunk": engine.decode_chunk,
        "pad_token_id": engine.pad_token_id,
        "spec_tokens": (engine.spec_tokens
                        if engine.draft_model is not None else None),
        "kv": engine.kv,
        "block_size": (engine.block_size if engine.kv == "paged" else None),
        "num_blocks": (engine.kv_pool.num_blocks
                       if engine.kv == "paged" else None),
        "mesh": mesh,
        "dtype": (str(engine._dtype) if engine._dtype is not None else None),
        "key_width": engine._key_width,
        # batched LoRA bakes the factor-stack avals (rank, slot count,
        # wrapped layer set) into every program signature; adapter IDs
        # and contents are dynamic and deliberately absent
        "lora": (None if getattr(engine, "lora", None) is None else {
            "rank": engine.lora.rank,
            "max_adapters": engine.lora.max_adapters,
            "targets": list(engine.lora.targets),
        }),
    }


# manifest keys whose mismatch only disqualifies the native-executable
# representation (the portable StableHLO one survives them).  backend /
# device_count are exe-only for UNMESHED engines; a mesh engine's
# programs bake a device assignment, so topology gates both
# representations there.
_EXE_ONLY_KEYS = ("jax_version", "backend", "device_count")


def _manifest_mismatches(saved: dict, live: dict) -> list:
    bad = []
    mesh_bound = live.get("mesh") is not None or saved.get("mesh") is not None
    for k in live:
        if k in _EXE_ONLY_KEYS and not (
                mesh_bound and k in ("backend", "device_count")):
            continue
        if saved.get(k) != live[k]:
            bad.append(f"{k}: artifact={saved.get(k)!r} != "
                       f"engine={live[k]!r}")
    return bad


def _serialize_native(raw_jitted, tracked, args, donate):
    """The pickled native executable of one program.  It is taken from the
    TrackedJit's AOT cache when the program is already compiled (warmup
    ran), so saving a warm engine recompiles nothing — unless the backend
    refuses an executable that has run: XLA:CPU's sort thunk resolves its
    comparator on first execution and from then on answers "`LessThan` is
    not serializable".  jax hands the same executable back for the same
    jit, so the program is compiled once more through a jit of its own
    and that never-executed executable is serialized."""
    import jax
    from jax.experimental import serialize_executable as _sx
    compiled = None
    if tracked is not None and hasattr(tracked, "compiled_for"):
        compiled = tracked.compiled_for(*args)
    if compiled is None:
        compiled = raw_jitted.lower(*args).compile()
    try:
        payload = _sx.serialize(compiled)
    except jax.errors.JaxRuntimeError:
        fresh = jax.jit(lambda *a: raw_jitted(*a), donate_argnums=donate)
        payload = _sx.serialize(fresh.lower(*args).compile())
    return pickle.dumps(payload, protocol=pickle.HIGHEST_PROTOCOL)


def _export_one(raw_jitted, tracked, args, donate):
    """(exe_blob | None, stablehlo_blob | None, errors) for one program."""
    errors = {}
    exe_blob = stablehlo_blob = None
    try:
        exe_blob = _serialize_native(raw_jitted, tracked, args, donate)
    except Exception as e:  # noqa: BLE001 — representation is optional
        errors["exe"] = f"{type(e).__name__}: {e}"[:300]
    try:
        from jax import export as jax_export
        stablehlo_blob = jax_export.export(raw_jitted)(*args).serialize()
    except Exception as e:  # noqa: BLE001
        errors["stablehlo"] = f"{type(e).__name__}: {e}"[:300]
    return exe_blob, stablehlo_blob, errors


def save_program_set(engine, path: str,
                     extra_meta: Optional[dict] = None) -> str:
    """Serialize the engine's whole program family to
    ``path + '.pdprograms'``.  Engine trace counters are snapshotted and
    restored (export re-traces; that must not look like extra serving
    compiles to `compile_counts()`).  Returns the artifact path."""
    family = engine._program_family()
    # export re-runs the traced python (host-side trace counters fire)
    compiles_snapshot = {"decode": engine._compiles["decode"],
                         "prefill": dict(engine._compiles["prefill"])}
    programs = {}
    save_errors = {}
    try:
        for name, fn, args, donate in family:
            raw = getattr(fn, "_jitted", fn)
            if isinstance(fn, LoadedProgram) or not hasattr(raw, "lower"):
                raise ProgramSetError(
                    f"program {name!r} was itself loaded from a program "
                    "set — re-exporting a loaded set is not supported; "
                    "save from a traced engine")
            exe_blob, hlo_blob, errors = _export_one(
                raw, fn, args, tuple(donate))
            if exe_blob is None and hlo_blob is None:
                raise ProgramSetError(
                    f"program {name!r} could not be serialized in any "
                    f"representation: {errors}")
            if errors:
                save_errors[name] = errors
            programs[name] = {"exe": exe_blob, "stablehlo": hlo_blob,
                              "donate": tuple(donate)}
    finally:
        engine._compiles["decode"] = compiles_snapshot["decode"]
        engine._compiles["prefill"].update(compiles_snapshot["prefill"])
    body = pickle.dumps(
        {"manifest": engine_manifest(engine),
         "extra_meta": dict(extra_meta or {}),
         "save_errors": save_errors,
         "programs": programs},
        protocol=pickle.HIGHEST_PROTOCOL)
    if not path.endswith(PROGRAM_SET_SUFFIX):
        path = path + PROGRAM_SET_SUFFIX
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        pickle.dump({"format": PROGRAM_SET_FORMAT,
                     "sha256": hashlib.sha256(body).hexdigest(),
                     "body": body}, f, protocol=pickle.HIGHEST_PROTOCOL)
    os.replace(tmp, path)  # atomic publish (the checkpoint discipline)
    return path


def _read_body(path: str) -> dict:
    if not path.endswith(PROGRAM_SET_SUFFIX) and not os.path.exists(path):
        path = path + PROGRAM_SET_SUFFIX
    try:
        with open(path, "rb") as f:
            envelope = pickle.load(f)
    except Exception as e:
        raise ProgramSetError(
            f"program set {path!r} unreadable: "
            f"{type(e).__name__}: {e}") from e
    if not isinstance(envelope, dict) or "body" not in envelope:
        raise ProgramSetError(f"program set {path!r}: not a program-set "
                              "artifact")
    if envelope.get("format") != PROGRAM_SET_FORMAT:
        raise ProgramSetError(
            f"program set {path!r}: format {envelope.get('format')!r} "
            f"unsupported (this build reads {PROGRAM_SET_FORMAT})")
    body = envelope["body"]
    digest = hashlib.sha256(body).hexdigest()
    if digest != envelope.get("sha256"):
        raise ProgramSetError(
            f"program set {path!r}: checksum mismatch (corrupt artifact) "
            "— refusing to load; delete it and re-save")
    try:
        return pickle.loads(body)
    except Exception as e:
        raise ProgramSetError(
            f"program set {path!r}: body undecodable: "
            f"{type(e).__name__}: {e}") from e


def read_manifest(path: str) -> dict:
    """The artifact's manifest + save metadata without loading programs."""
    body = _read_body(path)
    return {"manifest": body["manifest"],
            "extra_meta": body.get("extra_meta", {}),
            "save_errors": body.get("save_errors", {}),
            "programs": sorted(body["programs"])}


def _engine_devices(engine) -> list:
    """The devices the engine's programs were compiled for and run on: its
    mesh, or the one device that holds its KV pool.  A native executable
    must be loaded onto exactly these — jax's default is every device of
    the backend, and an executable loaded for eight then refuses one
    device's arguments."""
    if engine.mesh is not None:
        return list(engine.mesh.devices.flat)
    return list(engine._pools[0][0].devices())


def _load_one(name: str, rec: dict, exe_ok: bool,
              devices: list) -> LoadedProgram:
    errors = {}
    if exe_ok and rec.get("exe") is not None:
        try:
            from jax.experimental import serialize_executable as _sx
            payload = pickle.loads(rec["exe"])
            compiled = _sx.deserialize_and_load(
                *payload, execution_devices=devices)
            return LoadedProgram(name, "exe", compiled)
        except Exception as e:  # noqa: BLE001 — fall through to stablehlo
            errors["exe"] = f"{type(e).__name__}: {e}"[:300]
    if rec.get("stablehlo") is not None:
        try:
            import jax
            from jax import export as jax_export
            exported = jax_export.deserialize(rec["stablehlo"])
            # jax.export drops donation: re-apply the recorded indices
            # through an outer jit so the KV pool keeps updating in
            # place (a silent donation loss = a full pool copy per tick)
            donate = tuple(rec.get("donate") or ())
            fn = jax.jit(lambda *a, _ex=exported: _ex.call(*a),
                         donate_argnums=donate)
            return LoadedProgram(name, "stablehlo", fn)
        except Exception as e:  # noqa: BLE001
            errors["stablehlo"] = f"{type(e).__name__}: {e}"[:300]
    raise ProgramSetError(
        f"program {name!r} could not be loaded from any representation: "
        f"{errors or 'no representations in artifact'}")


def load_program_set(path: str, engine) -> Dict[str, LoadedProgram]:
    """Validate the artifact against the live engine and deserialize its
    programs.  Loading is deliberately SERIAL: executable
    deserialization contends on a process-wide XLA/LLVM lock, and
    thread-pooling it measures ~3x SLOWER wall-clock than one-at-a-time
    on CPU.  Raises `ProgramSetError` on ANY mismatch or corruption."""
    body = _read_body(path)
    live = engine_manifest(engine)
    saved = body["manifest"]
    mismatches = _manifest_mismatches(saved, live)
    if mismatches:
        raise ProgramSetError(
            "program set does not match this engine/runtime (stale "
            "artifacts are never reused silently): "
            + "; ".join(mismatches[:6]))
    wanted = [name for name, _, _, _ in engine._program_family()]
    missing = [n for n in wanted if n not in body["programs"]]
    if missing:
        raise ProgramSetError(
            f"program set lacks programs {missing} required by this "
            "engine configuration")
    # native executables are version- AND topology-bound; StableHLO only
    # needs the (already-validated) manifest
    exe_ok = all(saved.get(k) == live.get(k) for k in _EXE_ONLY_KEYS)
    out: Dict[str, LoadedProgram] = {}
    errors = {}
    devices = _engine_devices(engine)
    for n in wanted:
        try:
            out[n] = _load_one(n, body["programs"][n], exe_ok, devices)
        except ProgramSetError as e:
            errors[n] = str(e)
    if errors:
        raise ProgramSetError(f"program set load failed: {errors}")
    return out
