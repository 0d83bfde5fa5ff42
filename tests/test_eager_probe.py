"""Fast-tier guards for the eager-dispatch perf artifacts (ISSUE-2):
- probes/eager_probe.py --steps 3 smoke (the microbench can never rot),
- bench backend-probe hang fix (structured backend_unavailable, rc=0),
- GPT-2 solo-probe republish discipline."""
import json
import os
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench  # noqa: E402

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_eager_probe_smoke_runs_on_cpu():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "probes", "eager_probe.py"),
         "--steps", "3", "--mlp-steps", "2"],
        capture_output=True, text=True, timeout=300, env=env, cwd=_REPO)
    assert proc.returncode == 0, proc.stderr[-800:]
    lines = [l for l in proc.stdout.splitlines() if l.startswith("EAGER")]
    assert lines, proc.stdout[-400:]
    out = json.loads(lines[-1][len("EAGER"):])
    assert out["eager_ops_per_sec"] > 0
    assert "speedup_vs_uncached" in out
    assert "parity_error" not in out, out.get("parity_error")
    assert out["legs"]["cached"]["loss"] == out["legs"]["uncached"]["loss"]


def test_backend_probe_timeout_is_structured(monkeypatch):
    """BENCH_r05 regression: an unreachable accelerator made
    `jax.default_backend()` blow the 300 s subprocess timeout and crash
    main() rc=1.  The probe must catch it and return a structured
    backend_unavailable record instead."""
    def fake_run(*a, **k):
        raise subprocess.TimeoutExpired(cmd=a[0], timeout=k.get("timeout"))

    monkeypatch.setattr(bench.subprocess, "run", fake_run)
    out = bench._probe_backend(timeout=1)
    assert out["backend_unavailable"] is True
    assert out["backend"] is None
    assert "timed out" in out["error"]


def test_backend_probe_failure_rc_is_structured(monkeypatch):
    class P:
        returncode = 1
        stdout = ""
        stderr = "RuntimeError: no backend"

    monkeypatch.setattr(bench.subprocess, "run", lambda *a, **k: P())
    out = bench._probe_backend(timeout=1)
    assert out["backend_unavailable"] is True
    assert "no backend" in out["error"]


def test_backend_probe_cpu_ok(monkeypatch):
    class P:
        returncode = 0
        stdout = "cpu\n"
        stderr = ""

    monkeypatch.setattr(bench.subprocess, "run", lambda *a, **k: P())
    out = bench._probe_backend(timeout=1)
    assert out == {"backend": "cpu", "backend_unavailable": False}


_DEGRADED_GPT2_SCRIPT = r"""
import json, os
if os.environ.get("PDTPU_IGNORE_SLOT") == "1":
    print("GPT2" + json.dumps(
        {"step_ms": 136.0, "step_ms_spread": 0.7, "mfu": 34.72,
         "slot_tf_s": 150.0}))
else:
    print("GPT2" + json.dumps({"slot_bailed": True, "slot_tf_s": 150.0}))
"""


def test_gpt2_degraded_leg_republishes_solo_probe():
    """VERDICT r4 weak #1: a slot-degraded GPT-2 run must never publish its
    measured number at the headline keys — the qualified solo-probe
    measurement is republished instead, with the degraded live leg whole
    under live_leg.unpublished_degraded_measurement."""
    out = bench._run_tpu_probe(_DEGRADED_GPT2_SCRIPT, "GPT2", timeout=60)
    solo = bench._SOLO_PROBE_PUBLISH["GPT2"]
    assert out["republished_from_solo_probe"] is True
    assert out["live_leg_slot_degraded"] is True
    assert out["mfu"] == solo["mfu"]
    assert out["step_ms"] == solo["step_ms"]
    assert out["source"] == "probes/gpt2_probe_results.txt"
    live = out["live_leg"]
    assert live["slot_degraded"] is True
    assert live["unpublished_degraded_measurement"]["step_ms"] == 136.0
    assert live["unpublished_degraded_measurement"]["mfu"] == 34.72
