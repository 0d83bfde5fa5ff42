"""Rehearse chip_smoke.py's three phases on the CPU at a tiny size, with the
flash kernel in interpret mode, so the script that proves the system on the
chip cannot rot between chip runs.  What only a chip can show (the device
platform, `tpu_custom_call` in the step) is asserted in its `main()`."""
import json
import os
import subprocess
import sys

import jax
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import chip_smoke  # noqa: E402

from paddle_tpu import models  # noqa: E402
from paddle_tpu.ops import flash_attention as fa  # noqa: E402


def tiny_cfg(**kw):
    return models.GPTConfig(vocab_size=512, hidden_size=128,
                            num_hidden_layers=2, num_attention_heads=2,
                            max_position_embeddings=128, **kw)


@pytest.fixture()
def interpret(monkeypatch):
    monkeypatch.setattr(fa, "_INTERPRET", True)


def phase_lines(capsys):
    return [json.loads(l) for l in capsys.readouterr().out.splitlines()
            if l.startswith("{")]


def test_train_then_serve_phases_tiny(interpret, capsys):
    cfg = tiny_cfg()
    model, _ = chip_smoke.train_phase(cfg, seed=0, batch=2, seq=128, steps=6)
    chip_smoke.serve_phase(model, cfg, seed=0, slots=4, max_len=128,
                           prompt_lens=(5, 20, 25, 40, 70, 100),
                           new_tokens=8)
    train, serve = phase_lines(capsys)
    assert train["phase"] == "train"
    assert train["attention_paths"]["flash"] == cfg.num_hidden_layers
    assert not train["attention_paths"].get("xla")
    assert train["losses"][-1] < train["losses"][0]
    assert serve["phase"] == "serve" and serve["tokens_served"] == 6 * 8
    assert serve["compile_counts"]["total"] == len(serve["buckets"]) + 1
    assert serve["post_warmup_compiles"] == 0


def test_dp_phase_tiny_on_four_virtual_devices(interpret, capsys):
    assert len(jax.devices()) >= 4
    cfg = tiny_cfg(hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0)
    chip_smoke.dp_phase(cfg, seed=0, n_dev=4, per_dev_batch=1, seq=128)
    (dp,) = phase_lines(capsys)
    assert dp["phase"] == "dp" and dp["batch"] == 4
    assert dp["max_abs_loss_diff"] < chip_smoke.DP_LOSS_TOL


def test_no_chip_means_no_result(cpu8_env):
    """The contract's negative half: with no TPU the script exits non-zero
    and prints no result line."""
    proc = subprocess.run([sys.executable, chip_smoke.__file__],
                          capture_output=True, text=True, env=cpu8_env,
                          timeout=120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
