"""C inference API (reference: inference/capi/, train/demo/)."""
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as paddle

capi = pytest.importorskip("paddle_tpu.capi")
if not capi.available():  # pragma: no cover
    pytest.skip("capi build unavailable", allow_module_level=True)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def artifact(tmp_path_factory):
    d = tmp_path_factory.mktemp("capi")
    paddle.seed(0)
    m = paddle.nn.Linear(4, 3)
    m.eval()
    prefix = str(d / "model")
    paddle.jit.save(m, prefix,
                    input_spec=[paddle.static.InputSpec([2, 4], "float32")])
    return prefix, m


def test_ctypes_roundtrip(artifact):
    prefix, m = artifact
    p = capi.CPredictor(prefix)
    x = np.random.RandomState(0).randn(2, 4).astype("float32")
    y = p.run(x)
    ref = np.asarray(m(paddle.to_tensor(x)).numpy())
    np.testing.assert_allclose(y, ref, atol=1e-5)
    p.close()


def test_error_surface():
    p = None
    with pytest.raises(RuntimeError, match="PD_CreatePredictor"):
        p = capi.CPredictor("/nonexistent/model")
    assert p is None


def test_standalone_c_binary(artifact, tmp_path):
    """Compile demo/capi_demo.c into a real C binary that embeds the
    interpreter itself (train/demo parity) and run it out-of-process."""
    prefix, m = artifact
    inc, link = capi.embed_flags()
    exe = str(tmp_path / "capi_demo")
    cmd = (["g++", "-O2", os.path.join(REPO, "demo", "capi_demo.c"),
            os.path.join(REPO, "paddle_tpu", "native", "src", "capi.cc"),
            "-o", exe] + inc + link)
    proc = subprocess.run(cmd, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]

    env = dict(os.environ)
    # the artifact here is a CPU export: keep the C client off the chip
    env["PYTHONPATH"] = REPO
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([exe, prefix], capture_output=True, text=True,
                         timeout=300, env=env)
    assert out.returncode == 0, (out.stdout, out.stderr[-2000:])
    assert "out_shape=2x3 checksum=" in out.stdout
    # checksum must match the in-process forward on the same ramp input
    x = (np.arange(8, dtype=np.float32) * 0.1).reshape(2, 4)
    expect = float(np.asarray(m(paddle.to_tensor(x)).numpy()).sum())
    got = float(out.stdout.strip().split("checksum=")[1])
    assert abs(got - expect) < 1e-4


def test_train_session_python_side(tmp_path):
    """save_train_program + TrainSession: exported StableHLO step trains
    (reference train/demo program-save half)."""
    from paddle_tpu.jit.train_export import save_train_program, TrainSession
    import paddle_tpu.nn.functional as F
    from paddle_tpu import nn
    paddle.seed(0)
    model = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 2))
    opt = paddle.optimizer.Adam(learning_rate=0.05,
                                parameters=model.parameters())
    prefix = str(tmp_path / "trainp")
    save_train_program(model, lambda out, lbl: F.cross_entropy(out, lbl),
                       opt, prefix,
                       input_specs=[((16, 8), "float32"), ((16,), "int64")])
    sess = TrainSession(prefix)
    rng = np.random.RandomState(0)
    x = rng.randn(16, 8).astype("float32")
    y = (x.sum(1) > 0).astype("int64")
    losses = [sess.step(x, y) for _ in range(15)]
    assert losses[-1] < losses[0]
    # trained state is retrievable (the save_persistables analogue)
    sd = sess.state_dict()
    assert any(v.size for v in sd.values())


def test_standalone_c_train_binary(tmp_path):
    """demo/train_demo.c: a C binary trains the exported step end-to-end —
    the reference's standalone demo_trainer.cc tier."""
    from paddle_tpu.jit.train_export import save_train_program
    import paddle_tpu.nn.functional as F
    from paddle_tpu import nn
    paddle.seed(0)
    model = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 2))
    opt = paddle.optimizer.Adam(learning_rate=0.05,
                                parameters=model.parameters())
    prefix = str(tmp_path / "trainp")
    save_train_program(model, lambda out, lbl: F.cross_entropy(out, lbl),
                       opt, prefix,
                       input_specs=[((16, 8), "float32"), ((16,), "int64")])

    inc, link = capi.embed_flags()
    exe = str(tmp_path / "train_demo")
    cmd = (["g++", "-O2", os.path.join(REPO, "demo", "train_demo.c"),
            os.path.join(REPO, "paddle_tpu", "native", "src", "capi.cc"),
            "-o", exe] + inc + link)
    proc = subprocess.run(cmd, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]

    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([exe, prefix], capture_output=True, text=True,
                         timeout=300, env=env)
    assert out.returncode == 0, (out.stdout, out.stderr[-2000:])
    assert "TRAIN_DEMO_OK" in out.stdout
