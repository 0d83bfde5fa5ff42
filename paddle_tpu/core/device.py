"""Device / place management.

TPU-native replacement for the reference's Place hierarchy and
DeviceContextPool (reference: paddle/fluid/platform/place.h,
platform/device_context.h).  In XLA there are no user-managed streams or
per-place kernel registries: a "place" reduces to a `jax.Device`, and stream
ordering / allocator / context concerns are handled by PJRT.  We keep a
paddle-compatible `set_device`/`get_device` string API ("cpu", "tpu:0").
"""
from __future__ import annotations

import jax

_current_device = None  # None -> jax default


class Place:
    """Lightweight place descriptor wrapping a jax.Device."""

    def __init__(self, device: "jax.Device"):
        self._device = device

    @property
    def jax_device(self):
        return self._device

    def is_cpu_place(self):
        return self._device.platform == "cpu"

    def is_tpu_place(self):
        return self._device.platform == "tpu"

    def is_gpu_place(self):
        return self._device.platform == "gpu"

    def __repr__(self):
        return f"Place({self._device.platform}:{self._device.id})"

    def __eq__(self, other):
        return isinstance(other, Place) and self._device == other._device


def CPUPlace():
    cpus = [d for d in jax.devices("cpu")] if _has_platform("cpu") else []
    if not cpus:
        # jax may be running pure-TPU; fall back to default device
        return Place(jax.devices()[0])
    return Place(cpus[0])


def TPUPlace(idx: int = 0):
    devs = jax.devices()
    return Place(devs[idx % len(devs)])


# Paddle alias: CUDAPlace maps onto the accelerator place.
CUDAPlace = TPUPlace
XPUPlace = TPUPlace


def _has_platform(platform: str) -> bool:
    try:
        jax.devices(platform)
        return True
    except RuntimeError:
        return False


def set_device(device: str):
    """Set the default device by paddle-style string: 'cpu', 'tpu', 'tpu:1'."""
    global _current_device
    if device is None:
        _current_device = None
        return
    name = device.lower()
    if ":" in name:
        platform, _, idx = name.partition(":")
        idx = int(idx)
    else:
        platform, idx = name, 0
    if platform in ("gpu", "cuda", "xpu", "tpu"):
        # all accelerator names map to the default accelerator backend
        devs = jax.devices()
        dev = devs[idx % len(devs)]
    elif platform == "cpu":
        dev = jax.devices("cpu")[0] if _has_platform("cpu") else jax.devices()[0]
    else:
        raise ValueError(f"Unknown device {device!r}")
    _current_device = dev
    jax.config.update("jax_default_device", dev)
    return Place(dev)


def get_device() -> str:
    dev = _current_device or jax.devices()[0]
    return f"{dev.platform}:{dev.id}"


def current_jax_device():
    return _current_device or jax.devices()[0]


def device_count() -> int:
    return jax.device_count()


def is_compiled_with_cuda() -> bool:
    """Paddle-API compat: reports accelerator availability (TPU here)."""
    return False


def is_compiled_with_tpu() -> bool:
    return any(d.platform == "tpu" for d in jax.devices())


def is_compiled_with_xpu() -> bool:
    """Paddle-API compat: Baidu-Kunlun XPU — never present here."""
    return False


def get_cudnn_version():
    """Paddle-API compat: no cuDNN in the XLA/TPU stack."""
    return None


# paddle exposes CUDAPinnedPlace for pinned host staging buffers; host
# memory management is XLA's job here, so it aliases the host place.
CUDAPinnedPlace = CPUPlace
