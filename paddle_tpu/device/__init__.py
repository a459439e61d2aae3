"""Device API (paddle.device analog, python/paddle/device/__init__.py:281
set_device; Place taxonomy /root/reference/paddle/phi/common/place.h:135).

TPU-native: devices are jax devices; there are no streams/events to manage
(XLA orders execution); memory stats come from jax device memory stats
instead of the reference allocator's stat registry
(/root/reference/paddle/phi/core/memory/stats.cc).
"""
from __future__ import annotations

from typing import List, Optional

import jax


class Place:
    def __init__(self, device_type: str, device_id: int = 0):
        self.device_type = device_type
        self.device_id = device_id

    def __repr__(self):
        return f"Place({self.device_type}:{self.device_id})"

    def __eq__(self, other):
        return (isinstance(other, Place)
                and self.device_type == other.device_type
                and self.device_id == other.device_id)

    def is_tpu_place(self):
        return self.device_type == "tpu"

    def is_cpu_place(self):
        return self.device_type == "cpu"

    def is_gpu_place(self):
        return self.device_type == "gpu"


class TPUPlace(Place):
    def __init__(self, device_id: int = 0):
        super().__init__("tpu", device_id)


class CPUPlace(Place):
    def __init__(self, device_id: int = 0):
        super().__init__("cpu", device_id)


class CUDAPlace(Place):
    """Accepted for API compat; maps to whatever accelerator jax exposes."""

    def __init__(self, device_id: int = 0):
        super().__init__("gpu", device_id)


class CUDAPinnedPlace(Place):
    """API compat: host memory is always 'pinned' from XLA's view
    (device transfers stage through pinned buffers internally)."""

    def __init__(self):
        super().__init__("cpu", 0)


_current_device: Optional[str] = None


def _jax_platform_name() -> str:
    return jax.default_backend()


def _canonical(platform: str) -> str:
    if platform == "tpu":
        return "tpu"
    if platform in ("cuda", "rocm", "gpu"):
        return "gpu"
    return "cpu"


def _place_of_array(arr) -> Place:
    devs = getattr(arr, "devices", None)
    if devs is None:
        return Place(_canonical(_jax_platform_name()), 0)
    try:
        dev = sorted(arr.devices(), key=lambda d: d.id)[0]
    except Exception:
        return Place(_canonical(_jax_platform_name()), 0)
    return Place(_canonical(dev.platform), dev.id)


def set_device(device: str) -> Place:
    """paddle.set_device analog. Accepts 'tpu', 'cpu', 'tpu:0', also 'gpu'
    (mapped to the available accelerator) and registered custom device
    types (device/custom.py registry)."""
    global _current_device
    name, _, idx = device.partition(":")
    from . import custom as _custom
    if name in _custom._REGISTRY:
        _current_device = device
        return Place(name, int(idx) if idx else 0)
    name = _canonical(name)
    _current_device = device
    return Place(name, int(idx) if idx else 0)


def get_device() -> str:
    if _current_device is not None:
        return _current_device
    return f"{_canonical(_jax_platform_name())}:0"


def get_all_custom_device_type() -> List[str]:
    from . import custom as _custom
    return _custom.get_all_custom_device_type()


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return True


def device_count() -> int:
    return jax.device_count()


def max_memory_allocated(device=None) -> int:
    """paddle.device.cuda.max_memory_allocated analog
    (python/paddle/device/cuda/__init__.py:233) from jax memory stats."""
    dev = jax.devices()[0]
    stats = getattr(dev, "memory_stats", lambda: None)()
    if not stats:
        return 0
    return int(stats.get("peak_bytes_in_use", 0))


def memory_allocated(device=None) -> int:
    dev = jax.devices()[0]
    stats = getattr(dev, "memory_stats", lambda: None)()
    if not stats:
        return 0
    return int(stats.get("bytes_in_use", 0))


def synchronize(device=None):
    """Block until all queued work completes (effectful only for timing)."""
    (jax.device_put(0.0) + 0).block_until_ready()


class Stream:
    """API-compat stub: XLA has no user-visible streams; execution order is
    program order (reference: paddle/phi/backends/.../stream.cc)."""

    def synchronize(self):
        synchronize()


def current_stream(device=None) -> Stream:
    return Stream()


# ---------------------------------------------------------------------------
# long-tail device API parity (python/paddle/device/__init__.py remainder)
# ---------------------------------------------------------------------------

class XPUPlace(Place):
    def __init__(self, device_id: int = 0):
        super().__init__("xpu", device_id)


class IPUPlace(Place):
    def __init__(self, device_id: int = 0):
        super().__init__("ipu", device_id)


class Event:
    """API-compat stub (phi/backends stream events): XLA orders execution
    by data dependence; record/synchronize map to device sync points."""

    def __init__(self, device=None, enable_timing=False, blocking=False,
                 interprocess=False):
        self._t = None

    def record(self, stream=None):
        import time as _time
        synchronize()
        self._t = _time.perf_counter()

    def query(self) -> bool:
        return True

    def synchronize(self):
        synchronize()

    def elapsed_time(self, end_event) -> float:
        if self._t is None or end_event._t is None:
            return 0.0
        return (end_event._t - self._t) * 1000.0


def set_stream(stream=None):
    return Stream()


class stream_guard:
    """No-op context (XLA has no user streams)."""

    def __init__(self, stream=None):
        self.stream = stream

    def __enter__(self):
        return self.stream

    def __exit__(self, *exc):
        return False


def get_all_device_type() -> List[str]:
    return ["cpu", _canonical(_jax_platform_name())]


def get_available_device() -> List[str]:
    return [f"{_canonical(_jax_platform_name())}:{i}"
            for i in range(jax.device_count())]


def get_available_custom_device() -> List[str]:
    from . import custom as _custom
    return [f"{name}:{i}"
            for name in _custom.get_all_custom_device_type()
            for i in range(_custom.get_custom_device(name).device_count())]


def get_cudnn_version():
    return None  # no cuDNN on TPU


def is_compiled_with_cinn() -> bool:
    return False  # XLA replaces CINN wholesale (SURVEY.md L7)


def is_compiled_with_rocm() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_ipu() -> bool:
    return False


def is_compiled_with_custom_device(device_type: str) -> bool:
    from . import custom as _custom
    return device_type in _custom._REGISTRY


def is_compiled_with_distribute() -> bool:
    return True


class _DeviceNS:
    """paddle.device.gpu / .xpu / .npu namespace stubs."""

    @staticmethod
    def device_count():
        return 0


gpu = _DeviceNS()
xpu = _DeviceNS()
npu = _DeviceNS()

from . import custom  # noqa: E402,F401
