"""Device management (reference: python/paddle/device/ — set_device,
synchronize, device queries). On TPU, placement is owned by jax/XLA and
shardings; this module provides the paddle-shaped façade."""
from __future__ import annotations

import jax

_current_device = None


def get_all_devices():
    return jax.devices()


def device_count(device_type=None) -> int:
    if device_type in (None, "tpu"):
        try:
            return len(jax.devices("tpu"))
        except RuntimeError:
            pass
    try:
        return len(jax.devices(device_type)) if device_type else len(jax.devices())
    except RuntimeError:
        return 0


def _parse_device(device: str):
    """'tpu', 'tpu:0', 'gpu:1' (gpu aliases to the accelerator), 'cpu' →
    the jax.Device. Single resolver shared by set_device and the memory
    telemetry APIs."""
    name = device.split(":")[0]
    idx = int(device.split(":")[1]) if ":" in device else 0
    if name in ("gpu", "tpu"):
        # accelerator request must not silently land on CPU
        for platform in ("tpu", "gpu"):
            try:
                return jax.devices(platform)[idx]
            except RuntimeError:
                continue
        raise RuntimeError(
            f"set_device({device!r}): no accelerator backend available")
    return jax.devices(name)[idx]


def set_device(device: str):
    """reference: paddle.set_device. Accepts 'tpu', 'cpu', 'tpu:0', ...
    Sets jax's default device for subsequent array creation."""
    global _current_device
    dev = _parse_device(device)
    jax.config.update("jax_default_device", dev)
    _current_device = device
    return dev


def get_device() -> str:
    if _current_device is not None:
        return _current_device
    d = jax.devices()[0]
    return f"{d.platform}:{d.id}"


def synchronize(device=None):
    """Block until all dispatched work completes (reference:
    paddle.device.synchronize / cudaDeviceSynchronize)."""
    jax.effects_barrier()


def is_compiled_with_cuda() -> bool:
    return False


# ---------------------------------------------------------- memory telemetry
def memory_stats(device=None) -> dict:
    """Device memory telemetry (reference: paddle/fluid/memory/stats.cc +
    device.cuda.memory_* APIs) — PJRT's per-device stats dict; keys include
    bytes_in_use, peak_bytes_in_use, bytes_limit where the backend reports
    them. CPU backends may report nothing ({})."""
    dev = _resolve(device)
    try:
        return dict(dev.memory_stats() or {})
    except Exception:
        return {}


def _resolve(device):
    if device is None:
        return jax.devices()[0]
    if isinstance(device, int):
        return jax.devices()[device]
    if isinstance(device, str):
        return _parse_device(device)
    return device


def memory_allocated(device=None) -> int:
    """reference: device.cuda.memory_allocated — current live bytes."""
    return int(memory_stats(device).get("bytes_in_use", 0))


def max_memory_allocated(device=None) -> int:
    """reference: device.cuda.max_memory_allocated — peak live bytes."""
    return int(memory_stats(device).get("peak_bytes_in_use", 0))


def memory_reserved(device=None) -> int:
    """reference: device.cuda.memory_reserved — backend pool bytes."""
    s = memory_stats(device)
    return int(s.get("pool_bytes", s.get("bytes_reserved",
                                         s.get("bytes_in_use", 0))))


def max_memory_reserved(device=None) -> int:
    s = memory_stats(device)
    return int(s.get("peak_pool_bytes", s.get("peak_bytes_in_use", 0)))


# ------------------------------------------------- device API tail
# (reference: device/__init__.py — compile-flag predicates, vendor
# places, and the stream/event facade. On TPU, XLA owns scheduling: a
# "stream" is the device's ordered execution queue, events are markers
# realized by block_until_ready at sync points.)


def get_cudnn_version():
    """None: no cuDNN in the TPU build (reference returns None when
    not compiled with CUDA)."""
    return None


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_ipu() -> bool:
    return False


def is_compiled_with_cinn() -> bool:
    return False


def is_compiled_with_rocm() -> bool:
    return False


def is_compiled_with_custom_device(device_type: str = None) -> bool:
    """TPU rides PJRT's plugin mechanism — the moral equivalent of the
    reference's custom-device runtime."""
    return device_type in (None, "tpu")


def get_all_device_type():
    import jax

    return sorted({d.platform for d in jax.devices()} | {"cpu"})


def get_all_custom_device_type():
    return [t for t in get_all_device_type() if t not in ("cpu", "gpu")]


def get_available_device():
    import jax

    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def get_available_custom_device():
    return [d for d in get_available_device()
            if not d.startswith(("cpu", "gpu"))]


from ..framework.core_api import CPUPlace as _CPUPlace  # noqa: E402


class XPUPlace(_CPUPlace):
    def __init__(self, device_id: int = 0):
        raise RuntimeError("XPU hardware is not supported by the TPU build")


class IPUPlace(_CPUPlace):
    def __init__(self, device_id: int = 0):
        raise RuntimeError("IPU hardware is not supported by the TPU build")


class Stream:
    """Execution-queue handle (reference: device/cuda Stream). XLA
    serializes per-device execution; wait/synchronize map to
    block_until_ready barriers."""

    def __init__(self, device=None, priority=2):
        self.device = device
        self.priority = priority
        self._last = None

    def record(self, obj):
        self._last = obj

    def wait_stream(self, other: "Stream") -> None:
        if other._last is not None:
            import jax

            jax.block_until_ready(other._last)

    def synchronize(self) -> None:
        synchronize(self.device)


class Event:
    """Completion marker (reference: device/cuda Event)."""

    def __init__(self, enable_timing=False, blocking=False, interprocess=False):
        self._recorded = None
        import time as _t

        self._time = _t.time

    def record(self, stream: Stream = None) -> None:
        self._recorded = self._time()

    def query(self) -> bool:
        return True  # device queue is serialized; recorded == done at sync

    def synchronize(self) -> None:
        synchronize()

    def elapsed_time(self, end: "Event") -> float:
        if self._recorded is None or end._recorded is None:
            raise RuntimeError("both events must be recorded")
        return (end._recorded - self._recorded) * 1000.0


_default_stream = Stream()
_current_stream = [_default_stream]


def current_stream(device=None) -> Stream:
    return _current_stream[-1]


def set_stream(stream: Stream) -> Stream:
    prev = _current_stream[-1]
    _current_stream[-1] = stream
    return prev


class stream_guard:
    """Scoped stream switch (reference: device/__init__.py stream_guard)."""

    def __init__(self, stream: Stream):
        self._stream = stream

    def __enter__(self):
        self._prev = set_stream(self._stream)
        return self._stream

    def __exit__(self, *exc):
        set_stream(self._prev)
        return False

# submodules matching the reference layout: CPU-build-semantics facades
# (device_count()==0 / clear not-on-this-build errors) — the TPU device's
# real streams/events/memory APIs live on this module directly
from . import cuda, xpu  # noqa: E402,F401
