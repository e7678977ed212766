"""paddle_tpu.device — device management.

Reference analog: python/paddle/device (set_device/get_device, streams, events). TPU-first:
devices are PJRT devices from jax; streams/events have no user-managed analog (XLA orders
execution), so the Stream/Event API is a semantically-correct ordering shim built on
jax.block_until_ready.
"""
from __future__ import annotations

import os

import jax

_CURRENT = [None]


def enable_compile_cache():
    """Turn on jax's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and this
    sets nothing in code. Otherwise the cache lives at ``<checkout>/.jax_cache``,
    a fixed path derived from the package's location: the path is part of the
    cache key, so a directory that moves between runs never hits. Call it
    before the first compile; the entry-point script chip_smoke.py is the only
    caller (benchmarks/run.py places its own cache)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def _platforms():
    return {d.platform for d in jax.devices()}


def set_device(device: str):
    """'tpu', 'cpu', 'tpu:0', ... Maps to jax default device. Raises when
    the named platform has no devices here or the index is out of range: a
    caller that asked for the accelerator must not silently get the CPU."""
    name = device.split(":")[0]
    idx = int(device.split(":")[1]) if ":" in device else 0
    if name in ("gpu", "cuda", "custom_device"):
        name = "tpu"  # reference-style code asking for the accelerator gets the TPU
    try:
        devs = jax.devices(name)
    except RuntimeError as e:
        raise ValueError(
            f"set_device({device!r}): no {name!r} devices in this process "
            f"(platforms present: {sorted(_platforms())})") from e
    if not 0 <= idx < len(devs):
        raise ValueError(
            f"set_device({device!r}): index {idx} out of range, "
            f"{len(devs)} {name!r} device(s) present")
    dev = devs[idx]
    jax.config.update("jax_default_device", dev)
    _CURRENT[0] = f"{name}:{idx}"
    return dev


def get_device() -> str:
    if _CURRENT[0] is not None:
        return _CURRENT[0]
    d = jax.devices()[0]
    return "cpu" if d.platform == "cpu" else f"{d.platform}:{d.id}"


def get_all_device_type():
    return sorted(_platforms())


def get_available_device():
    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def get_available_custom_device():
    return [f"tpu:{d.id}" for d in jax.devices() if d.platform != "cpu"]


def device_count():
    return jax.device_count()


def is_compiled_with_cuda():
    return False


def cuda_device_count():
    return 0


def synchronize(device=None):
    """Block until all queued work is done (jax dispatch is async)."""
    (jax.device_put(0) + 0).block_until_ready()


def current_stream(device=None):
    return Stream()


def set_stream(stream):
    return stream


class Stream:
    """Ordering shim: XLA executes in dispatch order; wait_* is a barrier."""

    def __init__(self, device=None, priority=2):
        self.device = device

    def synchronize(self):
        synchronize()

    def wait_event(self, event):
        synchronize()

    def wait_stream(self, stream):
        synchronize()

    def record_event(self, event=None):
        return event or Event()

    def query(self):
        return True


class Event:
    def __init__(self, device=None, enable_timing=False, blocking=False, interprocess=False):
        pass

    def record(self, stream=None):
        pass

    def query(self):
        return True

    def synchronize(self):
        synchronize()


# -- memory introspection (reference: paddle.device.cuda.*_memory_* over the
# allocator's stats; here PJRT's per-device memory_stats) ---------------------
def _dev(device=None):
    if device is None:
        return jax.devices()[0]
    if isinstance(device, int):
        return jax.devices()[device]
    if isinstance(device, str) and ":" in device:
        return jax.devices()[int(device.split(":")[1])]
    return jax.devices()[0]


def memory_stats(device=None):
    """Raw PJRT allocator stats dict (empty on backends without support)."""
    return _dev(device).memory_stats() or {}


def memory_allocated(device=None):
    """Bytes currently held in device buffers (reference memory_allocated)."""
    stats = memory_stats(device)
    if "bytes_in_use" in stats:
        return int(stats["bytes_in_use"])
    d = _dev(device)
    return sum(int(np.prod(b.shape)) * b.dtype.itemsize
               for b in jax.live_arrays() if d in b.devices())


def max_memory_allocated(device=None):
    stats = memory_stats(device)
    return int(stats.get("peak_bytes_in_use", memory_allocated(device)))


def memory_reserved(device=None):
    # NOT bytes_limit: that is total allocatable capacity, not a reservation
    stats = memory_stats(device)
    return int(stats.get("bytes_reserved", memory_allocated(device)))


def max_memory_reserved(device=None):
    stats = memory_stats(device)
    return int(stats.get("peak_bytes_reserved", memory_reserved(device)))


def empty_cache():
    """PJRT manages the HBM pool; deleting dead python refs is the only lever."""
    import gc

    gc.collect()


import numpy as np  # noqa: E402


def stream_guard(stream):
    import contextlib

    @contextlib.contextmanager
    def guard():
        yield

    return guard()


from . import cuda  # noqa: E402,F401  (paddle.device.cuda compat namespace)


# reference device/__init__.py __all__ completion (round-3 sweep)
def get_cudnn_version():
    """No cuDNN on TPU: None, the reference's value when unavailable."""
    return None


def is_compiled_with_xpu():
    return False


def is_compiled_with_ipu():
    return False


def is_compiled_with_cinn():
    return False


def is_compiled_with_rocm():
    return False


def is_compiled_with_distribute():
    return True


def is_compiled_with_custom_device(device_type="tpu"):
    return device_type == "tpu"


def get_all_custom_device_type():
    return ["tpu"]


class XPUPlace:
    def __init__(self, device_id=0):
        self.device_id = device_id

    def __repr__(self):
        return f"Place(xpu:{self.device_id})"


class IPUPlace:
    def __repr__(self):
        return "Place(ipu)"
