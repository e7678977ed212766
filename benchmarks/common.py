"""What both drivers need from jax and from the program's public surface:
compile counting, peak memory, loading a builder's weights into its model, host
spans for the profiler and the traced slice. Compile counting and the memory
reading follow ``chip_smoke.py`` (PR 24), which ran on the chip; nothing here
imports it, and nothing here names a model: a cell's model and weights are its
builder's (``benchmarks/builders/<name>.py``)."""
from __future__ import annotations

import contextlib
import time


class Compiles:
    """Backend compiles and their seconds, from jax's own monitoring events (a
    persistent-cache hit fires the retrieval event instead)."""

    def __init__(self):
        from jax import monitoring

        self.n = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.long = []                   # seconds of each compile over one
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += seconds
            if seconds >= 1.0:
                self.long.append(round(seconds, 2))

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class Phases:
    """Seconds of each part of set-up, printed on an earlier line of a run."""

    def __init__(self, compiles):
        self.compiles = compiles
        self.seconds = {}

    @contextlib.contextmanager
    def phase(self, name):
        c = self.compiles
        n0, s0, t0 = c.n, c.seconds, time.perf_counter()
        try:
            yield
        finally:
            self.seconds[name] = {
                "wall_s": round(time.perf_counter() - t0, 3),
                "compile_s": round(c.seconds - s0, 3),
                "compiles": c.n - n0}


def peak_bytes(devices):
    """Peak bytes in use on the fullest device; the CPU backend reports none."""
    stats = [d.memory_stats() for d in devices]
    if any(s is None for s in stats):
        return 0
    return max(int(s["peak_bytes_in_use"]) for s in stats)


def annotate(name):
    """A host span in the profiler's own trace, on the device trace's clock."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def load_weights(model, made):
    """``made`` (``{state-dict name: array}``, the cell's builder's, on the
    device) loaded through ``set_state_dict`` as a checkpoint would be: a name
    the model lacks or misses is an error. Returns the number of parameters."""
    import paddle_tpu as paddle

    missing, unexpected = model.set_state_dict(
        {name: paddle.Tensor(value) for name, value in made.items()})
    if missing or unexpected:
        raise RuntimeError(f"weights do not fit the model: missing {missing}, "
                           f"unexpected {unexpected}")
    return sum(int(p.value.size) for p in model.parameters())


class SliceTracer:
    """Profile a short slice of the window into ``directory`` and reduce it.

    The slice is MEASURED on the device's clock (``xtrace.window_seconds``:
    first operation's start to last operation's end); ``t_start`` and
    ``t_stop`` are the host's clock after the profiler has started and before
    it is stopped, which only time the slice for the driver.

    ``rest``, where a driver sets it, is called before each edge and returns
    once the device has run everything dispatched to it, so that the slice is
    cut where the device is at rest. ``read_at_edges``, where a driver sets
    it, is called at the two edges, after that rest (before the profiler
    starts; before the host's clock is read and the profiler stopped), and
    ``edges`` keeps what it returned: what the program has counted at each
    edge of the slice."""

    def __init__(self, directory):
        self.directory = directory
        self.t_start = self.t_stop = None
        self.rest = None
        self.read_at_edges = None
        self.edges = []

    def _read_edge(self):
        if self.rest is not None:
            self.rest()
        if self.read_at_edges is not None:
            self.edges.append(self.read_at_edges())

    def start(self):
        import jax

        self._read_edge()
        jax.profiler.start_trace(self.directory)
        self.t_start = time.perf_counter()

    def stop(self):
        import jax

        self._read_edge()
        self.t_stop = time.perf_counter()
        jax.profiler.stop_trace()

    @property
    def running(self):
        return self.t_start is not None and self.t_stop is None


def device_rest(device):
    """A wait for whatever has been dispatched to ``device``: a trivial
    program on an array that already lives there, blocked on. One device runs
    what one thread dispatched in order, so it ends after everything queued
    before it; nothing of the program under test is reached into. The first
    call compiles, so it is made here, in set-up."""
    import jax
    import numpy as np

    @jax.jit
    def bench_rest(x):
        return x + 1

    x = jax.device_put(np.zeros((), np.int32), device)

    def rest():
        jax.block_until_ready(bench_rest(x))

    rest()
    return rest
