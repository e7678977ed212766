"""What both drivers need from jax and from the program's public surface:
compile counting, peak memory, model construction with the benchmark's weights,
host spans for the profiler. Construction, compile counting and the memory
reading follow ``chip_smoke.py`` (PR 24), which ran on the chip; nothing here
imports it."""
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


def construct_model(cfg):
    """The program's own ``LlamaForCausalLM`` at the configuration's sizes.

    The constructor draws every weight on the host (``Normal.__call__``), in
    float32, whatever it is given; it runs with the CPU as jax's default
    device, so that the draw is never shipped to the chip. ``load_weights``
    then replaces every value."""
    import jax

    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    opts = dict(cfg.get("model", {}))
    dtype = opts.pop("dtype", "bfloat16")
    heads = cfg["num_attention_heads"]
    if cfg.get("head_dim") and cfg["head_dim"] * heads != cfg["hidden_size"]:
        raise ValueError("LlamaConfig derives head_dim as hidden_size / heads")
    lcfg = LlamaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=heads,
        num_key_value_heads=cfg["num_key_value_heads"],
        max_position_embeddings=cfg["max_position_embeddings"],
        initializer_range=cfg.get("initializer_range", 0.02),
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        tie_word_embeddings=cfg.get("tie_word_embeddings", False),
        dtype=dtype, **opts)
    with jax.default_device(jax.devices("cpu")[0]):
        model = LlamaForCausalLM(lcfg)
        model.to(dtype=dtype)
    return model


def load_weights(model, cfg, seed):
    """The benchmark's weights, made on the device from the seed in one jitted
    call (bfloat16, what is served; a float32 model holds them widened), loaded
    through ``set_state_dict`` as a checkpoint would be. Returns the number of
    parameters."""
    import jax.numpy as jnp

    import paddle_tpu as paddle

    import weights as W

    made = W.make_all(seed, cfg, jnp.bfloat16)
    missing, unexpected = model.set_state_dict(
        {name: paddle.Tensor(value) for name, value in made.items()})
    if missing or unexpected:
        raise RuntimeError(f"weights do not fit the model: missing {missing}, "
                           f"unexpected {unexpected}")
    return sum(int(p.value.size) for p in model.parameters())


class SliceTracer:
    """Profile a short slice of the window into ``directory`` and reduce it."""

    def __init__(self, directory):
        self.directory = directory
        self.t_start = self.t_stop = None

    def start(self):
        import jax

        jax.profiler.start_trace(self.directory)
        self.t_start = time.perf_counter()

    def stop(self):
        import jax

        self.t_stop = time.perf_counter()
        jax.profiler.stop_trace()

    @property
    def running(self):
        return self.t_start is not None and self.t_stop is None
