"""The flagship live programs under graftir analysis.

These are not fixtures: each builder goes through the SAME code path the
production engines jit — ``LlamaDecodeEngine.build_mixed_step`` /
``build_decode_burst`` exactly as ``ContinuousBatchingEngine`` caches
them (donation mask included), and the ``parallelize()`` mesh train step
with DP=8 ZeRO-1 state already placed on the mesh. Shapes are tier-1
tiny (the hazards GI001–GI004 look for are structural, not
size-dependent), and everything here is TRACE-only — ``jax.make_jaxpr``
abstract evaluation, no XLA compile, no dispatch — so the full flagship
sweep costs seconds, not minutes.

All framework imports live inside the builders: importing this module
costs stdlib only (the CLI prints ``--list-programs`` without touching
jax).
"""
from __future__ import annotations

import os

from .ir import AnalysisError, trace

__all__ = ["FLAGSHIP", "build_program", "flagship_programs",
           "ensure_virtual_devices"]

#: name -> one-line description (the CLI's --list-programs view)
FLAGSHIP = {
    "serving.mixed_step": (
        "the continuous-batching engine's ONE jitted mixed step "
        "(decode + chunked-prefill + draft-verify lanes, donated pools)"),
    "serving.decode_burst": (
        "the engine's steady-state K-iteration fused decode burst "
        "(lax.scan, donated pools)"),
    "mesh.train_step": (
        "the parallelize() DP=8 ZeRO-1 llama train step (one donated "
        "shard_map program over the 8-device mesh)"),
}


def ensure_virtual_devices(n=8):
    """Force an n-device virtual CPU backend BEFORE jax's backends
    initialize (XLA reads XLA_FLAGS at backend init, not at import —
    the same trick tests/conftest.py plays). Returns True when the
    process ends up with >= n devices; once a smaller backend has
    already initialized the flag cannot retroactively split it, and
    callers surface the mesh program's typed error instead of
    crashing. Analysis is trace-only, so the virtual backend is always
    CPU: a static check never takes the chip."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    try:
        jax.config.update("jax_platforms",
                          os.environ.get("JAX_PLATFORMS", "cpu"))
    except Exception:  # noqa: BLE001 - backend already up: just measure
        pass
    return jax.device_count() >= n


def _tiny_llama(vocab=64, hidden=32, layers=2):
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=vocab, hidden_size=hidden,
                      intermediate_size=2 * hidden,
                      num_hidden_layers=layers, num_attention_heads=2,
                      num_key_value_heads=2, max_position_embeddings=32)
    return LlamaForCausalLM(cfg)


def _serving_engine():
    from paddle_tpu.models.serving import ContinuousBatchingEngine

    return ContinuousBatchingEngine(
        _tiny_llama(), max_batch=2, max_len=32, block_size=8,
        chunk_size=8, prefix_cache=False, decode_burst=4)


def _build_mixed_step():
    import jax
    import numpy as np

    eng = _serving_engine()
    T = eng.max_step_tokens
    fn = jax.jit(eng._inner.build_mixed_step(), donate_argnums=(1,))
    args = (np.zeros((2, T), np.int32), eng._pools,
            eng._tables(), np.zeros(T, np.int32),
            np.zeros(T, bool), np.zeros(T, bool), eng._inner.weights)
    return trace(fn, args, "serving.mixed_step"), fn, args


def _build_decode_burst():
    import jax
    import numpy as np

    eng = _serving_engine()
    fn = jax.jit(eng._inner.build_decode_burst(eng.decode_burst),
                 donate_argnums=(1,))
    args = (np.zeros((2, eng.max_batch), np.int32), eng._pools,
            eng._tables(), eng._inner.weights)
    return trace(fn, args, "serving.decode_burst"), fn, args


def _build_mesh_step():
    import jax

    if jax.device_count() < 8:
        raise AnalysisError(
            "mesh.train_step needs 8 virtual devices: jax initialized "
            "before the --xla_force_host_platform_device_count=8 hook "
            "ran (run via the CLI, or import this module before jax)",
            program="mesh.train_step")
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import mesh as pmesh

    m = _tiny_llama()
    opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                parameters=m.parameters())

    def loss_fn(model, ids, labels):
        loss, _ = model(ids, labels=labels)
        return loss

    r = np.random.RandomState(0)
    ids = r.randint(0, 64, (8, 8)).astype("int64")
    labels = r.randint(0, 64, (8, 8, 1)).astype("int64")
    mp = pmesh.parallelize(m, opt, loss_fn, (ids, labels),
                           config={"dp_degree": 8,
                                   "shard_optimizer": True})
    args = (mp._pv, mp._av, mp._mv, ids, labels)
    return trace(mp._jitted, args, "mesh.train_step"), mp._jitted, args


_BUILDERS = {
    "serving.mixed_step": _build_mixed_step,
    "serving.decode_burst": _build_decode_burst,
    "mesh.train_step": _build_mesh_step,
}


def build_program(name, with_callable=False):
    """One flagship :class:`~.ir.ProgramIR` by name. With
    ``with_callable=True`` also returns ``(program, jitted, args)`` so
    callers can compile-and-measure (the estimate-vs-measured
    tolerance test)."""
    builder = _BUILDERS.get(name)
    if builder is None:
        raise AnalysisError(
            f"unknown flagship program {name!r} "
            f"(known: {sorted(_BUILDERS)})", program=name)
    try:
        program, fn, args = builder()
    except AnalysisError:
        raise
    except Exception as e:  # noqa: BLE001 - typed isolation per program
        raise AnalysisError(
            f"building flagship program '{name}' failed: "
            f"{type(e).__name__}: {e}", program=name) from e
    program.meta["description"] = FLAGSHIP[name]
    return (program, fn, args) if with_callable else program


def flagship_programs(names=None):
    """[(name, ProgramIR-or-AnalysisError)] for every requested flagship
    program — a failed build is RETURNED typed, not raised, so one
    broken program cannot hide the other two's findings."""
    out = []
    for name in (names or FLAGSHIP):
        try:
            out.append((name, build_program(name)))
        except AnalysisError as e:
            out.append((name, e))
    return out
