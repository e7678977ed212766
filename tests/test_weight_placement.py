"""The serving weights are placed, once, in the layout the compiled mixed step
reads (``ContinuousBatchingEngine._compile_step``): its first compile leaves
the weights' layouts to the compiler, and each weight it asks for in another
layout replaces the array the engine and the model's parameter held, so that
the device holds one copy of it and no program re-lays it out in a call.

The CPU's compiler keeps every layout as it is (nothing moves); it honours a
layout it is given, so a compiler that asks for q/k/v transposed, as the TPU's
does, is stood in for by asking for ``Layout((1, 0))`` on them. The gauge
``paddle_tpu_serving_weight_relayout_bytes{where}`` reads the bytes placed
(``build``) and those the programs still copy into another layout
(``program``, read from the optimized programs by ``parts.relayout_bytes``).
"""
import os
import sys
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.layout import Format, Layout

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "benchmarks"), os.path.dirname(__file__)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import paddle_tpu  # noqa: E402
from paddle_tpu import monitor  # noqa: E402
from paddle_tpu.models import serving  # noqa: E402
from paddle_tpu.monitor import parts  # noqa: E402

QKV = ("wq", "wk", "wv")
FAMILIES = ("llama", "mimo", "olmo", "ouro")


def _model(name):
    """A tiny model of the family with drawn weights: the same weights at
    every call."""
    if name == "llama":
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

        paddle_tpu.seed(0)
        model = LlamaForCausalLM(LlamaConfig(
            vocab_size=128, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=128))
        model.eval()
        return model, dict(max_batch=3, max_len=64, block_size=8,
                           chunk_size=8)
    import common
    from builders import mimo_v2_flash, olmo_hybrid, ouro

    builder, module = {"mimo": (mimo_v2_flash, "test_mimo_v2_serving"),
                       "olmo": (olmo_hybrid, "test_olmo_hybrid_serving"),
                       "ouro": (ouro, "test_ouro_serving")}[name]
    cfg = __import__(module).CFG
    model = builder.construct(cfg)
    common.load_weights(model, builder.weights(7, cfg, "float32"))
    model.eval()
    return model, cfg["engine"]


def _serve(eng):
    """Greedy tokens of a few requests that fill the lanes, drain them and
    run both programs."""
    rng = np.random.default_rng(0)
    rids = [eng.submit(rng.integers(1, 100, n).astype(np.int32),
                       max_new_tokens=m) for n, m in ((5, 9), (17, 6), (30, 11))]
    out = {}
    while eng.num_active or eng.num_pending:
        for rid, toks in eng.step():
            out[rid] = np.asarray(toks).tolist()
    return [out[r] for r in rids]


def _logits(model):
    ids = jnp.asarray(np.arange(1, 25)[None] % 100, jnp.int32)
    out = model(ids)
    return np.asarray(getattr(out, "value", out))


def _transposed_qkv(real):
    """Ask, as the TPU's compiler does, for the 2-D q/k/v weights in the
    transposed layout; every other weight as ``real`` asks."""
    def ask(weights):
        formats = real(weights)
        for fmts, layer in zip(formats["layers"], weights["layers"]):
            for key in QKV:
                if key in layer and layer[key].ndim == 2:
                    fmts[key] = Format(Layout((1, 0)), layer[key].sharding)
        return formats
    return ask


@pytest.fixture
def on():
    monitor.disable()
    monitor.reset()
    monitor.enable()
    yield
    monitor.disable()
    monitor.reset()


def _gauge():
    values = monitor.snapshot()["metrics"][
        "paddle_tpu_serving_weight_relayout_bytes"]["values"]
    return {k.split("=", 1)[1]: v for k, v in values.items()}


@pytest.mark.parametrize("name", FAMILIES)
def test_off_a_tpu_nothing_moves(on, name):
    model, engine = _model(name)
    held = {id(p.value) for p in model.parameters()}
    eng = serving.ContinuousBatchingEngine(model, **engine)
    _serve(eng)
    assert eng.weight_relayout_bytes == 0
    assert {id(p.value) for p in model.parameters()} == held
    for x in jax.tree.leaves(eng._inner.weights):
        assert x.format.layout.major_to_minor == tuple(range(x.ndim))
    assert _gauge() == {"build": 0.0, "program": 0.0}


@pytest.mark.parametrize("name", FAMILIES)
def test_a_layout_the_program_asks_for_is_placed_once(on, monkeypatch, name):
    plain_model, engine = _model(name)
    plain = _serve(serving.ContinuousBatchingEngine(plain_model, **engine))
    monkeypatch.setattr(serving, "_free_formats",
                        _transposed_qkv(serving._free_formats))
    model, _ = _model(name)
    eng = serving.ContinuousBatchingEngine(model, **engine)
    assert _serve(eng) == plain
    np.testing.assert_array_equal(_logits(model), _logits(plain_model))
    # ONE array a weight: the engine's is the model's parameter's
    values = {id(p.value) for p in model.parameters()}
    placed = [layer[k] for layer in eng._inner.layers for k in QKV
              if k in layer and layer[k].ndim == 2]
    assert placed
    for x in placed:
        assert id(x) in values
        assert x.format.layout.major_to_minor == (1, 0)
    assert eng.weight_relayout_bytes == sum(x.nbytes for x in placed)
    gauge = _gauge()
    assert gauge["build"] == eng.weight_relayout_bytes
    # the CPU reads them in its own layout, and its programs copy them back:
    # what the gauge's other half exists to see
    assert gauge["program"] > 0


def test_engines_over_one_model_share_the_placed_weights(monkeypatch):
    """Replicas over one model (a ``FleetRouter``'s) hold the one placed
    array, whichever engine placed it."""
    monkeypatch.setattr(serving, "_free_formats",
                        _transposed_qkv(serving._free_formats))
    model, engine = _model("llama")
    first = serving.ContinuousBatchingEngine(model, **engine)
    second = serving.ContinuousBatchingEngine(model, **engine)
    assert _serve(first) == _serve(second)
    q = model.llama.layers[0].self_attn.q_proj.weight.value
    assert q.format.layout.major_to_minor == (1, 0)
    assert first._inner.layers[0]["wq"] is q
    assert second._inner.layers[0]["wq"] is q
    assert second.weight_relayout_bytes == first.weight_relayout_bytes > 0


def test_replicas_first_stepped_at_once_share_the_placed_weights(monkeypatch):
    """Replicas each first stepped in a thread of their own, at the same time
    (as a ``FleetRouter`` steps them), still hold the one placed array, and
    the compile cache's switch is left as it was. Each copy is slowed, so
    that placements that did not wait for one another would overlap."""
    monkeypatch.setattr(serving, "_free_formats",
                        _transposed_qkv(serving._free_formats))
    put = jax.device_put

    def slow_put(x, device=None, *a, **k):
        if isinstance(device, Format):
            time.sleep(0.02)
        return put(x, device, *a, **k)

    monkeypatch.setattr(jax, "device_put", slow_put)
    model, engine = _model("llama")
    engines = [serving.ContinuousBatchingEngine(model, **engine)
               for _ in range(3)]
    was = jax.config.jax_enable_compilation_cache
    start = threading.Barrier(len(engines))
    served, errors = {}, []

    def first_steps(i):
        try:
            start.wait()
            served[i] = _serve(engines[i])
        except Exception as e:  # noqa: BLE001 - reported below
            errors.append(e)

    threads = [threading.Thread(target=first_steps, args=(i,))
               for i in range(len(engines))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    assert served[0] == served[1] == served[2]
    assert jax.config.jax_enable_compilation_cache == was
    for layer, attn in zip(zip(*(e._inner.layers for e in engines)),
                           (d.self_attn for d in model.llama.layers)):
        for key, proj in (("wq", attn.q_proj), ("wk", attn.k_proj),
                          ("wv", attn.v_proj)):
            held = proj.weight.value
            assert held.format.layout.major_to_minor == (1, 0)
            assert all(lay[key] is held for lay in layer)
    assert {e.weight_relayout_bytes for e in engines} == {
        engines[0].weight_relayout_bytes}


def test_the_placement_holds_with_the_compile_cache_warm(monkeypatch,
                                                        tmp_path):
    """A copy into another layout that comes back from jax's persistent
    compilation cache writes the default layout (jax 0.9.0); the placement
    compiles its copies afresh, so an engine built with the cache warm from
    an earlier one still holds, and its programs still read, the weights as
    placed."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    options = {"jax_compilation_cache_dir": str(tmp_path),
               "jax_persistent_cache_min_compile_time_secs": 0,
               "jax_persistent_cache_min_entry_size_bytes": -1,
               "jax_enable_compilation_cache": True}
    saved = {name: getattr(jax.config, name) for name in options}
    monkeypatch.setattr(serving, "_free_formats",
                        _transposed_qkv(serving._free_formats))
    try:
        for name, value in options.items():
            jax.config.update(name, value)
        cc.reset_cache()
        served = []
        for _ in range(2):
            jax.clear_caches()          # the second engine reads the cache
            model, engine = _model("llama")
            eng = serving.ContinuousBatchingEngine(model, **engine)
            served.append(_serve(eng))
            q = eng._inner.layers[0]["wq"]
            assert q.format.layout.major_to_minor == (1, 0)
            assert q is model.llama.layers[0].self_attn.q_proj.weight.value
        assert served[0] == served[1]
        assert os.listdir(tmp_path)
    finally:
        for name, value in saved.items():
            jax.config.update(name, value)
        cc.reset_cache()


def _committed(x, layout):
    return jax.device_put(x, Format(Layout(layout),
                                    jax.sharding.SingleDeviceSharding(
                                        jax.devices()[0])))


def test_relayout_bytes_reads_a_weights_copy_into_another_layout():
    h = jnp.ones((4, 64), jnp.float32)
    wq = jnp.ones((64, 32), jnp.float32)

    def step(h, w):
        return h @ w["layers"][0]["wq"]

    def loop(h, w):            # the weight is carried by a loop, as in a burst
        return lax.fori_loop(0, 3, lambda i, x: jnp.tanh(
            x @ w["layers"][0]["wq"] @ w["layers"][0]["wq"].T), h)

    for fn in (step, loop):
        moved = {"layers": [{"wq": _committed(wq, (1, 0))}]}
        kept = {"layers": [{"wq": wq}]}
        text = jax.jit(fn).lower(h, moved).compile().as_text()
        assert parts.relayout_bytes(text, "w") >= wq.nbytes
        assert parts.relayout_bytes(text, "h") == 0
        assert parts.relayout_bytes(
            jax.jit(fn).lower(h, kept).compile().as_text(), "w") == 0
    # an activation in another layout is no weight
    text = jax.jit(step).lower(_committed(h, (1, 0)),
                               {"layers": [{"wq": wq}]}).compile().as_text()
    assert parts.relayout_bytes(text, "w") == 0


# one compiled for a TPU: the weight is prefetched into another memory space
# (S(1)) in its own layout, then a slice of it copied transposed; a copy of
# an argument that is no weight (``pack``) is no weight's
TPU_TEXT = """HloModule jit_serving_decode_burst, entry_computation_layout={}

ENTRY %main.1 (pack.1: s32[2,16], w__layers___0___wq__.1: bf16[256,512]) -> bf16[512,256] {
  %pack.1 = s32[2,16]{1,0:T(2,128)} parameter(0), metadata={op_name="pack"}
  %w__layers___0___wq__.1 = bf16[256,512]{1,0:T(8,128)(2,1)} parameter(1), metadata={op_name="w[\\'layers\\'][0][\\'wq\\']"}
  %copy-start = (bf16[256,512]{1,0:T(8,128)(2,1)S(1)}, bf16[256,512]{1,0:T(8,128)(2,1)}, u32[]{:S(2)}) copy-start(%w__layers___0___wq__.1), cross_program_prefetch_index=0
  %copy-done = bf16[256,512]{1,0:T(8,128)(2,1)S(1)} copy-done(%copy-start)
  %copy-start.1 = (s32[2,16]{0,1:T(2,128)}, s32[2,16]{1,0:T(2,128)}, u32[]{:S(2)}) copy-start(%pack.1)
  %slice-start = ((bf16[256,512]{1,0:T(8,128)(2,1)S(1)}), bf16[128,512]{1,0:T(8,128)(2,1)}, s32[]{:S(2)}) slice-start(%copy-done), slice={[0:128], [0:512]}
  %slice-done = bf16[128,512]{1,0:T(8,128)(2,1)} slice-done(%slice-start)
  %bitcast = bf16[512,128]{0,1:T(8,128)(2,1)} bitcast(%slice-done)
  ROOT %copy = bf16[512,128]{1,0:T(8,128)(2,1)} copy(%bitcast)
}
"""


def test_relayout_bytes_follows_a_weight_through_what_only_moves_it():
    assert parts.relayout_bytes(TPU_TEXT, "w") == 512 * 128 * 2
    assert parts.relayout_bytes(TPU_TEXT, "pack") == 2 * 16 * 4
    prefetch_only = TPU_TEXT.replace(
        "bf16[512,128]{1,0:T(8,128)(2,1)} copy(", "bf16[512,128]{0,1:T(8,128)"
        "(2,1)S(1)} copy(")
    assert parts.relayout_bytes(prefetch_only, "w") == 0
