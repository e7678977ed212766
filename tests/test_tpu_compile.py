"""The main path's kernels compile for a described TPU v5e, at real widths.

No chip is needed: the TPU compiler is installed and compiles for a topology
that is described, not attached (on-chip-measurement guide, section 2.3). What
interpret mode cannot show — a slice the tiling refuses, more VMEM than a
kernel may use, an operand the compiler pads past HBM — fails here, at no chip
time. A compile that passes is not a chip run.

All of these live in ONE file, and the topology is described only inside the
module-scoped fixture: one process at a time may load the chip's library, so
nothing here touches it at import, in ``parametrize`` or in ``skipif``.
"""
import os
import sys

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

import paddle_tpu  # noqa: F401
from paddle_tpu.incubate.distributed.models.moe import held_experts
from paddle_tpu.models import paged_kv
from paddle_tpu.ops.pallas import gated_delta_rule, grouped_matmul
from paddle_tpu.ops.pallas.flash_attention import flash_attention_fwd
from paddle_tpu.ops.pallas.paged_attention import (paged_attention,
                                                   paged_attention_gqa)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here: skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic(monkeypatch):
    """The kernels ask jax.devices() whether to interpret, and see the CPU
    here: steer them to the real lowering (through sys.modules — the package
    re-exports a function under the module's name)."""
    for name in ("flash_attention", "paged_attention", "grouped_matmul",
                 "gated_delta_rule"):
        mod = sys.modules["paddle_tpu.ops.pallas." + name]
        monkeypatch.setattr(mod, "_interpret", lambda: False)


FLASH_SHAPES = [
    pytest.param(1, 2048, 32, 32, 128, id="S2048-mha-D128"),   # chip_smoke
    pytest.param(1, 4096, 32, 8, 128, id="S4096-gqa32:8-D128"),
    pytest.param(1, 2048, 32, 32, 64, id="S2048-mha-D64"),
]


def _qkv(one_chip, B, S, Hq, Hkv, D):
    q = jax.ShapeDtypeStruct((B, S, Hq, D), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((B, S, Hkv, D), jnp.bfloat16, sharding=one_chip)
    return q, kv, kv


@pytest.mark.parametrize("B,S,Hq,Hkv,D", FLASH_SHAPES)
def test_flash_forward_compiles_for_v5e(one_chip, mosaic, B, S, Hq, Hkv, D):
    fwd = jax.jit(lambda q, k, v: flash_attention_fwd(q, k, v, causal=True))
    hlo = fwd.lower(*_qkv(one_chip, B, S, Hq, Hkv, D)).compile().as_text()
    assert hlo.count("custom_call_target=\"tpu_custom_call\"") == 1


@pytest.mark.parametrize("B,S,Hq,Hkv,D", FLASH_SHAPES)
def test_flash_backward_compiles_for_v5e(one_chip, mosaic, B, S, Hq, Hkv, D):
    """jax.grad adds the dq and the dk/dv kernels to the forward one."""
    def loss(q, k, v):
        out = flash_attention_fwd(q, k, v, causal=True)
        return out.astype(jnp.float32).sum()

    bwd = jax.jit(jax.grad(loss, (0, 1, 2)))
    hlo = bwd.lower(*_qkv(one_chip, B, S, Hq, Hkv, D)).compile().as_text()
    assert hlo.count("custom_call_target=\"tpu_custom_call\"") == 3


def test_flash_backward_is_refused_past_its_vmem(one_chip, mosaic):
    """The kernels keep whole (Sk, D) K and V blocks in VMEM: at D128 the
    backward stops compiling between Sk 4096 and 8192. When a tiled kernel
    lifts that, this test is the one to turn around."""
    def loss(q, k, v):
        out = flash_attention_fwd(q, k, v, causal=True)
        return out.astype(jnp.float32).sum()

    bwd = jax.jit(jax.grad(loss, (0, 1, 2)))
    with pytest.raises(Exception, match="vmem"):
        bwd.lower(*_qkv(one_chip, 1, 8192, 32, 8, 128)).compile()


def _paged_shapes(one_chip, T, width, bs, n_q, kv, D, nb):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return (sds((T, n_q, D), jnp.bfloat16), sds((nb, bs, kv, D), jnp.bfloat16),
            sds((nb, bs, kv, D), jnp.bfloat16), sds((T, width), jnp.int32),
            sds((T,), jnp.int32))


@pytest.mark.parametrize("n_q,kv", [
    pytest.param(32, 32, id="mha"),            # deepseek-7b-serve-offline
    pytest.param(32, 8, id="gqa32:8"),         # Mistral-7B's heads
])
def test_paged_attention_kernel_compiles_for_v5e(one_chip, mosaic, n_q, kv):
    """The serving programs' attention at the serve cell's shape: 144 lanes
    (max_batch 16 + chunk 128), table 16 x block 64, head dim 128, a pool of
    257 bf16 blocks. ONE kernel, and no gathered copy of the cache beside
    it: the plain path below asks for 3.8 GB of temporaries here."""
    compiled = jax.jit(paged_attention).lower(
        *_paged_shapes(one_chip, 144, 16, 64, n_q, kv, 128, 257)).compile()
    assert compiled.as_text().count(
        "custom_call_target=\"tpu_custom_call\"") == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 256 * 2 ** 20


@pytest.mark.parametrize("kv,blocks,window,sink", [
    pytest.param(4, 8193, None, False, id="full"),
    pytest.param(8, 262, 128, True, id="window"),
])
def test_paged_attention_gqa_kernel_compiles_for_v5e(one_chip, mosaic, kv,
                                                     blocks, window, sink):
    """The grouped-query kernel at the MiMo-V2-Flash cell's shapes: 320 lanes
    (max_batch 64 + chunk 256), 64 query heads of 192 against flat pools of
    K rows kv x 192 and V rows kv x 128, block 64, a table of 128 blocks
    (max_len 8192; 160 KB of scalars), full (4 KV heads, the whole-length
    pool) and window (8 KV heads, window 128, a sink logit a head)."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = [sds((320, 64, 192), jnp.bfloat16),
            sds((blocks, 64, kv * 192), jnp.bfloat16),
            sds((blocks, 64, kv * 128), jnp.bfloat16),
            sds((320, 128), jnp.int32), sds((320,), jnp.int32)]
    if sink:
        args.append(sds((64,), jnp.bfloat16))

    def attend(q, k, v, tables, pos, sk=None):
        return paged_attention_gqa(q, k, v, tables, pos, None, window, sk)

    compiled = jax.jit(attend).lower(*args).compile()
    assert compiled.as_text().count(
        "custom_call_target=\"tpu_custom_call\"") == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 256 * 2 ** 20


@pytest.mark.parametrize("n_q,kv", [
    pytest.param(32, 32, id="mha"),            # deepseek-7b-serve-offline
    pytest.param(32, 8, id="gqa32:8"),         # Mistral-7B's heads
])
def test_paged_attention_tiles_compile_for_v5e(one_chip, mosaic, n_q, kv):
    """The mixed step's call (ISSUE 33): told which lanes share a table row,
    the per-lane kernel over an ORDER of lanes (a grid as long as the order)
    and the query-tile kernel beside it (strided per-head loads from a
    bfloat16 block's 32-bit words, float32 products, a tile of 128 queries
    a head), and nothing gathered."""
    shapes = _paged_shapes(one_chip, 144, 16, 64, n_q, kv, 128, 257)
    rows = jax.ShapeDtypeStruct((144,), jnp.int32, sharding=one_chip)
    compiled = jax.jit(
        lambda q, k, v, t, p, r: paged_attention(q, k, v, t, p, rows=r)
    ).lower(*shapes, rows).compile()
    assert compiled.as_text().count(
        "custom_call_target=\"tpu_custom_call\"") == 2
    assert compiled.memory_analysis().temp_size_in_bytes < 256 * 2 ** 20


@pytest.mark.parametrize("kv,blocks,window,sink", [
    pytest.param(4, 8193, None, False, id="full"),
    pytest.param(8, 262, 128, True, id="window"),
])
def test_paged_attention_gqa_tiles_compile_for_v5e(one_chip, mosaic, kv,
                                                   blocks, window, sink):
    """The grouped-query kernels of a mixed step at the MiMo-V2-Flash cell's
    shapes: tiles of 32 lanes x 64 query heads, each KV head's product over
    the 128-aligned stretch of the merged K row that holds its 192 lanes."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = [sds((320, 64, 192), jnp.bfloat16),
            sds((blocks, 64, kv * 192), jnp.bfloat16),
            sds((blocks, 64, kv * 128), jnp.bfloat16),
            sds((320, 128), jnp.int32), sds((320,), jnp.int32),
            sds((320,), jnp.int32)]
    if sink:
        args.append(sds((64,), jnp.bfloat16))

    def attend(q, k, v, tables, pos, rows, sk=None):
        return paged_attention_gqa(q, k, v, tables, pos, None, window, sk,
                                   rows=rows)

    compiled = jax.jit(attend).lower(*args).compile()
    assert compiled.as_text().count(
        "custom_call_target=\"tpu_custom_call\"") == 2
    assert compiled.memory_analysis().temp_size_in_bytes < 256 * 2 ** 20


@pytest.mark.parametrize("lanes", [
    pytest.param(320, id="mixed-step-320-lanes"),
    pytest.param(64, id="burst-64-lanes"),
])
def test_grouped_matmul_kernels_compile_for_v5e(one_chip, mosaic, monkeypatch,
                                                lanes):
    """The held experts' layer at the MiMo-V2-Flash cell's shapes, the rule
    answering as on a TPU: ``lanes`` tokens routed top-8 over 256 experts, 16
    of them held, of hidden 4096 x width 2048 in bfloat16. TWO kernels (gate
    and up fused with the SwiGLU, then down) compile inside the VMEM they ask
    for, with the tokens' activations and output panel held beside the weight
    panels; the rows enter and leave the kernels by token, so the layer's
    program holds no gather of padded rows (2,800 at 320 lanes) and no
    scatter of rows of the hidden size, and nothing of the experts' size
    beside the kernels."""
    monkeypatch.setattr(held_experts, "_kernel_applies", lambda *a: True)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def layer(h, router, bias, w1, w3, w2):
        return held_experts.held_experts_mlp(h, router, bias, w1, w3, w2,
                                             jnp.int32(32), 8)

    compiled = jax.jit(layer).lower(
        sds((lanes, 4096), jnp.bfloat16), sds((4096, 256), jnp.bfloat16),
        sds((256,), jnp.bfloat16), sds((16, 4096, 2048), jnp.bfloat16),
        sds((16, 4096, 2048), jnp.bfloat16),
        sds((16, 2048, 4096), jnp.bfloat16)).compile()
    hlo = compiled.as_text()
    assert hlo.count("custom_call_target=\"tpu_custom_call\"") == 2
    assert "held_experts_gmm_up" in hlo and "held_experts_gmm_down" in hlo
    padded = grouped_matmul.padded_rows(lanes * 8, 16, 16)
    ops = [line for line in hlo.splitlines() if " = " in line]
    assert not [op for op in ops
                if f"[{padded},4096]" in op.split(" = ")[1].split(" ")[0]]
    assert not [op for op in ops if " scatter(" in op and ",4096]" in op]
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2 ** 20


def test_mixed_step_of_an_expert_model_holds_the_grouped_kernels(
        one_chip, mosaic, monkeypatch):
    """The compiled mixed step of a tiny lane-aligned expert model (hidden
    128, expert width 128, float32), the rule answering as on a TPU: two
    ``held_experts_gmm`` custom calls an expert layer and no ``ragged-dot``
    left; with the rule answering as elsewhere, no kernel (at these widths
    the compiler unrolls ``ragged_dot`` into plain products: the
    ``%ragged-dot-none`` custom call is the real widths')."""
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    for path in (os.path.dirname(bench), bench):
        if path not in sys.path:
            sys.path.insert(0, path)
    import common
    from builders import mimo_v2_flash as B

    from paddle_tpu.models.serving import ContinuousBatchingEngine
    from test_grouped_matmul import CFG

    model = B.construct(CFG)
    common.load_weights(model, B.weights(11, CFG, "float32"))
    model.eval()
    eng = ContinuousBatchingEngine(model, **CFG["engine"])
    lanes = eng.max_step_tokens

    def shapes(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (sds((2, lanes), jnp.int32), shapes(eng._pools),
            shapes(eng._tables()), sds((lanes,), jnp.int32),
            sds((lanes,), jnp.bool_), sds((lanes,), jnp.bool_),
            shapes(eng._inner.weights))

    def hlo():
        # a new function each time: jit's cache knows nothing of the rule
        return jax.jit(eng._inner.build_mixed_step()).lower(
            *args).compile().as_text()

    plain = hlo()
    assert "held_experts_gmm" not in plain and "tpu_custom_call" not in plain
    monkeypatch.setattr(held_experts, "_kernel_applies", lambda *a: True)
    kernels = hlo()
    layers = sum("router" in p for p in eng._inner.layers)
    assert layers == 2
    assert kernels.count("custom_call_target=\"tpu_custom_call\"") == \
        2 * layers
    assert kernels.count("held_experts_gmm_up") >= layers
    assert kernels.count("held_experts_gmm_down") >= layers
    # (the instructions alone: the text's table of stack frames names every
    # function a cached trace came through, a test of tests/
    # test_grouped_matmul.py named ..._against_ragged_dot among them when
    # that file ran before in this process)
    ops = "\n".join(line for line in kernels.splitlines() if " = " in line)
    assert "held_experts_gmm_up" in ops
    assert "ragged-dot" not in ops and "ragged_dot" not in ops


@pytest.mark.parametrize("lanes,planned", [(32, False), (288, True)],
                         ids=["burst-32-lanes", "mixed-step-288-lanes"])
def test_gated_delta_kernels_compile_for_v5e(one_chip, mosaic, monkeypatch,
                                             lanes, planned):
    """The recurrent layers' kernels at the Olmo-Hybrid cell's shapes: 30
    heads of 96 x 192 (pairs of heads fill 384 lanes), 33 slots. A burst's 32
    lanes run ``gated_delta_step`` alone; a mixed step's 288 lanes are planned
    inside the program (data: rows, positions, valid) and run both kernels,
    ``gated_delta_chunk`` over a dynamic number of chunks."""
    monkeypatch.setattr(gated_delta_rule, "_kernel_applies",
                        lambda q, v, s: True)
    H, dk, dv, slots = 30, 96, 192, 33

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def run(q, k, v, g, beta, state, positions, rows, valid):
        plan = gated_delta_rule.plan_runs(rows, positions, valid, slots) \
            if planned else None
        return gated_delta_rule.gated_delta(q, k, v, g, beta, state,
                                            positions, plan)

    hlo = jax.jit(run).lower(
        sds((lanes, H, dk)), sds((lanes, H, dk)), sds((lanes, H, dv)),
        sds((lanes, H)), sds((lanes, H)), sds((slots, H // 2, dk, 2 * dv)),
        sds((lanes,), jnp.int32), sds((lanes,), jnp.int32),
        sds((lanes,), jnp.bool_)).compile().as_text()
    assert hlo.count("custom_call_target=\"tpu_custom_call\"") == 1 + planned
    assert "gated_delta_step" in hlo
    assert ("gated_delta_chunk" in hlo) == planned


@pytest.mark.parametrize("lanes,planned", [(128, False), (384, True)],
                         ids=["burst-128-lanes", "mixed-step-384-lanes"])
def test_kda_kernels_compile_for_v5e(one_chip, mosaic, monkeypatch, lanes,
                                     planned):
    """The KDA layers' kernels at the Solar-Open2 cell's shapes: 64 heads of
    128 x 128 (one head fills a 128-lane row: no pairs), a decay a key
    channel, 129 slots. A burst's 128 lanes run ``kda_step`` alone; a mixed
    step's 384 lanes are planned inside the program and run both kernels,
    ``kda_chunk`` over a dynamic number of chunks; neither is the scalar
    decay's kernel."""
    monkeypatch.setattr(gated_delta_rule, "_kernel_applies",
                        lambda q, v, s: True)
    H, d, slots = 64, 128, 129
    assert gated_delta_rule.pack_of(H, d) == 1

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def run(q, k, v, g, beta, state, positions, rows, valid):
        plan = gated_delta_rule.plan_runs(rows, positions, valid, slots) \
            if planned else None
        return gated_delta_rule.gated_delta(q, k, v, g, beta, state,
                                            positions, plan)

    hlo = jax.jit(run).lower(
        sds((lanes, H, d)), sds((lanes, H, d)), sds((lanes, H, d)),
        sds((lanes, H, d)), sds((lanes, H)), sds((slots, H, d, d)),
        sds((lanes,), jnp.int32), sds((lanes,), jnp.int32),
        sds((lanes,), jnp.bool_)).compile().as_text()
    assert hlo.count("custom_call_target=\"tpu_custom_call\"") == 1 + planned
    assert "kda_step" in hlo and ("kda_chunk" in hlo) == planned
    assert "gated_delta_step" not in hlo and "gated_delta_chunk" not in hlo


def test_a_looped_stacks_programs_hold_each_layers_kernels_once(
        one_chip, mosaic, monkeypatch):
    """The compiled mixed step and burst of a tiny lane-aligned looped model
    (2 layers of 2 heads of 128, run 4 times a token, bfloat16), the rule
    answering as on a TPU: as many ``paged_attention`` custom calls as ONE
    pass has (the lane kernel and the tile kernel a layer in a mixed step,
    the lane kernel in a burst), the passes a loop around them; the pools
    hold every pass and the compile's scratch stays small."""
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    for path in (os.path.dirname(bench), bench):
        if path not in sys.path:
            sys.path.insert(0, path)
    from builders import ouro as B

    from paddle_tpu.models.serving import ContinuousBatchingEngine

    real = paged_kv._kernel_applies

    def as_on_a_tpu(*args):
        devices = jax.devices
        jax.devices = lambda *a: [type("Tpu", (), {"platform": "tpu"})()]
        try:
            return real(*args)
        finally:
            jax.devices = devices

    monkeypatch.setattr(paged_kv, "_kernel_applies", as_on_a_tpu)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def shapes(tree):
        return jax.tree.map(lambda a: sds(a.shape, a.dtype), tree)

    def programs(passes):
        cfg = dict(vocab_size=256, hidden_size=256, intermediate_size=256,
                   num_hidden_layers=2, num_attention_heads=2,
                   num_key_value_heads=2, head_dim=128, rope_theta=1e6,
                   rms_norm_eps=1e-6, total_ut_steps=passes,
                   early_exit_threshold=1, max_position_embeddings=256,
                   model={"dtype": "bfloat16"})
        model = B.construct(cfg)
        model.eval()
        eng = ContinuousBatchingEngine(model, max_batch=4, max_len=128,
                                       block_size=16, chunk_size=32)
        lanes = eng.max_step_tokens
        mixed = jax.jit(eng._inner.build_mixed_step()).lower(
            sds((2, lanes), jnp.int32), shapes(eng._pools),
            shapes(eng._tables()), sds((lanes,), jnp.int32),
            sds((lanes,), jnp.bool_), sds((lanes,), jnp.bool_),
            shapes(eng._inner.weights)).compile()
        burst = jax.jit(eng._inner.build_decode_burst(4)).lower(
            sds((2, 4), jnp.int32), shapes(eng._pools), shapes(eng._tables()),
            shapes(eng._inner.weights)).compile()
        return eng, mixed, burst

    calls = 'custom_call_target="tpu_custom_call"'
    eng, mixed, burst = programs(4)
    _, mixed_once, burst_once = programs(1)
    assert eng._pools[0][0].shape == (4 * (4 * 8 + 1), 16, 2, 128)
    assert mixed.as_text().count(calls) == mixed_once.as_text().count(calls) \
        == 2 * 2
    assert burst.as_text().count(calls) == burst_once.as_text().count(calls) \
        == 2
    assert mixed.memory_analysis().temp_size_in_bytes < 64 * 2 ** 20


def _as_on_a_tpu(rule):
    """A kernel rule that asks ``jax.devices()`` answering as on a TPU."""
    def applies(*args):
        devices = jax.devices
        jax.devices = lambda *a: [type("Tpu", (), {"platform": "tpu"})()]
        try:
            return rule(*args)
        finally:
            jax.devices = devices
    return applies


# (configuration, layers, whether the mixed step asks for a weight in another
# layout than the chip's default for its shape): a few layers of each served
# configuration at its published widths. Olmo-Hybrid's linear q/k
# ([3840, 2880]) lie transposed by default already: 2880 is no multiple of 128
SERVED = [pytest.param("deepseek-llm-7b", 2, True, id="llama-deepseek"),
          pytest.param("mimo-v2-flash", 2, True, id="mimo"),
          pytest.param("olmo-hybrid-7b", 4, False, id="olmo-3-linear-1-full"),
          pytest.param("ouro-2.6b", 2, True, id="ouro-4-passes")]


@pytest.mark.parametrize("config,layers,moves", SERVED)
def test_the_serving_programs_read_the_weights_as_placed(
        one_chip, mosaic, monkeypatch, config, layers, moves):
    """The mixed step and the burst of each served configuration (its
    published widths, a few layers, a vocabulary of 1024, the cell's lanes),
    compiled for a described v5e as the engine compiles them: the mixed step
    with the weights' layouts left to the compiler
    (``serving._free_formats``), the burst against the weights in the
    formats the mixed step chose (as ``place_weights`` puts them). Neither
    copies a weight into another layout (``parts.relayout_bytes``), and the
    burst reads every weight in the format the mixed step chose. Against the
    weights in the chip's default layouts, the burst copies every weight the
    placement moves (q/k, and Llama's and Ouro's v), in every call."""
    import json

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    for path in (os.path.dirname(bench), bench):
        if path not in sys.path:
            sys.path.insert(0, path)
    import importlib

    from jax.experimental.layout import Format

    from paddle_tpu.models import serving
    from paddle_tpu.monitor import parts

    monkeypatch.setattr(paged_kv, "_kernel_applies",
                        _as_on_a_tpu(paged_kv._kernel_applies))
    monkeypatch.setattr(held_experts, "_kernel_applies",
                        _as_on_a_tpu(held_experts._kernel_applies))
    monkeypatch.setattr(gated_delta_rule, "_kernel_applies",
                        lambda q, v, s: True)
    with open(os.path.join(bench, "configs", config + ".json")) as f:
        cfg = json.load(f)
    cfg.update(num_hidden_layers=layers, vocab_size=1024,
               initializer_range=0.0)
    model = importlib.import_module("builders." + cfg["builder"]).construct(
        cfg)
    model.eval()
    eng = serving.ContinuousBatchingEngine(
        model, **dict(cfg["engine"], max_len=256))

    def sds(shape, dtype, sharding=one_chip):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def shapes(tree):
        return jax.tree.map(lambda a: sds(a.shape, a.dtype), tree)

    T, B, weights = eng.max_step_tokens, eng.max_batch, eng._inner.weights
    held = shapes(weights)
    args = (sds((2, T), jnp.int32), shapes(eng._pools), shapes(eng._tables()),
            sds((T,), jnp.int32), sds((T,), jnp.bool_), sds((T,), jnp.bool_))
    mixed = jax.jit(eng._inner.build_mixed_step(), donate_argnums=(1,),
                    in_shardings=(None,) * len(args)
                    + (serving._free_formats(held),)).lower(
                        *args, held).compile()
    chosen = mixed.input_formats[0][-1]
    placed = jax.tree.map(   # (a weight no program reads has no layout)
        lambda a, fmt: a if fmt.layout is None else sds(a.shape, a.dtype, fmt),
        held, chosen)

    def burst(w):
        return jax.jit(eng._inner.build_decode_burst(eng.decode_burst),
                       donate_argnums=(1,)).lower(
            sds((2, B), jnp.int32), shapes(eng._pools), shapes(eng._tables()),
            w).compile()

    after, before = burst(placed), burst(held)
    assert parts.relayout_bytes(mixed.as_text(), "w") == 0
    assert parts.relayout_bytes(after.as_text(), "w") == 0
    assert jax.tree.leaves(after.input_formats[0][-1]) == \
        jax.tree.leaves(chosen)
    default = jax.tree.leaves(before.input_formats[0][-1])
    moved = [(a, fmt) for a, fmt, was in zip(jax.tree.leaves(held),
                                             jax.tree.leaves(chosen), default)
             if isinstance(fmt, Format) and fmt.layout != was.layout]
    assert bool(moved) == moves
    assert parts.relayout_bytes(before.as_text(), "w") == sum(
        a.size * a.dtype.itemsize for a, _ in moved)


def test_paged_attention_refuses_pools_whose_heads_fill_no_sublane_tile():
    """30 KV heads on a [blocks, block, heads, 128] pool: the one-row-a-head
    kernel's slices are refused by the compiler ("must be aligned to tiling
    (8)"), so the rule sends such a pool to the plain path (and a model with
    30 heads keeps flat pools, which the grouped-query kernel reads)."""
    q = jax.ShapeDtypeStruct((8, 30, 128), jnp.bfloat16)
    pool = jax.ShapeDtypeStruct((9, 64, 30, 128), jnp.bfloat16)
    ok = jax.ShapeDtypeStruct((9, 64, 32, 128), jnp.bfloat16)
    flat = jax.ShapeDtypeStruct((9, 64, 30 * 128), jnp.bfloat16)
    real = jax.devices

    class _Tpu:
        platform = "tpu"

    try:
        jax.devices = lambda *a: [_Tpu()]
        assert not paged_kv._kernel_applies(q, pool)
        assert paged_kv._kernel_applies(q, ok)
        for heads, fine in ((1, False), (2, True), (4, True), (6, False),
                            (8, True), (12, False), (16, True)):
            small = jax.ShapeDtypeStruct((9, 64, heads, 128), jnp.bfloat16)
            assert paged_kv._kernel_applies(q, small) == fine, heads
        assert paged_kv._kernel_applies(q, flat, flat)
    finally:
        jax.devices = real


def test_paged_decode_attention_fits_the_mixed_step(one_chip):
    """The PLAIN path (the kernel's reference, and what runs where the kernel
    does not apply) at chip_smoke's shape: 136 lanes (max_batch 8 + chunk
    128) x max_len 1024 x 32 kv heads x 128. Written as einsums the compiler
    padded the one-row matmuls to 8 sublanes and asked for 18 GB; as
    multiply + reduce it stays under a quarter of the HBM."""
    compiled = jax.jit(paged_kv.paged_attention_decode_plain).lower(
        *_paged_shapes(one_chip, 136, 16, 64, 32, 32, 128, 129)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 2 ** 30


def test_no_module_describes_the_topology_at_import():
    """get_topology_desc loads the chip's library; only a fixture or a test
    of THIS file may call it (see the module docstring)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    hits = []
    for sub in ("paddle_tpu", "tests", "tools"):
        for dirpath, _dirs, files in os.walk(os.path.join(root, sub)):
            for name in files:
                path = os.path.join(dirpath, name)
                if name.endswith(".py") and path != os.path.abspath(__file__):
                    with open(path, encoding="utf-8") as f:
                        if "get_topology_desc" in f.read():
                            hits.append(os.path.relpath(path, root))
    assert hits == []
