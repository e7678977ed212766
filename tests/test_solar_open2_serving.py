"""Solar-Open2-shaped serving at a small size on the CPU: the model's forward
pass and ``ContinuousBatchingEngine`` (chunked prefill with a chunk that
divides no prompt, mixed steps, bursts, slots that serve several requests)
against the benchmark's plain float32 reference, on logits; one chip's share
of the expert layer against the uncut layer; planted faults; the scheduler's
counters.

Every width is scaled down with its ratios kept: one GQA layer to three KDA
layers, twice, 4 query heads to 2 KV heads without rotary, KDA heads as wide
as the GQA heads, a convolution of kernel 4, an expert layer in every layer
with a shared expert as wide as a routed one, 8 of 32 routed experts held
with top-8 routing (a quarter of the router: a token's pairs fall on held
and absent experts alike), norms drawn away from 1.

Tolerance. The model and the reference are float32 with the same weights
(the reference rounds its leaves to the served dtype, float32 here). They
differ by the order of float32 sums (the chunked form against single steps,
attention over blocks against whole rows, grouped products against a scan
over experts): 2e-3 on logits of size ~4 is what 8 pre-normed layers make of
1e-5 a product; each planted fault (a gate dropped, a decay a head in place
of a decay a channel) moves logits by 0.1 or more.
"""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "benchmarks")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import common  # noqa: E402  (benchmarks/)
from builders import solar_open2 as B  # noqa: E402
from reference import solar_open2 as R  # noqa: E402

from paddle_tpu import monitor  # noqa: E402
from paddle_tpu.models import linear_attention as L  # noqa: E402
from paddle_tpu.models.serving import ContinuousBatchingEngine  # noqa: E402
from paddle_tpu.models.solar_open2 import SolarOpen2DecodeEngine  # noqa: E402

SEED = 11
TOL = 2e-3
HELD, ROUTED, CHIPS = 8, 32, 4
CFG = dict(
    vocab_size=128, hidden_size=64, intermediate_size=96,
    moe_intermediate_size=32, num_hidden_layers=8, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16,
    linear_attn_config={"short_conv_kernel_size": 4, "head_dim": 16,
                        "num_heads": 4, "num_kv_heads": None},
    gqa_layers=[0, 4, 8], use_gqa_gate=True, use_rope=False,
    partial_rotary_factor=1, rope_theta=10000, kda_use_full_proj=False,
    kda_allow_neg_eigval=True, n_routed_experts=HELD,
    published={"n_routed_experts": ROUTED}, n_shared_experts=1,
    num_experts_per_tok=8, norm_topk_prob=True, routed_scaling_factor=1,
    first_k_dense_replace=0, rms_norm_eps=1e-5, max_position_embeddings=512,
    initializer_range=0.1, torch_dtype="float32", model={"dtype": "float32"},
    # a chunk of 20 divides no prompt below, and is no multiple of the block
    engine=dict(max_batch=3, block_size=8, chunk_size=20, max_len=256,
                prefix_cache=False))
# (prompt length, tokens asked): longer than a chunk of the recurrence (64),
# shorter than a block, and enough at once that slots are taken over
REQUESTS = [(50, 20), (9, 30), (170, 12), (33, 40), (100, 20), (21, 9),
            (1, 5)]

_MODEL = []


@pytest.fixture(autouse=True, scope="module")
def _model():
    model = B.construct(CFG)
    common.load_weights(model, B.weights(SEED, CFG, "float32"))
    model.eval()
    _MODEL.append(model)
    yield
    _MODEL.clear()


@pytest.fixture(scope="module")
def reference():
    return R.ServeReference(SEED, CFG)


def _engine(**over):
    return ContinuousBatchingEngine(_MODEL[0], **{**CFG["engine"], **over})


def _prompts():
    rng = np.random.default_rng(0)
    return [(rng.integers(0, CFG["vocab_size"], n, dtype=np.int32), m)
            for n, m in REQUESTS]


def _serve(eng, prompts):
    rids = [eng.submit(p, max_new_tokens=m) for p, m in prompts]
    done, kinds = {}, []
    while eng.num_active or eng.num_pending:
        for rid, toks in eng.step():
            done[rid] = np.asarray(toks, np.int32)
        kinds.append(eng._step_kind)
    return [done[r] for r in rids], kinds


def _worst_gap(reference, prompts, answers):
    """The widest gap by which a served token's logit lies below the
    reference's best, over every served token of every request. Each
    sequence is padded to the engine's ``max_len`` (every layer is causal:
    what lies behind a position changes nothing before it), so that the
    reference compiles once."""
    worst = 0.0
    for (p, m), toks in zip(prompts, answers):
        assert len(toks) == m
        seq = np.concatenate([p, toks[:-1]])
        logits = np.asarray(reference.logits(np.pad(
            seq, (0, CFG["engine"]["max_len"] - len(seq)))))
        rows = logits[len(p) - 1:len(seq)]
        worst = max(worst, float(
            (rows.max(-1) - rows[np.arange(m), toks]).max()))
    return worst


def test_the_layers_are_told_apart_by_their_kinds_and_weights():
    e = _engine()._inner
    full, kda = e.kinds
    assert e.layer_kind == (0, 1, 1, 1) * 2
    assert (full.num_kv, full.head_dim, full.rotary_dim, full.flat) == \
        (2, 16, 0, True)
    assert (kda.name, kda.num_heads, kda.key_dim, kda.value_dim,
            kda.conv_kernel, kda.neg_eigval) == ("kda", 4, 16, 16, 4, True)
    gqa, lin = e.layers[0], e.layers[1]
    assert {"attn_gate", "ln1", "router", "shared_gate"} <= set(gqa)
    assert {"wfa", "wfb", "wga", "wgb", "ln1", "shared_gate"} <= set(lin)
    assert not {"wa", "wz", "attn_gate"} & set(lin)
    assert lin["dt_bias"].shape == (64,) and lin["A_log"].shape == (4,)
    assert gqa["w1"].shape == (HELD, 64, 32)
    assert gqa["router"].shape == (64, ROUTED)


def test_the_forward_pass_is_the_reference(reference):
    rng = np.random.default_rng(1)
    ids = rng.integers(0, CFG["vocab_size"], (2, 150), dtype=np.int32)
    logits = np.asarray(_MODEL[0](ids).value)
    for b in range(2):
        want = np.asarray(reference.logits(ids[b]))
        assert np.abs(logits[b] - want).max() < TOL


def test_the_engine_serves_the_references_tokens(reference):
    """Chunked prefill (20 divides no prompt), decode lanes beside chunks,
    several runs in a step, slots taken over by later requests, and bursts
    (expert layers inside them)."""
    prompts = _prompts()
    answers, kinds = _serve(_engine(decode_burst=4), prompts)
    assert {"burst", "mixed"} <= set(kinds)
    assert _worst_gap(reference, prompts, answers) < TOL


def test_the_chips_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """The uncut layer (all 32 experts held, the reference's expert layer)
    against the program's expert layer on each of the 4 chips that share it
    (8 experts each): the 4 routed parts and the shared expert, counted once
    (each chip computes it alike), add up to the uncut layer's output."""
    uncut = dict(CFG, n_routed_experts=ROUTED, published={})
    made = B.weights(SEED, uncut, "float32")
    pre = "model.layers.1."
    p = {n[len(pre):]: np.asarray(v) for n, v in made.items()
         if n.startswith(pre)}
    h = jax.random.normal(jax.random.PRNGKey(2), (40, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(R.moe(h, p, uncut, None))
        total = np.zeros_like(want)
        for chip in range(CHIPS):
            lo = chip * HELD
            # the serving block of the chip that holds experts lo .. lo + 7
            eng = SolarOpen2DecodeEngine.__new__(SolarOpen2DecodeEngine)
            eng.held_lo, eng.top_k, eng.eps = \
                lo, CFG["num_experts_per_tok"], CFG["rms_norm_eps"]
            layer = {"wo": jnp.zeros((16, 64)),
                     "router": p["mlp.gate.weight"],
                     "router_bias": p["mlp.gate.e_score_correction_bias"],
                     "w1": p["mlp.experts.gate_proj"][lo:lo + HELD],
                     "w3": p["mlp.experts.up_proj"][lo:lo + HELD],
                     "w2": p["mlp.experts.down_proj"][lo:lo + HELD]}
            if chip == 0:       # the shared expert, counted once
                s = "mlp.shared_experts."
                layer.update(shared_gate=p[s + "gate_proj.weight"],
                             shared_up=p[s + "up_proj.weight"],
                             shared_down=p[s + "down_proj.weight"])
            x = h[None]
            out, pairs = eng._post_attn(layer, x, jnp.zeros((1, 40, 16)))
            total += np.asarray(out - x)[0]
            # each chip routes all 40 tokens over all 32 experts
            assert int(pairs[1]) == 40 * 8
    assert np.abs(total - want).max() < 1e-4 * np.abs(want).max()
    # one chip's share alone is not the layer
    assert np.abs(total - want).max() * 100 < np.abs(
        np.asarray(out - x)[0] - want).max()


# -- planted faults: each has to move the served logits past the tolerance ----
def _attention_gate_dropped(eng, monkeypatch):
    for p in eng._inner.layers:
        p.pop("attn_gate", None)


def _kda_output_gate_dropped(eng, monkeypatch):
    monkeypatch.setattr(L, "_gate", lambda p, x, T, H, dv: jnp.ones(
        (T, H, dv), jnp.float32))


def _a_decay_a_head(eng, monkeypatch):
    real = L._decay
    monkeypatch.setattr(L, "_decay", lambda p, x, T, H, dk: real(
        p, x, T, H, dk).mean(-1))


@pytest.mark.parametrize("fault", [_attention_gate_dropped,
                                   _kda_output_gate_dropped, _a_decay_a_head],
                         ids=lambda f: f.__name__.lstrip("_"))
def test_a_planted_fault_moves_the_served_logits(reference, monkeypatch,
                                                 fault):
    eng = _engine()
    fault(eng, monkeypatch)
    prompts = _prompts()
    answers, _ = _serve(eng, prompts)
    assert _worst_gap(reference, prompts, answers) > 20 * TOL


def test_the_scheduler_counts_the_kda_layers_and_the_shared_expert():
    """The recurrent layers' counters under the kind's label, and the shared
    expert's rows at dispatch: every lane of every program, a forward pass
    and an expert layer each, used and idle adding up to the programs'
    lanes."""
    monitor.reset()
    monitor.enable()
    try:
        eng = _engine(decode_burst=4)
        steps = []
        real = eng._dispatch

        def spy(plan, *rest):
            fl = real(plan, *rest)
            steps.append((fl.kind, fl.forwards))
            return fl

        eng._dispatch = spy
        _serve(eng, _prompts())
        snap = monitor.snapshot()["metrics"]
    finally:
        monitor.disable()
        monitor.reset()
    rows = snap["paddle_tpu_serving_shared_expert_rows_total"]["values"]
    lanes = sum(eng.max_step_tokens if k == "mixed" else eng.max_batch * n
                for k, n in steps)
    assert rows["lanes=used"] + rows["lanes=idle"] == 8 * lanes
    assert 0 < rows["lanes=used"] < 8 * lanes
    fed = sum(len(p) for p, _ in _prompts())
    tokens = snap["paddle_tpu_serving_linear_tokens_total"]["values"]
    assert tokens["path=chunk"] + tokens["path=step"] >= fed
    kinds = snap["paddle_tpu_cache_byte_steps_total"]["values"]
    assert kinds["kind=kda"] > 0 and kinds["kind=full"] > 0
    assert "kind=linear" not in kinds


def test_no_model_name_in_the_serving_block_or_the_scheduler():
    for name in ("serving.py", "llama_decode.py", "paged_kv.py",
                 "linear_attention.py"):
        with open(os.path.join(ROOT, "paddle_tpu", "models", name)) as f:
            assert "solar" not in f.read().lower(), name
