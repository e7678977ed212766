"""The MiMo-V2-Flash configuration's own benchmark code against hand counts:
parameters held, model FLOPs, the two kernels' least bytes, and the reader
that turns pair counts into pairs per held expert."""
import json
import os

import pytest

import _bench_util as U

flops = U.load("flops", "mimo_v2_flash")
builder = U.load("builders", "mimo_v2_flash")


def _cfg():
    with open(os.path.join(U.BENCH, "configs", "mimo-v2-flash.json")) as f:
        return json.load(f)


H = 4096
ATTN_FULL = H * 64 * 192 + H * 4 * 192 + H * 4 * 128 + 64 * 128 * H     # q k v o
ATTN_WINDOW = H * 64 * 192 + H * 8 * 192 + H * 8 * 128 + 64 * 128 * H
EXPERT = 3 * H * 2048


def test_parameters_held_against_a_hand_count():
    cfg = _cfg()
    dense = ATTN_FULL + 3 * H * 16384 + 2 * H
    window = ATTN_WINDOW + 64 + H * 256 + 256 + 16 * EXPERT + 2 * H
    full = ATTN_FULL + H * 256 + 256 + 16 * EXPERT + 2 * H
    total = dense + 5 * window + full + 2 * 19072 * H + H
    assert (dense, window, full) == (290_463_744, 498_082_112, 492_839_168)
    assert builder.parameter_count(cfg) == total == 3_429_955_392 \
        == cfg["bytes"]["parameters"]
    assert [s[0] for s in builder.leaf_specs(cfg)][builder.layer_base(cfg, 6)] \
        == "model.layers.6.input_layernorm.weight"
    # the two lists are kept whole; the first seven entries are what is built
    assert cfg["hybrid_layer_pattern"][:7] == [0, 1, 1, 1, 1, 0, 1] \
        and len(cfg["hybrid_layer_pattern"]) == 48
    assert not any("attention_sink_bias" in s[0] and ".layers.0." in s[0]
                   for s in builder.leaf_specs(cfg))


def test_model_flops_against_a_hand_count():
    cfg = _cfg()
    # per token: the projections, the dense MLP once, six routers, half an
    # expert a layer in expectation (8 x 16 / 256), the head over the slice
    per_token = (2 * ATTN_FULL + 5 * ATTN_WINDOW + 3 * H * 16384
                 + 6 * (H * 256 + 0.5 * EXPERT) + H * 19072)
    assert flops.matmul_params_per_token(cfg) == per_token
    # 300 tokens from position 0: full layers attend to 1 + 2 + .. + 300,
    # window layers to 1 + .. + 128 and then 128 each
    attn = 2 * 64 * (192 + 128)
    ramp = 128 * 129 // 2
    want = 2 * per_token * 300 + attn * (2 * (300 * 301 // 2)
                                         + 5 * (ramp + 172 * 128))
    assert flops.forward_flops(cfg, 300, 300 * 301 // 2) == want
    assert flops.request_forward_flops(cfg, 280, 21) == want
    assert flops._capped(5, 126, 128) == 127 + 128 * 4
    assert flops._capped(10, 0, 128) == 55


def test_kernel_costs_against_a_hand_count():
    cfg = _cfg()
    counters = {flops.ATTN_KIND_BLOCKS: {"kind=full": 1280.0, "kind=window": 300.0}}
    ops, nbytes = flops.paged_attention_costs(cfg, cfg["engine"], counters)
    # a block: 64 positions x KV heads x (192 + 128) x 2 bytes; 2 full layers,
    # 5 window layers; 1280 / 128 = 10 lanes' queries and outputs a layer
    kv = 2 * 1280 * 64 * 4 * 320 * 2 + 5 * 300 * 64 * 8 * 320 * 2
    qo = 10 * 7 * 64 * 320 * 2
    assert nbytes == kv + qo
    assert ops == 2 * 64 * 320 * 64 * (2 * 1280 + 5 * 300)
    assert flops.paged_attention_costs(cfg, cfg["engine"], {}) is None
    pairs = {flops.EXPERT_PAIRS: {"where=held": 960.0, "where=experts_hit": 90.0,
                                  "where=routed": 15360.0}}
    ops, nbytes = flops.held_experts_costs(cfg, cfg["engine"], pairs)
    assert ops == 960 * 3 * 2 * H * 2048
    assert nbytes == (90 * EXPERT + 960 * 2 * H) * 2
    assert flops.held_experts_costs(cfg, cfg["engine"], {}) is None


def test_counter_ratio_reads_pairs_per_held_expert():
    reader = U.load("readers", "counter_ratio")
    with open(os.path.join(U.BENCH, "metrics",
                           "moe_pairs_per_held_expert.mix.json")) as f:
        params = json.load(f)["params"]
    snap = {"metrics": {"paddle_tpu_serving_expert_pairs_total": {"values": {
        "where=held": 1920.0, "where=routed": 30720.0,
        "where=experts_hit": 180.0, "where=expert_calls": 192.0}}}}
    value, note = reader.read({}, params, {"monitor_snapshot": snap})
    assert value == pytest.approx(10.0) and note["denominator"] == 192.0
    assert reader.read({}, params, {"monitor_snapshot": {"metrics": {}}}) is None


def test_the_heavy_tail_backlog_repeats_from_a_seed_in_one_fixed_order():
    import numpy as np

    reqs = U.load("generators", "lognormal_requests")
    with open(os.path.join(U.BENCH, "traffic", "heavy-tail-backlog.json")) as f:
        tr = json.load(f)
    cfg = _cfg()
    a, b, c = (reqs.requests(s, tr, cfg) for s in (4294967000, 4294967000, 17))
    assert len(a) == len(c) == tr["backlog_requests"] == 2400
    assert all(np.array_equal(x["prompt"], y["prompt"]) for x, y in zip(a, b))
    assert [(len(r["prompt"]), r["max_new"]) for r in a] == \
        [(len(r["prompt"]), r["max_new"]) for r in c]
    lens = np.array([len(r["prompt"]) for r in a])
    assert 430 < np.median(lens) < 600 and 900 < lens.mean() < 1200
    assert 0.25 < (lens < 256).mean() < 0.35 and 0.03 < (lens > 4096).mean() < 0.08
    assert all(len(r["prompt"]) + r["max_new"] <= 8192 for r in a)
    # ids from the vocabulary slice held here
    assert max(int(r["prompt"].max()) for r in a) < cfg["vocab_size"] == 19072
