"""The Olmo-Hybrid configuration and cell: the file against the published
config, hand counts of parameters, pools, FLOPs and least bytes, the traffic's
fixed order, and the cell's planted faults, each of which has to read not
correct under ``--rehearse``."""
import dataclasses
import json
import os

import numpy as np
import pytest

import _bench_util as U

CELL = "olmo-hybrid-7b-serve-long-answers"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
BENCH = U.benchmark_json()


def _cfg():
    entry = next(c for c in BENCH["configs"] if c["name"] == "olmo-hybrid-7b")
    with open(os.path.join(U.ROOT, entry["file"])) as f:
        return entry, json.load(f)


def test_the_file_holds_the_published_config_and_reduces_depth_alone():
    entry, cfg = _cfg()
    assert entry["reduced"] == cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["published"] == {"num_hidden_layers": 32}
    assert cfg["num_hidden_layers"] == 12
    # three whole periods of the published list, which is kept whole
    assert len(cfg["layer_types"]) == 32
    held = cfg["layer_types"][:12]
    assert held == (["linear_attention"] * 3 + ["full_attention"]) * 3
    assert cfg["engine"] == {"max_batch": 32, "block_size": 64,
                             "chunk_size": 256, "max_len": 4096,
                             "prefix_cache": False}
    assert len(cfg["assumed"]) >= 8 and cfg["deployment"]
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog of published configurations on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Olmo-Hybrid-7B")
    assert cfg["source"] == entry["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key


def test_parameters_and_pools_by_hand():
    _, cfg = _cfg()
    B = U.load("builders", "olmo_hybrid")
    h, m, v = 3840, 11008, 100352
    linear = (2 * h * 2880 + 3 * h * 5760 + 2 * h * 30 + 4 * 11520   # mixer
              + 30 + 30 + 192                          # A_log, dt_bias, o_norm
              + 3 * h * m + 2 * h)                     # MLP, two norms
    full = 4 * h * h + 2 * h + 3 * h * m + 2 * h
    assert linear == 215_570_172 and full == 185_809_920
    total = 9 * linear + 3 * full + 2 * v * h + h
    assert B.parameter_count(cfg) == total == cfg["bytes"]["parameters"]
    assert cfg["bytes"]["linear_layer_parameters"] == linear
    assert cfg["bytes"]["full_layer_parameters"] == full
    matmul = 9 * (linear - 4 * 11520 - 252 - 2 * h) \
        + 3 * (full - 4 * h) + v * h
    assert B.parameter_count(cfg, matmul_only=True) == matmul
    assert cfg["bytes"]["kv_pool_bytes"] == \
        (32 * 64 + 1) * 64 * 30 * 128 * 2 * 2 * 3
    assert cfg["bytes"]["state_pool_bytes"] == \
        33 * 9 * (30 * 96 * 192 * 4 + 3 * 11520 * 2)
    # 6.54 + 6.04 + 0.68 GB: over three quarters of the chip's 17.18 GB
    held = 2 * total + cfg["bytes"]["kv_pool_bytes"] \
        + cfg["bytes"]["state_pool_bytes"]
    assert 0.75 < held / 17.18e9 < 0.80


def test_the_weights_a_program_could_forget_are_drawn_where_it_shows():
    import jax.numpy as jnp

    _, cfg = _cfg()
    run = U.load("", "run")
    _, _, small, _, _ = run.load_cell(CELL, True)
    B = U.load("builders", "olmo_hybrid")
    made = B.weights(3, small, "float32")
    a = jnp.exp(made["model.layers.0.linear_attn.A_log"])
    assert float(a.min()) >= 1.0 and float(a.max()) <= 16.0
    step = np.log1p(np.exp(np.asarray(
        made["model.layers.0.linear_attn.dt_bias"], np.float64)))
    assert step.min() >= 1e-3 * 0.99 and step.max() <= 0.1 * 1.01
    for name, value in made.items():
        if name.endswith("norm.weight") or "layernorm" in name:
            assert 0.02 < float(jnp.std(value)) < 0.3, name
        if "conv1d" in name:
            assert 0.3 < float(jnp.std(value)) < 0.7, name
    assert set(made) == {s[0] for s in B.leaf_specs(small)}


def test_flops_and_least_bytes_by_hand():
    _, cfg = _cfg()
    F = U.load("flops", "olmo_hybrid")
    B = U.load("builders", "olmo_hybrid")
    per_token = 2 * B.parameter_count(cfg, matmul_only=True) \
        + 9 * 6 * 30 * 96 * 192
    assert F.forward_flops(cfg, 10, 55) == \
        10 * per_token + 3 * 4 * 30 * 128 * 55
    assert F.request_forward_flops(cfg, 4, 3) == F.forward_flops(cfg, 6, 21)
    counters = {F.LINEAR_RUNS: {"path=chunk": 2, "path=step": 30},
                F.LINEAR_TOKENS: {"path=chunk": 300, "path=step": 30}}
    flops, nbytes = F.gated_delta_costs(cfg, cfg["engine"], counters)
    assert flops == 330 * 9 * 6 * 30 * 96 * 192
    row = 2 * 30 * (96 + 192) * 2 + 2 * 30 * 4
    assert nbytes == 9 * (32 * 2 * 30 * 96 * 192 * 4 + 330 * row)
    assert F.gated_delta_costs(cfg, cfg["engine"], {}) is None
    counters = {F.ATTN_KIND_BLOCKS: {"kind=full": 130}}
    flops, nbytes = F.paged_attention_costs(cfg, cfg["engine"], counters)
    assert flops == 4 * 30 * 128 * 130 * 64 * 3
    lanes = -(-130 // 64)
    assert nbytes == 130 * 3 * 64 * 30 * 128 * 2 * 2 \
        + lanes * 3 * 30 * 128 * 2 * 2
    assert F.paged_attention_costs(cfg, cfg["engine"], {}) is None


def test_every_seed_replays_one_order_of_lengths():
    run = U.load("", "run")
    _, _, cfg, traffic, _ = run.load_cell(CELL, False)
    gen = U.load("generators", traffic["generator"])
    prompt, output = gen.shapes(traffic, int(traffic["backlog_requests"]))
    assert len(prompt) == 1200
    assert prompt.min() >= 64 and prompt.max() <= 2048
    assert output.min() >= 256 and output.max() <= 2048
    assert (prompt + output).max() <= 4096 == cfg["engine"]["max_len"]
    assert 330 < np.median(prompt) < 440 and 680 < np.median(output) < 860
    small = dict(traffic, backlog_requests=40)
    a = gen.requests(2 ** 31 + 7, small, cfg)
    b = gen.requests(11, small, cfg)
    assert [len(r["prompt"]) for r in a] == [len(r["prompt"]) for r in b]
    assert [r["max_new"] for r in a] == [r["max_new"] for r in b]
    assert any((x["prompt"][:8] != y["prompt"][:8]).any()
               for x, y in zip(a, b))


def test_the_new_metrics_list_the_cell_and_name_their_readers():
    mine = [m for m in BENCH["per_layer"] if m["name"].endswith(".lin")]
    assert {m["name"] for m in mine} == {
        "serve_step_mfu.lin", "gated_delta_roofline.lin",
        "paged_attention_roofline.lin", "state_cache_byte_share.lin",
        "linear_chunk_token_share.lin"}
    for m in mine:
        assert m["workloads"] == [CELL]
        assert m["moves"] == "serve_tokens_per_s"
        with open(os.path.join(U.BENCH, "metrics", m["name"] + ".json")) as f:
            spec = json.load(f)
        assert os.path.exists(os.path.join(U.BENCH, "readers",
                                           spec["reader"] + ".py"))


# -- planted faults ------------------------------------------------------------
# The in-process rehearsals' window: alone 80 requests finish in 2 s, beside
# three busy JAX processes a step takes 0.6-0.9 s and 4 finish in 8 s (PERF.md
# 7, q17); ``run_cell_with_fault`` tries 4 and 16 times as long where none did.
WINDOW_S = 8
def _decay_dropped(eng):
    import jax.numpy as jnp

    for p in eng._inner.layers:
        if "A_log" in p:
            p["A_log"] = jnp.full_like(p["A_log"], -40.0)    # exp(g) = 1


def _beta_not_doubled(eng):
    e = eng._inner
    e.kinds = tuple(k if k.paged else dataclasses.replace(k, neg_eigval=False)
                    for k in e.kinds)


@pytest.fixture
def restore():
    """Module functions a fault replaces, put back after the test."""
    saved = []
    yield lambda mod, name, value: (saved.append((mod, name,
                                                  getattr(mod, name))),
                                    setattr(mod, name, value))
    for mod, name, value in reversed(saved):
        setattr(mod, name, value)


def _state_not_reset(replace):
    def fault(eng):
        import jax.numpy as jnp

        from paddle_tpu.ops.pallas import gated_delta_rule as G

        real = G.gated_delta

        def stale(q, k, v, g, beta, state, positions, plan=None):
            if plan is not None:
                plan = dict(plan, fresh=jnp.zeros_like(plan["fresh"]),
                            code=jnp.minimum(plan["code"], 1),
                            order_fresh=jnp.zeros_like(plan["order_fresh"]))
            return real(q, k, v, g, beta, state, positions + 1, plan)

        replace(G, "gated_delta", stale)
    return fault


def _conv_not_carried(replace):
    def fault(eng):
        import jax.numpy as jnp

        from paddle_tpu.models import linear_attention as L

        real = L.causal_conv
        replace(L, "causal_conv", lambda xin, conv, taps, positions, plan:
                real(xin, jnp.zeros_like(conv), taps, positions, plan))
    return fault


@pytest.mark.parametrize("fault", ["decay_dropped", "beta_not_doubled",
                                   "state_not_reset", "conv_not_carried"])
def test_a_planted_fault_reads_not_correct(restore, fault):
    planted = {"decay_dropped": _decay_dropped,
               "beta_not_doubled": _beta_not_doubled,
               "state_not_reset": _state_not_reset(restore),
               "conv_not_carried": _conv_not_carried(restore)}[fault]
    res, err = U.run_cell_with_fault(CELL, 2 ** 31 + 99, WINDOW_S, planted)
    assert res["correct"] is False
    c = res["compared"]["token_logit_gap"]
    # a gap read from served tokens: a window in which nothing finished reads
    # inf, which is over any limit and says nothing about the fault
    assert np.isfinite(c["value"]) and c["value"] > c["limit"]
    assert "NOT OK" in err


def test_the_unbroken_cell_reads_correct_in_process():
    res, _ = U.run_cell_with_fault(CELL, 2 ** 31 + 99, WINDOW_S,
                                   lambda eng: None)
    assert res["correct"] is True
    assert np.isfinite(res["compared"]["token_logit_gap"]["value"])
