"""The Solar-Open2 configuration and cell: the file against the published
config, hand counts of parameters, pools, FLOPs and least bytes, the traffic's
fixed order, the cell's entries, and a planted altered token, which has to
read not correct under ``--rehearse``."""
import json
import math
import os

import numpy as np

import _bench_util as U

CELL = "solar-open2-serve-long-reasoning"
# the published configuration's row, as the public catalog of architectures has it
CATALOG = os.path.join(os.path.dirname(__file__), "fixtures",
                       "solar_open2_published.jsonl")
BENCH = U.benchmark_json()


def _cfg():
    entry = next(c for c in BENCH["configs"] if c["name"] == "solar-open2-250b")
    with open(os.path.join(U.ROOT, entry["file"])) as f:
        return entry, json.load(f)


def test_the_file_holds_the_published_config_and_one_chips_share():
    entry, cfg = _cfg()
    reduced = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert entry["reduced"] == cfg["reduced"] == reduced
    assert cfg["published"] == {"num_hidden_layers": 48,
                                "n_routed_experts": 320, "vocab_size": 196608}
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (4, 40, 24576)
    assert cfg["chips_sharing_a_layer"] == 8 and cfg["deployment"]
    # one whole period, GQA first, and every layer an expert layer
    assert [i in cfg["gqa_layers"] for i in range(4)] == [True] + [False] * 3
    assert cfg["first_k_dense_replace"] == 0 and not cfg["use_rope"]
    assert cfg["engine"] == {"max_batch": 128, "block_size": 64,
                             "chunk_size": 256, "max_len": 6144,
                             "prefix_cache": False}
    assert len(cfg["assumed"]) >= 10
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Solar-Open2-250B")
    assert cfg["source"] == entry["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in reduced:
            assert cfg[key] == value, key


def test_parameters_and_pools_by_hand():
    _, cfg = _cfg()
    B = U.load("builders", "solar_open2")
    h, m, w, d = 4096, 1280, 64 * 128, 128
    kda = (3 * h * w + w * h                       # q, k, v, o
           + 2 * (h * d + d * w)                   # decay and gate, rank 128
           + h * 64 + 3 * 4 * w + 64 + w + d)      # beta, taps, A_log, dt, norm
    gqa = h * w + 2 * h * 8 * 128 + w * h + h * w  # q, k, v, o, the gate
    experts = 41 * 3 * h * m + h * 320 + 320       # 40 held + shared, router
    assert kda == cfg["bytes"]["kda_mixer_parameters"] == 137_732_288
    assert gqa == cfg["bytes"]["gqa_mixer_parameters"] == 109_051_904
    assert experts + 2 * h == cfg["bytes"]["expert_layer_parameters"]
    total = 3 * kda + gqa + 4 * (experts + 2 * h) + 2 * 24576 * h + h
    assert B.parameter_count(cfg) == total == cfg["bytes"]["parameters"] \
        == 3_308_353_344
    assert cfg["bytes"]["kv_pool_bytes"] == \
        (128 * 96 + 1) * 64 * 8 * 128 * 2 * 2
    slot = 64 * 128 * 128 * 4 + 3 * 3 * w * 2
    assert cfg["bytes"]["state_pool_bytes"] == 129 * 3 * slot
    # 6.62 GB of weights, 3.22 GB of keys and values, 1.68 GB of state:
    # two thirds of the chip's 17.18 GB before activations
    held = 2 * total + cfg["bytes"]["kv_pool_bytes"] \
        + cfg["bytes"]["state_pool_bytes"]
    assert 0.65 < held / 17.18e9 < 0.70


def test_the_weights_a_program_could_forget_are_drawn_where_it_shows():
    import jax.numpy as jnp

    run = U.load("", "run")
    _, _, small, _, _ = run.load_cell(CELL, True)
    B = U.load("builders", "solar_open2")
    made = B.weights(3, small, "float32")
    assert set(made) == {s[0] for s in B.leaf_specs(small)}
    a = jnp.exp(made["model.layers.1.self_attn.A_log"])
    assert float(a.min()) >= 1.0 and float(a.max()) <= 16.0
    dt = made["model.layers.1.self_attn.dt_bias"]
    assert dt.shape == (4 * 16,)                   # a key channel each
    step = np.log1p(np.exp(np.asarray(dt, np.float64)))
    assert step.min() >= 1e-3 * 0.99 and step.max() <= 0.1 * 1.01
    bias = made["model.layers.0.mlp.gate.e_score_correction_bias"]
    assert 0.005 < float(jnp.std(bias)) < 0.05
    for name, value in made.items():
        if name.endswith("norm.weight") or "layernorm" in name:
            assert 0.02 < float(jnp.std(value)) < 0.3, name
        if "conv1d" in name:
            assert 0.3 < float(jnp.std(value)) < 0.7, name


def test_flops_and_least_bytes_by_hand():
    _, cfg = _cfg()
    F = U.load("flops", "solar_open2")
    B = U.load("builders", "solar_open2")
    h, m = 4096, 1280
    # of the 40 held experts a token reaches 8 x 40 / 320 = 1 a layer
    per_token = 2 * (B.parameter_count(cfg, matmul_only=True)
                     - 4 * 39 * 3 * h * m) + 3 * 6 * 64 * 128 * 128
    assert F.forward_flops(cfg, 10, 55) == 10 * per_token \
        + 4 * 64 * 128 * 55
    assert F.request_forward_flops(cfg, 4, 3) == F.forward_flops(cfg, 6, 21)
    counters = {F.LINEAR_RUNS: {"path=chunk": 2, "path=step": 30},
                F.LINEAR_TOKENS: {"path=chunk": 300, "path=step": 30}}
    flops, nbytes = F.kda_costs(cfg, cfg["engine"], counters)
    assert flops == 330 * 3 * 6 * 64 * 128 * 128
    row = 4 * 64 * 128 * 2 + 64 * 128 * 4 + 64 * 4
    assert nbytes == 3 * (32 * 2 * 64 * 128 * 128 * 4 + 330 * row)
    assert F.kda_costs(cfg, cfg["engine"], {}) is None
    pairs = {F.EXPERT_PAIRS: {"where=held": 1000, "where=experts_hit": 150}}
    flops, nbytes = F.held_experts_costs(cfg, cfg["engine"], pairs)
    assert flops == 1000 * 3 * 2 * h * m
    assert nbytes == (150 * 3 * h * m + 1000 * 2 * h) * 2
    assert F.held_experts_costs(cfg, cfg["engine"], {}) is None


def test_a_decode_steps_bytes_are_mostly_experts_and_state():
    """The cell's arithmetic, from the file: at 128 lanes a decode step
    reads the weights once (nearly every held expert hit) and reads and
    writes every lane's KDA state; the KDA layers and the expert layers
    carry about 85% of it."""
    _, cfg = _cfg()
    b = cfg["bytes"]
    weights = 2 * b["parameters"]
    experts = 2 * 4 * b["expert_layer_parameters"]
    state = 2 * 128 * 3 * 64 * 128 * 128 * 4
    kv = 128 * 1800 * 8 * 128 * 2 * 2          # ~1800 positions a lane
    kda = 2 * 3 * b["kda_mixer_parameters"]
    share = (experts + state + kda) / (weights + state + kv)
    assert 0.80 < share < 0.90
    assert math.isclose(state / 1e9, 3.2, rel_tol=0.05)


def test_every_seed_replays_one_order_of_lengths():
    run = U.load("", "run")
    _, _, cfg, traffic, _ = run.load_cell(CELL, False)
    gen = U.load("generators", traffic["generator"])
    prompt, output = gen.shapes(traffic, int(traffic["backlog_requests"]))
    assert len(prompt) == 1200
    assert prompt.min() >= 96 and prompt.max() <= 3072
    assert output.min() >= 384 and output.max() <= 4608
    assert (prompt + output).max() <= 6144 == cfg["engine"]["max_len"]
    assert 680 < np.median(prompt) < 860 and 1350 < np.median(output) < 1700
    small = dict(traffic, backlog_requests=40)
    a = gen.requests(2 ** 31 + 7, small, cfg)
    b = gen.requests(11, small, cfg)
    assert [len(r["prompt"]) for r in a] == [len(r["prompt"]) for r in b]
    assert [r["max_new"] for r in a] == [r["max_new"] for r in b]
    assert all(r["prompt"].max() < 24576 for r in a)


def test_the_cell_and_its_metrics_are_new_entries():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("solar-open2-250b", "long-reasoning-backlog", 1)
    mine = [m for m in BENCH["per_layer"] if m["name"].endswith(".kda")]
    assert {m["name"] for m in mine} == {
        "serve_step_mfu.kda", "kda_roofline.kda",
        "moe_experts_gmm_roofline.kda", "recurrent_part_share.kda",
        "state_cache_byte_share.kda"}
    for m in mine:
        assert m["workloads"] == [CELL]
        assert m["moves"] == "serve_tokens_per_s"
        with open(os.path.join(U.BENCH, "metrics", m["name"] + ".json")) as f:
            spec = json.load(f)
        assert os.path.exists(os.path.join(U.BENCH, "readers",
                                           spec["reader"] + ".py"))
    serve = next(m for m in BENCH["end_to_end"]
                 if m["name"] == "serve_tokens_per_s")
    assert serve["workloads"][-1] == CELL
    sat = [m for m in BENCH["per_layer"] if CELL in m.get("workloads", [])
           and m["name"].endswith(".sat")]
    assert len(sat) == 14 and all(m["workloads"][-1] == CELL for m in sat)


# -- planted faults ------------------------------------------------------------
WINDOW_S = 8


def _altered_token(eng):
    real = eng.step

    def step(*a, **k):
        out = []
        for rid, tokens in real(*a, **k):
            tokens = list(tokens)
            tokens[len(tokens) // 2] = (tokens[len(tokens) // 2] + 1) % 256
            out.append((rid, tokens))
        return out
    eng.step = step


def test_an_altered_served_token_reads_not_correct():
    """(The model's own planted faults, a gate dropped and a decay a head,
    are read against the reference in ``tests/test_solar_open2_serving.py``;
    the sound cell reads ``correct`` in ``test_benchmark_rehearsal.py``.)"""
    res, err = U.run_cell_with_fault(CELL, 2 ** 31 + 99, WINDOW_S,
                                     _altered_token)
    assert res["correct"] is False
    c = res["compared"]["token_logit_gap"]
    # a gap read from served tokens: a window in which nothing finished reads
    # inf, which is over any limit and says nothing about the fault
    assert np.isfinite(c["value"]) and c["value"] > c["limit"]
    assert "NOT OK" in err

