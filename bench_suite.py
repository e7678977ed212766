"""BASELINE benchmark suite: the five reference configs, measured.

BASELINE.json lists the reference's headline benchmark configs (the reference
itself publishes no in-tree numbers — BASELINE.md):

  1. lenet      — LeNet/MNIST-shape, single-device EAGER (the PR1 reference)
  2. resnet50   — paddle.vision.models.resnet50, AMP O2, single chip
  3. bert_dp    — BERT-base pretraining step (fleet DataParallel surface;
                  dp mechanics proven in tests/test_launch.py — here the
                  per-chip step is measured)
  4. gpt_hybrid — GPT under tp2 x pp2 x sharding2 (ZeRO stage 2) on the
                  8-device virtual CPU mesh (hybrid mechanics + step time;
                  per-chip perf for the transformer family is the flagship
                  llama number)
  5. llama      — the flagship: measured by bench.py (driver contract), not
                  duplicated here

`python bench_suite.py [--configs lenet,resnet50,...]` runs each config in
its own subprocess (own backend init / device-count env) and appends one
JSON line per config to tools/suite_results.jsonl. Shapes auto-scale: full
headline sizes on TPU, smoke sizes on CPU so the suite is CI-runnable.

One process per chip: this parent never touches jax (it imports
bench_common, which imports jax only inside functions) and runs ONE child
at a time, so the child is the only client of the device. The mesh configs
(gpt_hybrid, mesh, trainchaos, fusion) need 8 devices and run their child on
the 8-device virtual CPU mesh: their timings are CPU timings, never device
numbers.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
RESULTS = os.path.join(ROOT, "tools", "suite_results.jsonl")

CONFIGS = ("lenet", "resnet50", "bert_dp", "gpt_hybrid", "serving",
           "chaos", "spec", "mesh", "trainchaos", "fusion", "fleet",
           "obs", "control")


# --------------------------------------------------------------------------- #
# shared helpers (worker side) — the donated train step, execution fence and
# timing loop live in bench_common.py (shared with bench.py)
# --------------------------------------------------------------------------- #

from bench_common import force as _force  # noqa: E402
from bench_common import build_step as _build_step  # noqa: E402
from bench_common import timed_loop as _timed_loop_impl  # noqa: E402


def _timed_loop(step, state0, batch, iters):
    dt, _state, loss = _timed_loop_impl(step, state0, batch, iters)
    import jax

    return dt, float(jax.device_get(loss))


def _emit(doc):
    print(json.dumps(doc), flush=True)


def _device():
    import jax

    d = jax.devices()[0]
    return d, d.platform == "tpu", str(getattr(d, "device_kind", d.platform))


# --------------------------------------------------------------------------- #
# config workers
# --------------------------------------------------------------------------- #

def run_lenet():
    """Config 1 — LeNet, single-device EAGER (no jit): this is the eager
    hot-path number (dispatch + autograd tape per op), the suite's analog of
    the reference's dygraph mode."""
    import numpy as np

    import paddle_tpu as paddle

    dev, on_tpu, kind = _device()
    batch = 256 if on_tpu else 64
    iters = 20 if on_tpu else 5

    paddle.seed(0)
    model = paddle.vision.models.LeNet()
    opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                parameters=model.parameters())
    ce = paddle.nn.CrossEntropyLoss()
    r = np.random.RandomState(0)
    x = paddle.to_tensor(r.randn(batch, 1, 28, 28).astype("float32"))
    y = paddle.to_tensor(r.randint(0, 10, (batch,)).astype("int64"))

    def one():
        loss = ce(model(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    loss = one()  # warm caches
    _force(loss.value)
    t0 = time.perf_counter()
    for _ in range(iters):
        loss = one()
    _force(loss.value)
    dt = (time.perf_counter() - t0) / iters
    _emit({"config": "lenet", "value": round(batch / dt, 1),
           "unit": "images/s",
           "detail": {"mode": "eager", "batch": batch, "iters": iters,
                      "step_ms": round(dt * 1e3, 2), "device": kind,
                      "loss": float(loss)}})


def run_resnet50():
    """Config 2 — ResNet-50, AMP O2 (bf16 compute + fp32 master weights on
    TPU), single chip, jitted fused train step."""
    import numpy as np

    import paddle_tpu as paddle

    dev, on_tpu, kind = _device()
    if on_tpu:
        batch, hw, iters, amp_level = 128, 224, 10, "O2"
    else:
        batch, hw, iters, amp_level = 2, 64, 2, "O1"  # smoke: tiny + cheap

    paddle.seed(0)
    model = paddle.vision.models.resnet50(num_classes=1000)
    opt = paddle.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                    parameters=model.parameters(),
                                    multi_precision=on_tpu)
    if on_tpu:
        model, opt = paddle.amp.decorate(models=model, optimizers=opt,
                                         level="O2", dtype="bfloat16")
    ce = paddle.nn.CrossEntropyLoss()

    def loss_fn(m, images, labels):
        with paddle.amp.auto_cast(enable=on_tpu, level=amp_level,
                                  dtype="bfloat16"):
            logits = m(images)
            return ce(logits, labels)

    step, state, _ = _build_step(model, opt, loss_fn)
    r = np.random.RandomState(0)
    images = np.asarray(r.randn(batch, 3, hw, hw), "float32")
    labels = r.randint(0, 1000, (batch,)).astype("int64")
    dt, loss = _timed_loop(step, state(), (images, labels), iters)
    _emit({"config": "resnet50", "value": round(batch / dt, 1),
           "unit": "images/s",
           "detail": {"amp": amp_level, "batch": batch, "image": hw,
                      "iters": iters, "step_ms": round(dt * 1e3, 2),
                      "device": kind, "loss": loss}})


def run_bert_dp():
    """Config 3 — BERT-base pretraining step (MLM+NSP). The DataParallel
    axis is exercised end-to-end in tests/test_launch.py (2-process loss
    parity); here the per-chip fused step is measured — with replicated
    params + sharded batch, per-chip time IS the dp-scaled unit."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.models.bert import (BertConfig, BertForPretraining,
                                        BertPretrainingCriterion)

    dev, on_tpu, kind = _device()
    if on_tpu:
        cfg = BertConfig()  # base: L12 H768 A12
        batch, seq, iters = 32, 128, 8
    else:
        cfg = BertConfig(vocab_size=1024, hidden_size=128,
                         num_hidden_layers=2, num_attention_heads=4,
                         intermediate_size=256, max_position_embeddings=64)
        batch, seq, iters = 4, 32, 2

    paddle.seed(0)
    model = BertForPretraining(cfg)
    if on_tpu:
        model.to(dtype="bfloat16")
    crit = BertPretrainingCriterion(cfg.vocab_size)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters(),
                                 multi_precision=on_tpu)

    r = np.random.RandomState(0)
    ids = r.randint(0, cfg.vocab_size, (batch, seq)).astype("int64")
    tt = np.zeros((batch, seq), "int64")
    mlm_labels = r.randint(0, cfg.vocab_size, (batch, seq)).astype("int64")
    nsp = r.randint(0, 2, (batch,)).astype("int64")

    def loss_fn(m, ids_t, tt_t, mlm_t, nsp_t):
        scores, rel = m(ids_t, token_type_ids=tt_t)
        return crit(scores, rel, mlm_t, nsp_t)

    step, state, _ = _build_step(model, opt, loss_fn)
    dt, loss = _timed_loop(step, state(), (ids, tt, mlm_labels, nsp), iters)
    _emit({"config": "bert_dp", "value": round(batch * seq / dt, 1),
           "unit": "tokens/s",
           "detail": {"layers": cfg.num_hidden_layers,
                      "hidden": cfg.hidden_size, "batch": batch, "seq": seq,
                      "samples_per_s": round(batch / dt, 1),
                      "step_ms": round(dt * 1e3, 2), "device": kind,
                      "dp_degree": 1, "loss": loss}})


def run_gpt_hybrid():
    """Config 4 — GPT under fleet hybrid parallel tp2 x pp2 x sharding2 on the
    8-device virtual CPU mesh (run via orchestrator with
    xla_force_host_platform_device_count=8): proves the ERNIE/GPT hybrid
    recipe end-to-end and reports the compiled step time. Not a per-chip
    perf number — that is the llama flagship."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.distributed import fleet
    from paddle_tpu.models import LlamaConfig
    from paddle_tpu.models.llama import LlamaForCausalLMPipe

    strategy = fleet.DistributedStrategy()
    # BASELINE config 4 is "TP+PP+sharding stage2": tp2 x pp2 x sharding2
    strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 2,
                               "pp_degree": 2, "sharding_degree": 2}
    strategy.sharding = True
    strategy.sharding_configs = {"sharding_degree": 2, "stage": 2}
    strategy.pipeline_configs = {"accumulate_steps": 2,
                                 "micro_batch_size": 2, "compiled": True,
                                 "schedule_mode": "1F1B"}
    fleet.init(is_collective=True, strategy=strategy)

    paddle.seed(0)
    # gpt-decoder shape (the reference's ERNIE/GPT configs are
    # decoder-transformers; the pipe wrapper here is the shared
    # decoder-LM pipeline implementation)
    cfg = LlamaConfig(
        vocab_size=512, hidden_size=128, intermediate_size=352,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=4,
        max_position_embeddings=128, tensor_parallel_degree=2,
        pipeline_parallel_degree=2)
    model = fleet.distributed_model(LlamaForCausalLMPipe(cfg))
    opt = fleet.distributed_optimizer(paddle.optimizer.AdamW(
        learning_rate=1e-4, parameters=model.parameters()))

    r = np.random.RandomState(0)
    batch, seq = 4, 64
    ids = paddle.to_tensor(r.randint(0, 512, (batch, seq)).astype("int64"))
    labels = paddle.to_tensor(
        r.randint(0, 512, (batch, seq)).astype("int64"))

    losses = []
    t0 = time.perf_counter()
    iters = 3
    for i in range(iters):
        loss = model.train_batch([ids, labels], opt)
        losses.append(float(loss))
        if i == 0:
            t0 = time.perf_counter()  # exclude compile step
    dt = (time.perf_counter() - t0) / max(1, iters - 1)
    _emit({"config": "gpt_hybrid", "value": round(batch * seq / dt, 1),
           "unit": "tokens/s",
           "detail": {"mesh": "tp2 x pp2 x sharding2 (8 virtual cpu devices)",
                      "schedule": "1F1B", "batch": batch, "seq": seq,
                      "step_ms": round(dt * 1e3, 2),
                      "loss_first": losses[0], "loss_last": losses[-1],
                      "trains": losses[-1] < losses[0]}})


def run_serving(smoke=False):
    """Config 5 — the serving engine: continuous batching (chunked
    prefill + radix prefix cache) vs the static-batch baseline at equal
    batch capacity on a Poisson open-loop mixed-length workload
    (bench_common.serving_bench; docs/serving.md). ``smoke`` runs the
    tier-1-safe tiny-model shape (`bench_suite.py --smoke serving`)."""
    import numpy as np  # noqa: F401 - platform probe below imports jax

    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    from bench_common import serving_bench

    dev, on_tpu, kind = _device()
    paddle.seed(0)
    if smoke or not on_tpu:
        cfg = LlamaConfig(vocab_size=96, hidden_size=64,
                          intermediate_size=176, num_hidden_layers=2,
                          num_attention_heads=4, num_key_value_heads=2,
                          max_position_embeddings=256)
        params = dict(max_batch=8, block_size=8, chunk_size=16,
                      decode_burst=12, n_requests=20, n_groups=2,
                      prefix_blocks=6, tail_range=(4, 12),
                      new_range=(4, 64), repeats=3)
    else:
        cfg = LlamaConfig(vocab_size=32000, hidden_size=2048,
                          intermediate_size=5632, num_hidden_layers=8,
                          num_attention_heads=16, num_key_value_heads=16,
                          max_position_embeddings=1024, dtype="bfloat16")
        params = dict(max_batch=16, block_size=64, chunk_size=128,
                      decode_burst=8, n_requests=24, n_groups=3,
                      prefix_blocks=4, tail_range=(32, 128),
                      new_range=(32, 128), repeats=2)
    model = LlamaForCausalLM(cfg)
    if on_tpu and not smoke:
        model.to(dtype="bfloat16")
    res = serving_bench(model, **params)
    res["device"] = kind
    res["smoke"] = bool(smoke)
    _emit({"config": "serving", "value": res["serving_tokens_per_sec"],
           "unit": "tokens/s", "detail": res})


def run_chaos(smoke=False):
    """Config 6 — the serving resilience drill (bench_common.chaos_bench):
    kill the driving thread mid-decode and verify recovery time, warm
    restart and bit-identical outputs; overload a bounded queue with a
    low-priority flood and verify high-priority goodput holds while the
    flood sheds with typed rejections. ``smoke`` is the tier-1-safe shape
    (`bench_suite.py --smoke chaos`)."""
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    from bench_common import chaos_bench

    dev, on_tpu, kind = _device()
    paddle.seed(0)
    if smoke or not on_tpu:
        cfg = LlamaConfig(vocab_size=96, hidden_size=64,
                          intermediate_size=176, num_hidden_layers=2,
                          num_attention_heads=4, num_key_value_heads=2,
                          max_position_embeddings=256)
        params = dict(max_batch=4, block_size=8, chunk_size=16,
                      decode_burst=4, max_queue=6, n_requests=8,
                      n_bronze=24, prompt_len=14, max_new=10, kill_nth=5)
    else:
        cfg = LlamaConfig(vocab_size=32000, hidden_size=2048,
                          intermediate_size=5632, num_hidden_layers=8,
                          num_attention_heads=16, num_key_value_heads=16,
                          max_position_embeddings=1024, dtype="bfloat16")
        params = dict(max_batch=8, block_size=64, chunk_size=128,
                      decode_burst=8, max_queue=12, n_requests=12,
                      n_bronze=48, prompt_len=96, max_new=64, kill_nth=9)
    model = LlamaForCausalLM(cfg)
    if on_tpu and not smoke:
        model.to(dtype="bfloat16")
    res = chaos_bench(model, **params)
    res["device"] = kind
    res["smoke"] = bool(smoke)
    if smoke:
        # the drill's own bounds (tier-1 gates on this exit code): the
        # kill must have happened and recovery must be warm, fast and
        # bit-exact; the flood must shed with typed rejections while
        # gold's outputs stay identical to its isolated run
        k, o = res["kill_drill"], res["overload"]
        assert k["killed"] and k["recoveries"] >= 1, k
        assert k["flight_dump"], k
        assert k["recovered_warm"], k
        assert k["tokens_match_reference"], k
        assert 0 < k["recovery_ms"] < 5000, k
        assert o["bronze_shed"] > 0, o
        assert 0.05 <= o["bronze_shed_rate"] <= 0.95, o
        assert o["gold_tokens_match_isolated"], o
    _emit({"config": "chaos",
           "value": res["overload"]["gold_goodput_ratio"],
           "unit": "goodput_ratio", "detail": res})


def run_spec(smoke=False):
    """Config 7 — speculative decoding + quantized KV
    (bench_common.spec_bench / kv_capacity_bench): the same engine with
    and without ``spec_lookahead`` on a repeat-heavy prefix-shared
    workload (greedy outputs must match bit-exactly; the speedup is the
    accepted-drafts-per-dispatch lever), plus the int8 pool capacity
    check (>= 1.8x the concurrent requests of the full-precision engine
    at an equal-or-smaller pool byte budget, read from the
    ``paddle_tpu_serving_kv_pool_bytes`` gauge). ``smoke`` is the
    tier-1-safe shape (`bench_suite.py --smoke spec`)."""
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    from bench_common import kv_capacity_bench, spec_bench

    dev, on_tpu, kind = _device()
    paddle.seed(0)
    if smoke or not on_tpu:
        cfg = LlamaConfig(vocab_size=96, hidden_size=64,
                          intermediate_size=176, num_hidden_layers=2,
                          num_attention_heads=4, num_key_value_heads=2,
                          max_position_embeddings=256)
        params = dict(max_batch=1, block_size=8, chunk_size=8,
                      max_step_tokens=24, decode_burst=4,
                      spec_lookahead=22, n_requests=6, n_groups=2,
                      max_new=160, repeats=3)
    else:
        cfg = LlamaConfig(vocab_size=32000, hidden_size=2048,
                          intermediate_size=5632, num_hidden_layers=8,
                          num_attention_heads=16, num_key_value_heads=16,
                          max_position_embeddings=1024, dtype="bfloat16")
        params = dict(max_batch=4, block_size=64, chunk_size=64,
                      max_step_tokens=128, decode_burst=8,
                      spec_lookahead=16, n_requests=12, n_groups=3,
                      pattern_len=64, head_len=16, max_new=256, repeats=2)
    model = LlamaForCausalLM(cfg)
    if on_tpu and not smoke:
        model.to(dtype="bfloat16")
    res = spec_bench(model, **params)
    # capacity check on a head-dim-64 model: at the 1.875x block ratio
    # the int8-vs-bf16 byte arithmetic (4D bf16 vs 2D + 8 scale bytes
    # int8 per token) needs head_dim >= ~60 for bytes_ratio <= 1.0, so
    # head_dim 64 clears it by only ~1% — don't shrink this shape. The
    # KV pools compare bf16 against int8 regardless of platform
    paddle.seed(0)
    cap_cfg = LlamaConfig(vocab_size=96, hidden_size=128,
                          intermediate_size=176, num_hidden_layers=2,
                          num_attention_heads=2, num_key_value_heads=1,
                          max_position_embeddings=128, dtype="bfloat16")
    cap_model = LlamaForCausalLM(cap_cfg)
    cap_model.to(dtype="bfloat16")
    res["int8_capacity"] = kv_capacity_bench(cap_model, max_batch=8,
                                             block_size=8, max_len=64)
    res["device"] = kind
    res["smoke"] = bool(smoke)
    if smoke:
        # hard bounds tier-1 gates on (exit code): speculation must be
        # EXACT and well-accepted, and the quantized pool must admit
        # 1.8x the requests within the bf16 byte budget. The >= 1.3x
        # wall-clock bar is asserted by the tier-1 test with the repo's
        # retry-up-to-3 discipline (shared-CPU noise), not here.
        assert res["spec_tokens_match"] is True, res
        assert res["spec_accept_rate"] >= 0.5, res
        assert res["spec_accepted_tokens"] > 0, res
        cap = res["int8_capacity"]
        assert cap["request_ratio"] >= 1.8, cap
        assert cap["bytes_ratio"] <= 1.0, cap
        assert cap["int8"]["concurrent"] == cap["int8"]["max_batch"], cap
    _emit({"config": "spec", "value": res["spec_speedup"],
           "unit": "speedup_vs_nonspec", "detail": res})


def run_fleet(smoke=False):
    """Config 11 — the FLEET resilience drill (bench_common.fleet_bench,
    paddle_tpu/serving/fleet.py): an N-replica health-checked router
    under the Poisson mixed prefix-shared workload. Kill drill: one of
    the replicas dies mid-decode → failover re-seeds every in-flight
    request onto the survivors and every output is bit-identical to an
    undisturbed fleet, with zero post-warmup recompiles under the
    graftsan sentinel. Drain drill: a mid-stream graceful drain loses
    zero requests. ``smoke`` is the tier-1-safe shape
    (`bench_suite.py --smoke fleet`)."""
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    from bench_common import fleet_bench

    dev, on_tpu, kind = _device()
    paddle.seed(0)
    if smoke or not on_tpu:
        cfg = LlamaConfig(vocab_size=96, hidden_size=64,
                          intermediate_size=176, num_hidden_layers=2,
                          num_attention_heads=4, num_key_value_heads=2,
                          max_position_embeddings=256)
        params = dict(replicas=3, max_batch=2, block_size=8,
                      chunk_size=16, decode_burst=2, n_requests=12,
                      n_groups=2, prefix_blocks=2, tail_range=(4, 10),
                      max_new=8, kill_nth=6)
    else:
        cfg = LlamaConfig(vocab_size=32000, hidden_size=2048,
                          intermediate_size=5632, num_hidden_layers=8,
                          num_attention_heads=16, num_key_value_heads=16,
                          max_position_embeddings=1024, dtype="bfloat16")
        params = dict(replicas=3, max_batch=8, block_size=64,
                      chunk_size=128, decode_burst=8, n_requests=24,
                      n_groups=3, prefix_blocks=4, tail_range=(32, 96),
                      max_new=64, kill_nth=12)
    model = LlamaForCausalLM(cfg)
    if on_tpu and not smoke:
        model.to(dtype="bfloat16")
    res = fleet_bench(model, **params)
    res["device"] = kind
    res["smoke"] = bool(smoke)
    if smoke:
        # the drill's own hard bounds (tier-1 gates on this exit code):
        # ISSUE 14 acceptance — 1-of-3 replicas killed mid-workload →
        # every request completes, outputs bit-identical to the
        # undisturbed fleet, >= 1 failover counted, warm recovery (zero
        # post-warmup recompiles under the sentinel), and the drain
        # drill loses zero requests
        k, d = res["kill_drill"], res["drain_drill"]
        assert res["all_complete_reference"], res
        assert k["killed"] and k["recoveries"] >= 1, k
        assert k["failovers"] >= 1, k
        assert k["flight_dump"], k
        assert k["all_complete"], k
        assert k["tokens_match_reference"], k
        assert k["recompiles_post_warmup"] == 0, k
        assert k["sentinel_trips"] == 0, k
        assert 0 < k["recovery_ms"] < 5000, k
        assert d["lost"] == 0 and d["all_complete"], d
        assert d["parked"], d
        assert d["tokens_match_reference"], d
    _emit({"config": "fleet", "value": res["fleet_tokens_per_sec"],
           "unit": "tokens/s", "detail": res})


def run_obs(smoke=False):
    """Config 12 — the graftscope scrape-under-load drill
    (bench_common.obs_bench, monitor/server.py + timeline.py): the
    serving smoke workload with and without a 10 Hz scraper polling the
    live debug endpoint. Hard bounds (asserted in-worker): scraped
    outputs BIT-IDENTICAL (observation must not perturb the engine),
    zero scrape errors, and a TTFT decomposition whose components sum
    to the measured TTFT exactly. The <=3% overhead bar is wall clock
    and lives in the tier-1 test behind the tests/_retry.py
    contention-aware floor. ``smoke`` is the tier-1-safe shape
    (`bench_suite.py --smoke obs`)."""
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    from bench_common import obs_bench

    dev, on_tpu, kind = _device()
    paddle.seed(0)
    if smoke or not on_tpu:
        cfg = LlamaConfig(vocab_size=96, hidden_size=64,
                          intermediate_size=176, num_hidden_layers=2,
                          num_attention_heads=4, num_key_value_heads=2,
                          max_position_embeddings=256)
        params = dict(max_batch=4, block_size=8, chunk_size=16,
                      decode_burst=8, n_requests=32, n_groups=2,
                      prefix_blocks=2, tail_range=(4, 10),
                      new_range=(48, 96), repeats=3)
    else:
        cfg = LlamaConfig(vocab_size=32000, hidden_size=2048,
                          intermediate_size=5632, num_hidden_layers=8,
                          num_attention_heads=16, num_key_value_heads=16,
                          max_position_embeddings=1024, dtype="bfloat16")
        params = dict(max_batch=8, block_size=64, chunk_size=128,
                      decode_burst=8, n_requests=16, n_groups=2,
                      prefix_blocks=4, tail_range=(16, 64),
                      new_range=(16, 64), repeats=2)
    model = LlamaForCausalLM(cfg)
    if on_tpu and not smoke:
        model.to(dtype="bfloat16")
    res = obs_bench(model, **params)
    res["device"] = kind
    res["smoke"] = bool(smoke)
    if smoke:
        # the drill's own DETERMINISTIC bounds (tier-1 gates on this
        # exit code): scraping a live engine changes nothing but wall
        # clock — bit-identical outputs, every scrape answered, and the
        # timeline decomposition sane for every request (components
        # non-negative and inside the measured TTFT). The overhead
        # ratio is asserted by TestObsSmoke with the repo's
        # retry/floor discipline, not here.
        assert res["tokens_match"] is True, res
        assert res["scrapes"] >= 5, res
        assert res["scrape_errors"] == 0, res
        d = res["ttft_decomposition"]
        assert d["requests"] == params["n_requests"], d
        assert d["components_sane"] is True, d
        assert d["p50_ms"]["ttft_ms"] > 0, d
        assert d["p50_ms"]["prefill_ms"] > 0, d
    _emit({"config": "obs", "value": res["overhead_ratio"],
           "unit": "scraped_vs_unscraped_ratio", "detail": res})


def run_control(smoke=False):
    """Config 13 — the graftpilot diurnal load sweep
    (bench_common.control_bench, paddle_tpu/control/): the same
    quiet -> peak -> quiet arrival pattern over a fleet that starts
    with one active replica, served static vs controlled vs
    controller-off. The controller resumes drained replicas from queue
    depth, moves the serving knobs within their declared bounds, and
    records every decision; the record must REPLAY to the identical
    decision sequence. ``smoke`` is the tier-1-safe shape
    (`bench_suite.py --smoke control`)."""
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    from bench_common import control_bench

    dev, on_tpu, kind = _device()
    paddle.seed(0)
    if smoke or not on_tpu:
        cfg = LlamaConfig(vocab_size=96, hidden_size=64,
                          intermediate_size=176, num_hidden_layers=2,
                          num_attention_heads=4, num_key_value_heads=2,
                          max_position_embeddings=256)
        params = dict(replicas=3, max_batch=2, block_size=8,
                      chunk_size=16, decode_burst=2, n_quiet=5,
                      n_peak=24, n_groups=2, prefix_blocks=2,
                      tail_range=(4, 10), max_new=48, ttft_slo_ms=150.0)
    else:
        cfg = LlamaConfig(vocab_size=32000, hidden_size=2048,
                          intermediate_size=5632, num_hidden_layers=8,
                          num_attention_heads=16, num_key_value_heads=16,
                          max_position_embeddings=1024, dtype="bfloat16")
        params = dict(replicas=3, max_batch=8, block_size=64,
                      chunk_size=128, decode_burst=8, n_quiet=8,
                      n_peak=24, n_groups=3, prefix_blocks=4,
                      tail_range=(32, 96), max_new=32,
                      ttft_slo_ms=500.0)
    model = LlamaForCausalLM(cfg)
    if on_tpu and not smoke:
        model.to(dtype="bfloat16")
    res = control_bench(model, **params)
    res["device"] = kind
    res["smoke"] = bool(smoke)
    if smoke:
        # the sweep's DETERMINISTIC bounds (tier-1 gates on this exit
        # code): every pass completes, the decision record replays to
        # the bit-identical sequence, every actuation respected its
        # declared min/max/slew, the autoscaler actually scaled up
        # under the peak, and neither the running nor the off
        # controller changed a single output token. The comparative
        # violation-minutes bar (controlled <= static) is wall clock
        # and lives in TestControlSmoke behind the tests/_retry.py
        # discipline, not here.
        c = res["controlled"]
        assert res["static"]["all_complete"], res
        assert c["all_complete"], res
        assert res["off"]["all_complete"], res
        assert c["decisions"] > 0, c
        assert c["scale_ups"] >= 1, c
        assert c["replay_identical"] is True, c
        assert c["bounds_violations"] == [], c
        assert c["degraded"] is False, c
        assert res["controlled_tokens_match_static"] is True, res
        assert res["off_tokens_match_static"] is True, res
    _emit({"config": "control",
           "value": res["controlled"]["slo_violation_minutes"],
           "unit": "slo_violation_minutes", "detail": res})


def _force_virtual_mesh():
    """The 8-device virtual CPU mesh env, set BEFORE jax's backends
    initialize (shared by the mesh-family workers; _run_config applies
    the same flags to its subprocess env dict)."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (flags +
                                   " --xla_force_host_platform_device_count=8")
    os.environ.setdefault("PADDLE_TPU_PLATFORM", "cpu")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")


def run_mesh(smoke=False):
    """Config 8 — simulated-mesh SPMD training (paddle_tpu.mesh): DP=8 and
    DP x TP = 4x2 llama training under shard_map on the 8-device virtual
    CPU mesh vs the single-device step (bench_common.mesh_bench), plus the
    ZeRO-1 per-replica optimizer-state-bytes lever. ``smoke`` is the
    tier-1-safe shape (`bench_suite.py --smoke mesh`)."""
    _force_virtual_mesh()

    import paddle_tpu as paddle  # noqa: F401 - initializes the 8-device view

    from bench_common import mesh_bench

    if smoke:
        params = dict(dp=8, tp=2, batch=8, seq=8, iters=1, vocab=64,
                      hidden=32, layers=2, heads=4, ffn=64)
    else:
        params = dict(dp=8, tp=2, batch=16, seq=64, iters=4, vocab=512,
                      hidden=128, layers=4, heads=4, ffn=352)
    res = mesh_bench(**params)
    if "skipped" in res:
        _emit({"config": "mesh", "error": res["skipped"]})
        return
    if smoke:
        # the bounds tier-1 gates on (exit code): losses must match the
        # single-device run within fp tolerance on every pass, the compiled
        # programs must actually communicate, and ZeRO-1 must shrink
        # per-replica optimizer state to ~1/dp of the replicated layout
        assert res["dp8_loss_close"], res
        assert res["zero1_loss_close"], res
        assert res["hybrid_loss_close"], res
        assert res["collectives"]["dp8"].get("all_reduce", 0) >= 1, res
        assert res["collectives"]["dp8_zero1"].get("reduce_scatter", 0) >= 1, res
        assert res["collectives"]["dp8_zero1"].get("all_gather", 0) >= 1, res
        b = res["opt_state_bytes"]
        assert b["ratio"] <= 1.0 / params["dp"] + 0.02, b
        # ISSUE 13 communication-efficiency bounds: int8 grad reduction
        # cuts grad bytes-on-wire to <= 30% of the uncompressed ZeRO
        # exchange (census-measured) at final-loss parity within the
        # declared bound, and the bucketed-overlap pass really buckets
        c = res["comm_opt"]["int8"]
        assert c["grad_bytes_ratio"] <= 0.30, c
        assert c["loss_parity"], c
        assert c["buckets"] >= 2, c
        o = res["comm_opt"]["overlap"]
        assert o["buckets"] >= 2, o
        assert abs(o["loss"] - res["dp8_zero1_loss"]) \
            <= c["parity_bound"], (o, res["dp8_zero1_loss"])
        # ISSUE 15 graftscope timeline: the PR 13 completion-ordered
        # bucketed build must MEASURE a strictly higher comm-overlap
        # fraction than the legacy tape-end exchange (deterministic:
        # the modeled schedule depends only on the traced programs)
        t = res["timeline"]
        assert t["overlap_strictly_higher"], t
        assert t["overlapped"]["collectives"] \
            < t["non_overlapped"]["collectives"], t
    _emit({"config": "mesh", "value": res["dp8_tokens_per_sec"],
           "unit": "tokens/s", "detail": res})


def run_trainchaos(smoke=False):
    """Config 9 — the TRAINING resilience drill (bench_common.
    train_chaos_bench, mesh/trainer.py + checkpoint/): kill a DP=8 llama
    train run mid-step, recover WARM from the last committed async
    checkpoint (<5s, compiled step program survives) and verify the
    replayed per-step losses are bit-identical to an uninterrupted
    reference pass. ``smoke`` is the tier-1-safe shape
    (`bench_suite.py --smoke trainchaos`)."""
    _force_virtual_mesh()

    import paddle_tpu as paddle  # noqa: F401 - initializes the 8-device view

    from bench_common import train_chaos_bench

    if smoke:
        params = dict(dp=8, steps=8, kill_at=6, ckpt_every=2, batch=8,
                      seq=8, vocab=64, hidden=32, layers=2, heads=4,
                      ffn=64)
    else:
        params = dict(dp=8, steps=16, kill_at=12, ckpt_every=4, batch=16,
                      seq=32, vocab=256, hidden=96, layers=3, heads=4,
                      ffn=256)
    res = train_chaos_bench(**params)
    if "skipped" in res:
        _emit({"config": "trainchaos", "error": res["skipped"]})
        return
    if smoke:
        # the drill's own hard bounds (tier-1 gates on this exit code):
        # the kill happened, ONE recovery fired a flight dump, restored
        # from a committed checkpoint, the replay was bit-identical and
        # the compiled step survived (zero post-recovery recompiles).
        # The <5s warm-recovery bar is wall-clock: it lives in the
        # tier-1 test behind the tests/_retry.py contention-aware floor
        # (the worker only sanity-caps it, so an oversubscribed runner
        # can still relax the bar instead of dying in-process)
        assert res["killed"] and res["recoveries"] == 1, res
        assert res["flight_dump"], res
        assert res["restored_step"] >= 0, res
        assert res["losses_bit_identical"], res
        assert res["compiled_programs_after_recovery"] == 1, res
        assert 0 < res["recovery_ms"] < 30000, res
    _emit({"config": "trainchaos", "value": res["recovery_ms"],
           "unit": "recovery_ms", "detail": res})


def run_fusion(smoke=False):
    """Config 10 — the graftopt drill (bench_common.fusion_bench,
    analysis/jaxpr/opt.py + planner.py): fusion rewrites over the three
    LIVE flagship programs (bit-exact outputs, fewer fusible regions,
    GI003 peaks) plus the HBM-budget remat drill on the DP=8 ZeRO-1
    llama step (planner fits a below-peak budget, compiler-measured
    bytes confirm within the 15% band, loss parity, zero post-warmup
    recompiles). ``smoke`` is the tier-1-safe shape
    (`bench_suite.py --smoke fusion`)."""
    _force_virtual_mesh()

    import paddle_tpu as paddle  # noqa: F401 - initializes the 8-device view

    from bench_common import fusion_bench

    res = fusion_bench(iters=2 if smoke else 4)
    if "skipped" in res:
        _emit({"config": "fusion", "error": res["skipped"]})
        return
    if smoke:
        # hard DETERMINISTIC bounds tier-1 gates on (exit code); the
        # step-time speedups are reported, never gated (wall clock on a
        # shared CPU). ISSUE 12 acceptance: optimized programs bit-
        # identical, a measurable dispatch-count (fusible-region) win,
        # and the budget drill end to end.
        for name, row in res["fusion"].items():
            assert row["bit_exact"], (name, row)
            assert row["regions"][1] < row["regions"][0], (name, row)
            assert sum(row["rewrites"].values()) >= 1, (name, row)
        rm = res["remat"]
        assert rm["budget_bytes"] < rm["unoptimized_peak_bytes"], rm
        assert rm["plan_size"] >= 1, rm
        assert rm["fits_budget"], rm
        assert rm["within_band"], rm
        assert rm["loss_parity"], rm
        assert rm["recompiles_post_warmup"] == 0, rm
    # headline: the fusible-region reduction on the serving mixed step
    mix = res["fusion"]["serving.mixed_step"]
    _emit({"config": "fusion",
           "value": round(mix["regions"][0] / max(mix["regions"][1], 1), 3),
           "unit": "region_reduction_x", "detail": res})


# --------------------------------------------------------------------------- #
# orchestrator
# --------------------------------------------------------------------------- #

def _run_config(name, timeout):
    env = dict(os.environ)
    if name in ("gpt_hybrid", "mesh", "trainchaos", "fusion"):
        # hybrid/mesh mechanics need 8 devices: the 8-device virtual CPU mesh
        # (one chip, or a four-chip host, cannot hold a dp2 x mp2 x pp2 mesh)
        env["PADDLE_TPU_PLATFORM"] = "cpu"
        flags = env.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            env["XLA_FLAGS"] = (flags +
                                " --xla_force_host_platform_device_count=8")
    t0 = time.time()
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--worker", name],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env,
        cwd=ROOT)
    try:
        stdout, stderr = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        proc.kill()
        stdout, stderr = proc.communicate()
        stderr = f"killed at the {timeout}s limit\n{stderr or ''}"
    doc = None
    for line in reversed((stdout or "").strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                cand = json.loads(line)
            except json.JSONDecodeError:
                continue
            if "config" in cand:
                doc = cand
                break
    if doc is None:
        doc = {"config": name,
               "error": f"rc={proc.returncode}: "
                        f"{(stderr or stdout or '')[-800:]}"}
    doc["wall_s"] = round(time.time() - t0, 1)
    return doc


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--configs", default=",".join(CONFIGS))
    ap.add_argument("--timeout", type=int,
                    default=int(os.environ.get("SUITE_TIMEOUT", "1500")))
    ap.add_argument("--smoke", metavar="CONFIG",
                    help="run ONE config in-process at tier-1-safe smoke "
                         "shapes and print its JSON line (serving, chaos, "
                         "spec, mesh, trainchaos, fusion, fleet, obs, "
                         "control)")
    args = ap.parse_args()

    if args.smoke:
        smokes = {"serving": run_serving, "chaos": run_chaos,
                  "spec": run_spec, "mesh": run_mesh,
                  "trainchaos": run_trainchaos, "fusion": run_fusion,
                  "fleet": run_fleet, "obs": run_obs,
                  "control": run_control}
        if args.smoke not in smokes:
            ap.error(f"--smoke supports {sorted(smokes)}, "
                     f"not {args.smoke!r}")
        smokes[args.smoke](smoke=True)
        return

    rows = []
    for name in args.configs.split(","):
        name = name.strip()
        if not name:
            continue
        if name not in CONFIGS:
            print(f"[suite] unknown config {name!r} "
                  f"(choices: {', '.join(CONFIGS)}; llama -> bench.py)",
                  file=sys.stderr)
            continue
        print(f"[suite] running {name} ...", file=sys.stderr, flush=True)
        doc = _run_config(name, args.timeout)
        doc["ts"] = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
        rows.append(doc)
        try:
            with open(RESULTS, "a") as f:
                f.write(json.dumps(doc) + "\n")
        except OSError:
            pass
        print(f"[suite] {name}: "
              f"{doc.get('value', doc.get('error', '?'))} "
              f"{doc.get('unit', '')}", file=sys.stderr, flush=True)
    print(json.dumps(rows, indent=2))


if __name__ == "__main__":
    if "--worker" in sys.argv:
        which = sys.argv[sys.argv.index("--worker") + 1]
        import paddle_tpu

        paddle_tpu.device.enable_compile_cache()
        {"lenet": run_lenet, "resnet50": run_resnet50,
         "bert_dp": run_bert_dp, "gpt_hybrid": run_gpt_hybrid,
         "serving": run_serving, "chaos": run_chaos,
         "spec": run_spec, "mesh": run_mesh,
         "trainchaos": run_trainchaos, "fusion": run_fusion,
         "fleet": run_fleet, "obs": run_obs,
         "control": run_control}[which]()
    else:
        main()
