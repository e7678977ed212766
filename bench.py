"""Benchmark: flagship LLaMA training throughput on the chip.

Prints ONE JSON line: {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}.

The reference publishes no in-tree numbers (BASELINE.md); vs_baseline is therefore
reported against the analytic hardware roofline: achieved model FLOP/s utilisation (MFU)
— the fraction of the chip's published peak matmul throughput the training step sustains.

`python bench.py` runs in this process and measures only on a TPU: with no TPU it
exits non-zero and prints no metric line. A block that fails raises; a kernel that
does not compile is an error, not a reason to measure another path. Nothing is
cached or replayed. Cells, metrics and BENCHMARK.json are the next PR's
(ROADMAP.md Speed 0(b)); this file is what is left of the old single-metric bench.
"""
from __future__ import annotations

import json
import os
import sys
import time


# --------------------------------------------------------------------------- #
# worker
# --------------------------------------------------------------------------- #

# Peak dense bf16 matmul FLOP/s of one chip, keyed by jax's device_kind.
# Source: Google Cloud TPU documentation, system architecture pages
# ("TPU v5e": 197 TFLOP/s bf16; v4 275; v5p 459; v6e 918). A device that is
# not in the table is an error, not a default.
_PEAK_BF16_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12, "TPU v5e": 197e12,
    "TPU v5": 459e12, "TPU v5p": 459e12,
    "TPU v6 lite": 918e12, "TPU v6e": 918e12,
}


def _peak_flops(device):
    """Peak bf16 FLOP/s of ``device`` (the MFU denominator); KeyError for a
    device_kind the table does not hold."""
    return _PEAK_BF16_FLOPS[device.device_kind]


def _log(msg):
    msg = f"[{time.strftime('%H:%M:%S')}] {msg}"
    print(msg, file=sys.stderr, flush=True)
    path = os.environ.get("BENCH_LOG_FILE")
    if path:
        try:
            with open(path, "a") as f:
                f.write(msg + "\n")
        except OSError:
            pass


def _check_flash_attention():
    """The Pallas kernel on the device: correctness vs the math path (raises
    past the bf16 tolerance), kernel-vs-math timing, and one backward."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from paddle_tpu.nn.functional.flash_attention import _math_sdpa
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_fwd

    B, S, H, D = 2, 1024, 8, 128
    r = np.random.RandomState(0)
    q, k, v = (jnp.asarray(r.standard_normal((B, S, H, D)), jnp.bfloat16)
               for _ in range(3))

    flash = jax.jit(lambda q, k, v: flash_attention_fwd(q, k, v, causal=True))
    math = jax.jit(lambda q, k, v: _math_sdpa(q, k, v, causal=True))
    err = float(jnp.max(jnp.abs(flash(q, k, v).astype(jnp.float32)
                                - math(q, k, v).astype(jnp.float32))))
    if not err < 2e-2:
        raise AssertionError(f"flash attention off the math path by {err}")

    def _time(fn, iters=20):
        _force(fn(q, k, v))
        t0 = time.perf_counter()
        out = None
        for _ in range(iters):
            out = fn(q, k, v)
        _force(out)
        return (time.perf_counter() - t0) / iters * 1e3

    info = {"max_abs_err": err, "flash_ms": round(_time(flash), 3),
            "math_ms": round(_time(math), 3)}
    # backward through the custom VJP as well
    g = jax.jit(jax.grad(lambda q: flash(q, k, v).astype(jnp.float32).sum()))
    _force(g(q))
    return info


def _dispatch_bench():
    """Eager per-op dispatch overhead (us/op): the reference's C++ hot path is
    ~us (SURVEY §3.1); ours is Python defop dispatch + lazy jit-cached vjp.
    Measured on tiny tensors so the number is dispatch, not compute."""
    import numpy as np

    import paddle_tpu as paddle

    x = paddle.to_tensor(np.random.RandomState(0).randn(4, 4).astype("float32"))
    y = paddle.to_tensor(np.random.RandomState(1).randn(4, 4).astype("float32"))
    xg = paddle.to_tensor(np.random.RandomState(2).randn(4, 4).astype("float32"),
                          stop_gradient=False)

    def _t(f, n=300):
        f()  # warm (fills the per-signature caches)
        t0 = time.perf_counter()
        for _ in range(n):
            f()
        return round((time.perf_counter() - t0) / n * 1e6, 1)

    def fwd_bwd():
        xg.clear_grad()
        (xg + y).sum().backward()

    import jax.numpy as jnp

    xv, yv = x.value, y.value
    out = {
        "raw_jnp_add": _t(lambda: jnp.add(xv, yv)),  # the dispatch floor
        "add_tape_off": _t(lambda: x + y),
        "add_tape_on_fwd": _t(lambda: xg + y),
        "matmul_tape_off": _t(lambda: x @ y),
        "add_fwd_bwd": _t(fwd_bwd, 150),
    }
    return out


def _trace_overhead_bench():
    """Span-tracing tax on the dispatch microbench: us/op with tracing
    enabled vs disabled (the sampled dispatch.op spans are the only
    enabled-mode cost on this path). Stamped as detail.trace_overhead so
    future BENCH_*.json rounds track the trace tax like any other
    regression."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.monitor import trace

    y = paddle.to_tensor(np.random.RandomState(1).randn(4, 4).astype("float32"))
    xg = paddle.to_tensor(np.random.RandomState(2).randn(4, 4).astype("float32"),
                          stop_gradient=False)

    def _t(f, n=60, reps=5):
        # min-of-reps floor (tests/test_monitor.py _floor_us): the DELTA of
        # two measurements is meaningless if either one eats a scheduler
        # hiccup
        f()
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            for _ in range(n):
                f()
            best = min(best, (time.perf_counter() - t0) / n * 1e6)
        return round(best, 2)

    assert not trace.enabled()
    off = _t(lambda: xg + y)
    trace.enable()
    try:
        on = _t(lambda: xg + y)
    finally:
        trace.disable()
        trace.reset()
    return {
        "add_tape_on_fwd_us_trace_off": off,
        "add_tape_on_fwd_us_trace_on": on,
        "delta_us": round(on - off, 2),
        "dispatch_sample_every": trace.dispatch_sample_every(),
    }


def _sanitizer_overhead_bench():
    """graftsan tax on the dispatch microbench: us/op with sanitizers off
    (the shipping default — must be ~zero: no hook in the concretize slot,
    no wrapped locks on the dispatch path) vs fully enabled. Stamped as
    detail.sanitizer_overhead so future BENCH_*.json rounds track the
    sanitizer tax like the trace tax."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.analysis import sanitizers as san

    y = paddle.to_tensor(np.random.RandomState(3).randn(4, 4).astype("float32"))
    xg = paddle.to_tensor(np.random.RandomState(4).randn(4, 4).astype("float32"),
                          stop_gradient=False)

    def _t(f, n=60, reps=5):
        f()
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            for _ in range(n):
                f()
            best = min(best, (time.perf_counter() - t0) / n * 1e6)
        return round(best, 2)

    # force a clean 'off' measurement even when PADDLE_TPU_SANITIZE enabled
    # them at import (the natural way a user looks at the sanitizer tax)
    san.disable()
    san.reset()
    off = _t(lambda: xg + y)
    san.enable()
    try:
        on = _t(lambda: xg + y)
    finally:
        san.disable()
        san.reset()
    return {
        "add_tape_on_fwd_us_sanitize_off": off,
        "add_tape_on_fwd_us_sanitize_on": on,
        "delta_us": round(on - off, 2),
    }


def _numerics_overhead_bench():
    """numsan tax at a step boundary: us/check with the numerics
    sanitizer off (the shipping default — one slot load, nothing else)
    vs on (the compiled all-finite reduction and its ONE host bool over
    a serving-shaped region set). Stamped as detail.numerics beside
    detail.sanitizer_overhead so BENCH_*.json rounds track the sentinel
    tax the same way."""
    import numpy as np

    import jax.numpy as jnp

    from paddle_tpu.analysis import sanitizers as san

    toks = jnp.asarray(np.zeros((8, 4), np.int32))
    pools = jnp.asarray(
        np.random.RandomState(5).randn(64, 128).astype("float32"))
    regions = (("tokens", toks), ("kv_pools", pools))

    def _t(f, n=60, reps=5):
        f()
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            for _ in range(n):
                f()
            best = min(best, (time.perf_counter() - t0) / n * 1e6)
        return round(best, 2)

    san.disable()
    san.reset()
    off = _t(lambda: san.numsan_check("bench.step", regions))
    san.enable("numerics")
    try:
        on = _t(lambda: san.numsan_check("bench.step", regions))
    finally:
        san.disable()
        san.reset()
    return {
        "numsan_check_us_off": off,
        "numsan_check_us_on": on,
        "delta_us": round(on - off, 2),
    }


# the donated fused train step + timing-loop machinery is shared with
# bench_suite.py — see bench_common.py


def _decode_bench(model, cfg):
    """Serving metric: KV-cache greedy decode latency/throughput on the same
    flagship model (the inference-engine number next to the training MFU)."""
    import numpy as np

    import jax

    from paddle_tpu.models.llama_decode import LlamaDecodeEngine

    batch, prefill, steps = 8, 128, 32
    # BENCH_DECODE_KV=int8 measures the quantized KV cache (half the KV
    # read bandwidth — the decode bottleneck); any other value (bf16/fp16/
    # unset) runs the full-precision default. BENCH_DECODE_LAYOUT=paged
    # runs the block-table cache (models/paged_kv.py) — ms/token should
    # match dense (same gather bandwidth) while cache memory drops to
    # blocks-actually-used.
    kv_env = (os.environ.get("BENCH_DECODE_KV") or "").strip().lower()
    kv_dtype = "int8" if kv_env == "int8" else None
    layout_env = (os.environ.get("BENCH_DECODE_LAYOUT") or "").strip().lower()
    layout = "paged" if layout_env == "paged" else None
    eng = LlamaDecodeEngine(model, max_len=prefill + steps + 1,
                            kv_cache_dtype=kv_dtype, kv_cache_layout=layout)
    kv_label = ("int8" if kv_dtype else str(eng.emb.dtype)) \
        + ("/paged" if layout else "")
    r = np.random.RandomState(0)
    ids = r.randint(0, cfg.vocab_size, (batch, prefill)).astype("int32")

    logits, cache, pos = eng.prefill(ids)
    tok = logits.argmax(-1).astype("int32")[:, None]
    logits, cache = eng.decode_step(tok, cache, pos)   # compile the step
    _force(logits)
    pos += 1

    t0 = time.perf_counter()
    for _ in range(steps):
        tok = logits.argmax(-1).astype("int32")[:, None]
        logits, cache = eng.decode_step(tok, cache, pos)
        pos += 1
    _force(logits)
    dt = time.perf_counter() - t0
    return {
        "batch": batch, "prefill": prefill, "steps": steps,
        "kv_cache": kv_label,
        "ms_per_token": round(dt / steps * 1e3, 3),
        "tokens_per_sec": round(batch * steps / dt, 1),
    }


def _serving_bench(model):
    """Serving metric: continuous batching (chunked prefill + radix
    prefix cache, models/serving.py) vs the static-batch baseline at
    equal batch capacity, on a Poisson open-loop mixed-length workload
    with shared prompt prefixes. Emits serving_tokens_per_sec, TTFT
    p50/p99 and the prefix-hit rate, plus the speculative-decoding rows
    (spec-on vs spec-off tokens/s, drafted/accepted counts, accept rate;
    bench_common.spec_bench) (docs/serving.md)."""
    from bench_common import serving_bench, spec_bench

    params = dict(max_batch=16, block_size=64, chunk_size=128,
                  max_step_tokens=None, decode_burst=8, n_requests=24,
                  n_groups=3, prefix_blocks=4, tail_range=(32, 128),
                  new_range=(32, 128), repeats=2)
    spec_params = dict(max_batch=4, block_size=64, chunk_size=64,
                       max_step_tokens=128, decode_burst=8,
                       spec_lookahead=16, n_requests=12, n_groups=3,
                       pattern_len=64, head_len=16, max_new=256,
                       repeats=2)
    out = serving_bench(model, **params)
    spec = spec_bench(model, **spec_params)
    out.update({k: spec[k] for k in (
        "spec_off_tokens_per_sec", "spec_on_tokens_per_sec",
        "spec_speedup", "spec_drafted_tokens", "spec_accepted_tokens",
        "spec_accept_rate", "spec_tokens_match", "spec_lookahead")})
    return out


def _fusion_bench(model, optimizer, loss_fn, step_box, ids, labels):
    """detail.fusion: the graftopt transform over THIS run's live train
    step — applied rewrites, eqn/fusible-region deltas, GI003 peak
    before/after, and (BENCH_FUSION_MEASURE=1: it pays a second compile)
    the optimized program's step time vs the original. Plus the remat
    planner's answer for this model at 95% of the unoptimized GI003
    peak: the plan size the budget knob would buy (flags restored —
    this is a what-if, not a mutation of the measured run)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.analysis.jaxpr import estimate, trace
    from paddle_tpu.analysis.jaxpr import opt as gopt
    from paddle_tpu.analysis.jaxpr import planner as gplanner

    step = step_box["step"]
    state = step_box["state"]
    args = (*state, ids, labels)
    prog = trace(step, args, "bench.train_step")
    est_before = estimate(prog)
    oprog, res = gopt.optimize_program(prog)
    est_after = estimate(oprog)
    info = {
        "rewrites": res.by_rule(),
        "eqns": [res.eqns_before, res.eqns_after],
        "regions": [res.regions_before, res.regions_after],
        "gi003_peak": [est_before["peak_bytes"], est_after["peak_bytes"]],
    }

    if os.environ.get("BENCH_FUSION_MEASURE"):
        # rebuild + re-jit the optimized program and race it against the
        # original (threaded donated state, fresh copies per side)
        opt_fn, _ = gopt.optimize_jitted(step, args, name="bench.train_step")

        def run(f, n=3):
            pv, av, mv = jax.tree_util.tree_map(jnp.array, state)
            loss, pv, av, mv = f(pv, av, mv, ids, labels)   # warm/compile
            _force(loss)
            t0 = time.perf_counter()
            for _ in range(n):
                loss, pv, av, mv = f(pv, av, mv, ids, labels)
            _force(loss)
            return (time.perf_counter() - t0) / n, loss

        t_raw, l_raw = run(step)
        t_opt, l_opt = run(opt_fn)
        info["step_ms"] = [round(t_raw * 1e3, 2), round(t_opt * 1e3, 2)]
        info["speedup"] = round(t_raw / max(t_opt, 1e-9), 3)
        info["loss_match"] = bool(gopt.bit_exact(l_raw, l_opt))

    # the budget knob's what-if: plan size at 95% of the unoptimized peak
    cands = gplanner.remat_candidates(model)
    saved = [(layer, layer._recompute) for _n, layer in cands]
    try:
        budget = int(est_before["peak_bytes"] * 0.95)
        plan = gplanner.plan_for_model(model, optimizer, loss_fn,
                                       (ids, labels), budget)
        info["remat_plan"] = {
            "budget_bytes": budget,
            "base_peak_bytes": plan["base_peak_bytes"],
            "planned_peak_bytes": plan["planned_peak_bytes"],
            "plan_size": len(plan["sites"]),
            "sites": plan["sites"],
            "n_traces": plan["n_traces"],
        }
    except gplanner.RematPlanError as e:
        info["remat_plan"] = {"budget_bytes": int(
            est_before["peak_bytes"] * 0.95),
            "unsatisfiable": str(e)[:160]}
    finally:
        for layer, flag in saved:
            layer._recompute = flag
    return info


from bench_common import force as _force  # noqa: E402

def worker():
    import numpy as np

    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.autograd import tape  # noqa: F401 - keeps tape module hot
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"bench.py measures on a TPU; jax's platform here is "
                         f"{dev.platform!r} (no metric printed)")
    peak = _peak_flops(dev)    # KeyError for an unknown device_kind, up front
    _log(f"[bench] device={dev} kind={dev.device_kind} compile cache at "
         f"{paddle.device.enable_compile_cache()}")

    flash_info = ({"skipped": True}
                  if os.environ.get("BENCH_SKIP_FLASHCHECK")
                  else _check_flash_attention())
    _log(f"[bench] flash_attention check: {flash_info}")

    if os.environ.get("BENCH_SKIP_DISPATCH"):
        dispatch_us = trace_overhead = sanitizer_overhead = numerics = \
            {"skipped": True}
    else:
        dispatch_us = _dispatch_bench()
        trace_overhead = _trace_overhead_bench()
        sanitizer_overhead = _sanitizer_overhead_bench()
        numerics = _numerics_overhead_bench()
    _log(f"[bench] dispatch_us: {dispatch_us}")
    _log(f"[bench] trace_overhead: {trace_overhead}")
    _log(f"[bench] sanitizer_overhead: {sanitizer_overhead}")
    _log(f"[bench] numerics: {numerics}")

    # ~540M-param model in bf16 (per-layer remat + Pallas flash attention keep
    # activations O(S)). Invented widths — ROADMAP Reach replaces this config
    # with published ones; the env knobs sweep shapes without editing the file
    hidden = int(os.environ.get("BENCH_HIDDEN", "2048"))
    layers = int(os.environ.get("BENCH_LAYERS", "8"))
    inter = int(os.environ.get("BENCH_INTER", str(hidden * 11 // 4)))
    seq = int(os.environ.get("BENCH_SEQ", "2048"))
    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=hidden, intermediate_size=inter,
        num_hidden_layers=layers,
        num_attention_heads=hidden // 128,
        num_key_value_heads=hidden // 128,
        max_position_embeddings=seq, dtype="bfloat16",
        recompute=os.environ.get("BENCH_REMAT", "1") != "0",
        recompute_granularity=os.environ.get("BENCH_REMAT_GRAN", "full"),
        fused_head_ce=os.environ.get("BENCH_FUSED_CE", "0") != "0")
    batch = int(os.environ.get("BENCH_BATCH", "8"))
    iters = int(os.environ.get("BENCH_ITERS", "10"))

    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.to(dtype="bfloat16")
    optimizer = paddle.optimizer.AdamW(
        learning_rate=1e-4, parameters=model.parameters(),
        multi_precision=True)

    from bench_common import build_step, timed_loop

    r = np.random.RandomState(0)
    ids = jnp.asarray(r.randint(0, cfg.vocab_size, (batch, seq)), jnp.int32)
    labels = jnp.asarray(r.randint(0, cfg.vocab_size, (batch, seq)), jnp.int32)

    attention_path = ("xla_math" if os.environ.get("PADDLE_TPU_DISABLE_PALLAS")
                      else "pallas_flash")

    def loss_fn(m, ids_t, labels_t):
        loss, _ = m(ids_t, labels=labels_t)
        return loss

    step, state_fn, params = build_step(model, optimizer, loss_fn)
    _log(f"[bench] timed loop: {iters} steps...")
    dt, (pv, av, mv), loss = timed_loop(
        step, state_fn(), (ids, labels), iters,
        log=lambda m: _log(f"[bench]   {m}"))
    step_box = {"step": step, "state": (pv, av, mv)}   # for the HBM row
    _log(f"[bench] timed loop done: {dt * 1e3:.1f} ms/step")

    tokens_per_s = batch * seq / dt

    # the compiled step donated the params' original buffers; rebind the live
    # Parameters to the final trained values before anything reads them again
    for p, v in zip(params, pv):
        p._replace_value(v)

    decode_info = ({"skipped": True} if os.environ.get("BENCH_SKIP_DECODE")
                   else _decode_bench(model, cfg))
    _log(f"[bench] decode: {decode_info}")

    serving_info = ({"skipped": True} if os.environ.get("BENCH_SKIP_SERVING")
                    else _serving_bench(model))
    _log(f"[bench] serving: {serving_info}")

    # graftir HBM row: the GI003 static estimate of THIS run's train step
    # (trace-only) vs the live program's bytes — jax.Array state bytes
    # always, plus the compiler's own memory analysis with
    # BENCH_HBM_MEASURE=1 (it pays a second compile of the step)
    if os.environ.get("BENCH_SKIP_HBM"):
        hbm_info = {"skipped": True}
    else:
        from paddle_tpu.analysis import jaxpr as _graftir

        _hargs = (*step_box["state"], ids, labels)
        _est = _graftir.estimate_fn(step_box["step"], _hargs,
                                    name="bench.train_step")
        hbm_info = {
            "estimate_peak_bytes": _est["peak_bytes"],
            "estimate_bounds": [_est["peak_sched_bytes"],
                                _est["peak_order_bytes"]],
            "args_bytes": _est["args_bytes"],
            "live_state_bytes": int(sum(
                getattr(v, "nbytes", 0) for v in
                jax.tree_util.tree_leaves(step_box["state"]))),
        }
        if os.environ.get("BENCH_HBM_MEASURE"):
            hbm_info["measured"] = _graftir.measure_compiled(
                step_box["step"], _hargs)
    _log(f"[bench] hbm: {hbm_info}")

    # graftopt fusion row: rewrites + region deltas over THIS run's live step,
    # and the remat planner's plan size at 95% of the unoptimized GI003 peak
    # (docs/ir_analysis.md)
    fusion_info = ({"skipped": True} if os.environ.get("BENCH_SKIP_FUSION")
                   else _fusion_bench(model, optimizer, loss_fn, step_box,
                                      ids, labels))
    _log(f"[bench] fusion: {fusion_info}")

    # 6*N FLOPs/token (fwd+bwd) + causal attention term — the standard
    # PaLM appendix-B accounting, owned by monitor/timeline.py since
    # ISSUE 15 (one formula, shared with obs_bench/perf analytics)
    from paddle_tpu.monitor.timeline import transformer_flops_per_token

    n_params = sum(int(np.prod(p.shape)) for p in params)
    flops_per_token = transformer_flops_per_token(
        n_params, num_layers=cfg.num_hidden_layers,
        hidden=cfg.hidden_size, seq=seq)
    mfu = tokens_per_s * flops_per_token / peak

    from paddle_tpu import monitor as _monitor

    doc = {
        "metric": "llama_train_tokens_per_sec",
        "value": round(tokens_per_s, 2),
        "unit": "tokens/s",
        "vs_baseline": round(mfu, 4),
        "detail": {
            "model_params": n_params,
            "batch": batch, "seq": seq,
            "step_ms": round(dt * 1e3, 2),
            "device": {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(jax.devices())},
            "mfu": round(mfu, 4),
            "loss": float(jax.device_get(loss)),
            "attention_path": attention_path,
            "remat": {"on": cfg.recompute,
                      "granularity": getattr(cfg, "recompute_granularity",
                                             "full")},
            "flash_attention": flash_info,
            "dispatch_us": dispatch_us,
            "trace_overhead": trace_overhead,
            "sanitizer_overhead": sanitizer_overhead,
            "numerics": numerics,
            "decode": decode_info,
            "serving": serving_info,
            "hbm_estimate": hbm_info,
            "fusion": fusion_info,
            # git rev, hostname, platform, timestamps: so the line can be
            # validated rather than trusted
            "provenance": _monitor.provenance(),
        },
    }
    print(json.dumps(doc))


if __name__ == "__main__":
    worker()
