"""Driver ``serve_engine``: the program's ``ContinuousBatchingEngine`` on one
chip under a backlog, driven through ``submit`` / ``step`` by one thread.

Set-up first answers the backlog's last prompt (which no window reaches) with
a few tokens: both of the engine's programs (mixed step, decode burst) compile
there, so no request of the backlog has a compile inside its life. It then
submits the backlog (every request is due at t = 0) and steps the engine
``prime_steps`` times, which leaves the lanes full of the backlog's own
requests. The window opens mid-stream, so all of it is the saturated state, and
closes mid-stream too. After the close the backlog goes on feeding the lanes
until every request that was in flight at the close has ended, so that each
has its own first-token and finish time from the same state of the engine as
the window had; then the peak memory is read, the engine is freed, and the
reference scores a seeded sample of what finished inside the window.

The model and its weights are the cell's builder's (``ctx["builder"]``); the
engine's settings come from the configuration's ``engine`` group. Traffic
parameters: see the generator, plus ``prime_steps``, ``trace_seconds``,
``check_requests``, ``grace_seconds`` and ``counters`` (the program's monitor
counters whose increase over the window a traced run reads). Times in the
records are seconds from the window's start (negative: before it opened).

**The traced slice and its edges.** The engine keeps one step in flight (PR
37), so between two calls of ``step()`` the device is busy. Before the
profiler starts, and again before it is stopped, a traced run therefore waits
for whatever is queued on the device (``common.device_rest``: a trivial
program of the benchmark's own, blocked on; nothing of the engine is reached
into), and reads every counter series of the program's monitor at those two
instants. The step in flight at the first edge ends BEFORE the trace begins;
every step dispatched between the edges, the one in flight at the last edge
too, runs INSIDE it. The readers get the increase as ``slice_counters``:

- what the program adds when it DISPATCHES a step (``attn_blocks_total``,
  ``attn_kind_blocks_total``, ``attn_lanes_total``, ``linear_runs_total``,
  ``linear_tokens_total``, ``dispatch_total``) is counted for exactly the
  steps whose operations the trace holds;
- what it adds when it ROUTES a step, one call later (``steps_total``,
  ``generated_tokens_total``, ``expert_pairs_total``), lags by the one step
  in flight: the same NUMBER of steps, moved by one (the first edge's step in
  flight is in, the last edge's is not).

``notes.slice_dispatches`` is the first kind's step count, to lay beside the
serving programs on the trace's ``XLA Modules`` line (``run.py`` prints them
as ``programs``): equal, where the wait does what it is for. The wait costs
the device one start-up gap at each edge, both outside the slice as the
device's clock measures it; a traced run reports no end-to-end metric.
"""
from __future__ import annotations

import gc
import time

import numpy as np

import common
import compare
import stats

WARMUP_MAX_NEW = 8        # tokens asked of the warm-up prompt: two bursts
DISPATCHES = "paddle_tpu_serving_dispatch_total"    # one a dispatched step


def submit_all(eng, offered):
    """Hand the backlog to the engine; one record per request, in order."""
    records = []
    for req in offered:
        rec = {"submit": time.perf_counter(), "first": None, "finish": None,
               "n_out": 0, "asked": req["max_new"],
               "n_prompt": len(req["prompt"]), "tokens": None}
        with common.annotate("bench.submit"):
            rec["rid"] = eng.submit(req["prompt"], max_new_tokens=req["max_new"])
        records.append(rec)
    return records


def step_until(eng, by_rid, stop, samples, tracer=None, trace_from=None):
    """``eng.step()`` until ``stop(now, steps_made)`` or the engine runs dry.
    Fills the records of what finishes; ``samples`` gets (requests in a slot
    after the call, requests queued, requests it handed back, seconds) of each
    call (``stats.lanes_in_use`` makes a step's lanes of them). The profiler
    starts at ``trace_from`` and is stopped by the caller. Returns the number
    of steps made."""
    n = 0
    while eng.num_active or eng.num_pending:
        now = time.perf_counter()
        if stop(now, n):
            break
        if tracer is not None and tracer.t_start is None and now >= trace_from:
            tracer.start()
            now = time.perf_counter()
        with common.annotate("bench.eng_step"):
            finished = eng.step()
        t_done = time.perf_counter()
        n += 1
        samples.append((eng.num_active, eng.num_pending, len(finished),
                        t_done - now))
        for rid, tokens in finished:
            rec = by_rid[rid]
            rec["finish"] = t_done
            rec["tokens"] = np.asarray(tokens, np.int32)
            rec["n_out"] = len(tokens)
            rec["first"] = rec["submit"] + eng.pop_stats(rid)["ttft_ns"] * 1e-9
    return n


def sample_of(records, offered, window_s, seed, cfg, traffic):
    """A sample, drawn from the seed, of the requests that the window finished,
    with the longest in it, laid out for the reference: prompt + served tokens
    padded to the engine's ``max_len``, the positions whose next token was
    served, the served tokens, which entries are real, and how many answers
    came back with another number of tokens than was asked for."""
    done = sorted((i for i, r in enumerate(records)
                   if r["finish"] is not None and 0.0 < r["finish"] <= window_s),
                  key=lambda i: -(records[i]["n_prompt"] + records[i]["n_out"]))
    if not done:
        return None
    n_check = int(traffic.get("check_requests", 8))
    rng = np.random.Generator(np.random.PCG64([int(seed), 2]))
    rest = rng.choice(done[1:], size=min(n_check - 1, len(done) - 1),
                      replace=False).tolist()
    sample = done[:1] + sorted(rest)
    max_len, rows = int(cfg["engine"]["max_len"]), int(traffic["output"]["max"])
    tokens = np.zeros((n_check, max_len), np.int32)
    positions = np.zeros((n_check, rows), np.int32)
    served = np.zeros((n_check, rows), np.int32)
    valid = np.zeros((n_check, rows), bool)
    short = 0
    for j, i in enumerate(sample):
        rec, req = records[i], offered[i]
        p, n = rec["n_prompt"], rec["n_out"]
        short += int(n != rec["asked"])
        n = min(n, rows)
        tokens[j, :p] = req["prompt"]
        tokens[j, p:p + n - 1] = rec["tokens"][:n - 1]
        positions[j, :n] = p - 1 + np.arange(n)
        served[j, :n] = rec["tokens"][:n]
        valid[j, :n] = True
    return {"tokens": tokens, "positions": positions, "served": served,
            "valid": valid, "short": short, "requests": len(sample)}


def check(ref_mod, seed, cfg, sample, control=None):
    """The numbers compared: how far below the reference's best the served
    tokens' logits lie. With ``control`` (a precision the reference module
    knows) the same is read for the tokens that precision puts first, at the
    same positions of the same prompts and tokens."""
    if sample is None:
        return {"token_logit_gap": float("inf"), "short_answers": 0.0}
    ref = ref_mod.ServeReference(seed, cfg)
    query = sample["served"]
    if control is not None:
        low = ref_mod.ServeReference(seed, cfg, quant=control)
        _, query = low.gaps(sample["tokens"], sample["positions"], query)
    gaps, _ = ref.gaps(sample["tokens"], sample["positions"], np.asarray(query))
    return compare.serve_numbers(np.asarray(gaps), sample["valid"],
                                 sample["short"])


def counter_series():
    """Every counter series of the program's monitor, now:
    ``{counter: {"label=value,...": count}}``, keyed as ``monitor.snapshot()``
    keys them (no histogram is ranked and nothing is formatted: this is read
    inside the window)."""
    from paddle_tpu import monitor

    return {name: {",".join(f"{k}={v}" for k, v in zip(m.labelnames, values)):
                   child.value for values, child in m.children()}
            for name, m in monitor.registry.collect() if m.kind == "counter"}


def increase(before, after):
    """``after - before`` of two ``counter_series``; a series that the first
    reading lacks started at nought."""
    return {name: {key: value - before.get(name, {}).get(key, 0.0)
                   for key, value in series.items()}
            for name, series in after.items()}


def build(model, cfg):
    from paddle_tpu.models.serving import ContinuousBatchingEngine

    return ContinuousBatchingEngine(model, **cfg["engine"])


def prime(eng, offered, traffic):
    """Compile both programs on the backlog's last prompt, then submit the
    rest of the backlog and step the engine ``prime_steps`` times: the warm-up
    is the cell's own traffic, and leaves the lanes full of it."""
    last = offered[-1]
    eng.submit(last["prompt"],
               max_new_tokens=min(last["max_new"], WARMUP_MAX_NEW))
    while eng.num_active or eng.num_pending:
        eng.step()
    records = submit_all(eng, offered[:-1])
    by_rid = {r["rid"]: r for r in records}
    steps = int(traffic["prime_steps"])
    made = step_until(eng, by_rid, lambda now, n: n >= steps, [])
    if made < steps:
        raise RuntimeError("the backlog ran dry while the lanes were primed")
    return records, by_rid


def window(eng, records, by_rid, seconds, traffic, tracer=None, counters=None):
    """Open mid-stream, step for ``seconds``, close, and go on stepping until
    what was in flight at the close has ended. Moves the records' times to the
    window's clock and returns what the window counted. ``counters``
    ``{name: read}`` are read at the opening and at the close."""
    slice_s = float(traffic.get("trace_seconds", 4.0))
    samples = []
    counted = {name: read() for name, read in (counters or {}).items()}
    t_open = time.perf_counter()
    # the traced slice is the window's last seconds: the profiler is stopped
    # after the close, so that its export takes no step from the window
    step_until(eng, by_rid, lambda now, n: now - t_open >= seconds, samples,
               tracer=tracer, trace_from=t_open + seconds - slice_s)
    t_close = time.perf_counter()
    counted = {name: read() - counted[name]
               for name, read in (counters or {}).items()}
    if tracer is not None and tracer.running:
        tracer.stop()
    if t_close - t_open < seconds:
        raise RuntimeError("the backlog did not outlast the window")
    # the queue is admitted in order, so what has left it by now is what the
    # window started; the queue keeps feeding the lanes while those end
    started = records[:len(records) - eng.num_pending]
    in_flight = [r for r in started if r["finish"] is None]
    grace = float(traffic.get("grace_seconds", 90.0))
    step_until(eng, by_rid, lambda now, n: now - t_close >= grace or all(
        r["finish"] is not None for r in in_flight), [])
    for r in records:
        for k in ("submit", "first", "finish"):
            if r[k] is not None:
                r[k] -= t_open
    # what the window touched: in a lane at some time between its edges
    attempted = [r for r in started if r["finish"] is None or r["finish"] > 0.0]
    return {"window_s": t_close - t_open, "attempted": attempted,
            "drain_s": time.perf_counter() - t_close,
            "occupancy_samples": samples, "counters": counted}


def _finished_inside(records, window_s):
    return [r for r in records
            if r["finish"] is not None and 0.0 < r["finish"] <= window_s]


def run(ctx):
    import jax

    cfg, traffic, phases = ctx["config"], ctx["traffic"], ctx["phases"]
    seed, seconds = ctx["seed"], ctx["seconds"]
    builder, tracer = ctx["builder"], ctx.get("tracer")

    with phases.phase("construct_model"):
        model = builder.construct(cfg)
    with phases.phase("load_weights"):
        n_params = common.load_weights(
            model, builder.weights(seed, cfg, cfg["torch_dtype"]))
        model.eval()
    with phases.phase("build_engine"):
        eng = build(model, cfg)
        if ctx.get("fault"):
            ctx["fault"](eng)
    offered = ctx["generator"].requests(seed, traffic, cfg)
    with phases.phase("prime_lanes"):
        records, by_rid = prime(eng, offered, traffic)
    counters = None
    if tracer is not None:
        # a traced run reads the program's own counters beside its clock
        from paddle_tpu import monitor

        monitor.enable()
        counters = {name: (lambda c=monitor.counter(name): c.value)
                    for name in traffic.get("counters", [])}
        tracer.read_at_edges = counter_series
        tracer.rest = common.device_rest(jax.devices()[0])
    compiles_before = ctx["compiles"].n

    ctx["mark_window_start"]()
    w = window(eng, records, by_rid, seconds, traffic, tracer, counters)
    compiled = ctx["compiles"].n - compiles_before
    attempted = w["attempted"]
    failed = [r for r in attempted
              if r["finish"] is None or r["n_out"] != r["asked"]]
    peak = common.peak_bytes(jax.devices()[:1])
    kv_pool_bytes = int(eng.kv_pool_bytes)

    # -- free the engine, then the reference over a seeded sample ------------
    del eng, model
    gc.collect()
    t_ref = time.perf_counter()
    sample = sample_of(records, offered, w["window_s"], seed, cfg, traffic)
    numbers = check(ctx["reference"], seed, cfg, sample)
    numbers["compiled_in_window"] = float(compiled)
    public = [{k: r[k] for k in ("submit", "first", "finish", "n_out",
                                 "n_prompt", "asked")}
              for r in attempted]
    inside = _finished_inside(attempted, w["window_s"])
    step_s = sorted(call[-1] for call in w["occupancy_samples"])
    sliced, slice_notes = {}, {}
    if tracer is not None and len(tracer.edges) == 2:
        sliced = {"slice_counters": increase(*tracer.edges),
                  "slice_seconds": tracer.t_stop - tracer.t_start}
        dispatched = sliced["slice_counters"].get(DISPATCHES)
        if dispatched:                   # a program without the counter: no note
            slice_notes = {"slice_dispatches": sum(dispatched.values())}
    return {
        **sliced,
        "attempted": len(attempted), "failed": len(failed),
        "window_s": w["window_s"], "records": public, "counters": w["counters"],
        "occupancy_samples": stats.lanes_in_use(
            [call[:3] for call in w["occupancy_samples"]]),
        "max_batch": int(cfg["engine"]["max_batch"]),
        "n_params": n_params, "memory_peak_bytes": peak,
        "numbers": numbers,
        "notes": {"reference_s": round(time.perf_counter() - t_ref, 3),
                  "drain_s": round(w["drain_s"], 3),
                  "steps_in_window": len(step_s),
                  "step_s_median": round(step_s[len(step_s) // 2], 4),
                  "step_s_longest": [round(t, 4) for t in step_s[-3:]],
                  "apportioned_tokens_per_s": round(stats.serve_tokens_per_s(
                      attempted, w["window_s"]), 3),
                  "finished_before_window": sum(
                      1 for r in records
                      if r["finish"] is not None and r["finish"] <= 0.0),
                  "in_flight_at_open": sum(
                      1 for r in attempted
                      if r["first"] is not None and r["first"] <= 0.0),
                  "finished_in_window": len(inside),
                  "in_flight_at_close": len(attempted) - len(inside),
                  "checked_requests": sample["requests"] if sample else 0,
                  "checked_tokens": int(sample["valid"].sum()) if sample else 0,
                  "kv_pool_bytes": kv_pool_bytes, **slice_notes},
    }


def readings(ctx, model, seed, with_controls):
    """For the limits (``benchmarks/readings.py``): the numbers one seed gives
    for the program over a window of the cell's own load and, when asked, for
    the float8 control at the same positions of the same prompts and tokens.
    ``model`` is constructed once and reloaded per seed."""
    cfg, traffic = ctx["config"], ctx["traffic"]
    common.load_weights(
        model, ctx["builder"].weights(seed, cfg, cfg["torch_dtype"]))
    model.eval()
    eng = build(model, cfg)
    offered = ctx["generator"].requests(seed, traffic, cfg)
    records, by_rid = prime(eng, offered, traffic)
    w = window(eng, records, by_rid, ctx["seconds"], traffic)
    del eng
    gc.collect()
    ws = w["window_s"]
    sample = sample_of(records, offered, ws, seed, cfg, traffic)
    out = {"program": check(ctx["reference"], seed, cfg, sample),
           "checked_tokens": int(sample["valid"].sum()) if sample else 0,
           "attempted": len(w["attempted"]),
           "finished_in_window": len(_finished_inside(w["attempted"], ws)),
           "tokens_per_s": stats.serve_tokens_per_s(w["attempted"], ws),
           "finished_tokens_per_s": stats.finished_tokens_per_s(w["attempted"], ws),
           "steps": len(w["occupancy_samples"]), "drain_s": w["drain_s"]}
    if with_controls:
        out["control_fp8"] = check(ctx["reference"], seed, cfg, sample,
                                   control="fp8")
    return out
