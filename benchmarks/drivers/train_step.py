"""Driver ``train_step``: the program's ``mesh.parallelize`` step on one chip.

Set-up builds ONE object, the compiled step with its state, drives it from the
seed through its first steps (reading the loss of each, the first gradient's
norms from the optimizer's state after step one, and every parameter's change
after the last), and hands that same object to the window. The window feeds a
new seeded batch each step, made on the host while the device runs the step
before. Once the window has closed and the peak memory is read, the state is
freed and the reference follows the same first steps.

The model and its weights are the cell's builder's (``ctx["builder"]``); the
gradient checks go on reading ``benchmarks/weights.py``'s leaves. Traffic
parameters: ``batch``, ``sequence_length``, ``optimizer`` (``name`` of a
``paddle_tpu.optimizer`` class, ``learning_rate`` and its other arguments),
``parallelize`` (the config handed to ``mesh.parallelize``), ``check_steps``,
``trace_steps``.
"""
from __future__ import annotations

import gc
import time

import common
import compare
import weights as W

# the accumulator that holds the first gradient after one step, and the factor
# that turns it back into the gradient, per optimizer class
FIRST_GRADIENT = {
    "Momentum": ("velocity", lambda opt: 1.0),
    "AdamW": ("moment1", lambda opt: 1.0 / (1.0 - opt.get("beta1", 0.9))),
}


def _loss_fn(model, ids, labels):
    loss, _ = model(ids, labels=labels)
    return loss


def _optimizer(paddle, spec, model):
    args = {k: v for k, v in spec.items() if k != "name"}
    cls = getattr(paddle.optimizer, spec["name"])
    return cls(parameters=model.parameters(), multi_precision=True, **args)


def build(paddle, pmesh, model, traffic, first):
    """The compiled step with its state: the one object that the first steps
    and the window both drive."""
    optimizer = _optimizer(paddle, traffic["optimizer"], model)
    return pmesh.parallelize(model, optimizer, _loss_fn, first,
                             config=dict(traffic["parallelize"]))


def first_steps(handle, first, feed, seed, cfg, traffic):
    """Drive the first steps through the window's own call and feed. Returns
    the program's readings and the next batch to feed."""
    import jax

    opt_spec = traffic["optimizer"]
    acc_key, grad_scale = FIRST_GRADIENT[opt_spec["name"]]
    names = list(handle.param_names)
    program = {"losses": [], "grad_norms": None, "change_norms": None}
    batch = first
    for i in range(int(traffic["check_steps"])):
        loss = handle.step(*batch)
        program["losses"].append(float(jax.block_until_ready(loss.value)))
        if i == 0:
            k = handle._acc_keys[0].index(acc_key)
            scale = grad_scale(opt_spec)
            program["grad_norms"] = {
                n: v * scale for n, v in W.norms(
                    {n: row[k] for n, row in zip(names, handle._av)}).items()}
        batch = next(feed)
    program["change_norms"] = W.change_norms(seed, cfg,
                                             dict(zip(names, handle._mv)))
    return program, batch


def reference_readings(ref_mod, gen, seed, cfg, traffic, **broken):
    """The reference over the same first steps. ``broken`` (``quant``,
    ``rows``) puts a control or a planted fault in its place."""
    ref = ref_mod.TrainReference(seed, cfg, traffic["optimizer"], **broken)
    replay = gen.batches(seed, traffic, cfg)
    out = {"losses": [ref.step(*next(replay))
                      for _ in range(int(traffic["check_steps"]))]}
    out["grad_norms"] = ref.grad_norms
    out["change_norms"] = ref.change_norms()
    return out


def run(ctx):
    import jax

    import paddle_tpu as paddle
    from paddle_tpu import mesh as pmesh

    cfg, traffic, phases = ctx["config"], ctx["traffic"], ctx["phases"]
    seed, seconds = ctx["seed"], ctx["seconds"]
    builder, feed = ctx["builder"], ctx["generator"].batches(seed, traffic, cfg)

    with phases.phase("construct_model"):
        model = builder.construct(cfg)
    with phases.phase("load_weights"):
        n_params = common.load_weights(
            model, builder.weights(seed, cfg, cfg["torch_dtype"]))
        model.train()
    with phases.phase("build_step"):
        first = next(feed)
        handle = build(paddle, pmesh, model, traffic, first)
        if ctx.get("fault"):
            ctx["fault"](handle)
    with phases.phase("first_steps"):
        program, batch = first_steps(handle, first, feed, seed, cfg, traffic)
    compiles_before = ctx["compiles"].n
    tokens_per_step = int(traffic["batch"]) * int(traffic["sequence_length"])

    # -- the window ----------------------------------------------------------
    tracer = ctx.get("tracer")
    trace_from, trace_steps = 2, int(traffic.get("trace_steps", 3))
    pending, steps = None, 0
    ctx["mark_window_start"]()
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        if tracer is not None and steps in (trace_from, trace_from + trace_steps):
            if pending is not None:
                jax.block_until_ready(pending)
                pending = None
            tracer.stop() if tracer.running else tracer.start()
        with common.annotate("bench.handle_step"):
            loss = handle.step(*batch).value
        with common.annotate("bench.make_batch"):
            batch = next(feed)
        if pending is not None:
            with common.annotate("bench.block_until_ready"):
                jax.block_until_ready(pending)
        pending = loss
        steps += 1
    with common.annotate("bench.block_until_ready"):
        jax.block_until_ready(pending)
    window_s = time.perf_counter() - t0
    if tracer is not None and tracer.running:
        tracer.stop()
    compiled_in_window = ctx["compiles"].n - compiles_before
    peak = common.peak_bytes(jax.devices()[:1])

    # -- free the program's state, then the reference ------------------------
    del handle, model, loss, pending
    gc.collect()
    t_ref = time.perf_counter()
    reference = reference_readings(ctx["reference"], ctx["generator"], seed,
                                   cfg, traffic)
    numbers, notes = compare.train_numbers(program, reference)
    numbers["compiled_in_window"] = float(compiled_in_window)
    return {
        "attempted": steps, "failed": 0,
        "window_s": window_s, "steps": steps,
        "tokens_per_step": tokens_per_step, "sequence_length":
            int(traffic["sequence_length"]), "batch": int(traffic["batch"]),
        "n_params": n_params, "memory_peak_bytes": peak,
        "numbers": numbers,
        "notes": {**notes, "reference_s": round(time.perf_counter() - t_ref, 3),
                  "program_losses": program["losses"],
                  "reference_losses": reference["losses"]},
    }


def readings(ctx, model, seed, with_controls):
    """For the limits (``benchmarks/readings.py``): the numbers one seed gives
    for the program and, when asked, for the float8 control and for the planted
    fault "half of the batch left out", each in the program's place against
    the same reference. ``model`` is constructed once and reloaded per seed."""
    import paddle_tpu as paddle
    from paddle_tpu import mesh as pmesh

    cfg, traffic, gen = ctx["config"], ctx["traffic"], ctx["generator"]
    common.load_weights(
        model, ctx["builder"].weights(seed, cfg, cfg["torch_dtype"]))
    model.train()
    feed = gen.batches(seed, traffic, cfg)
    first = next(feed)
    handle = build(paddle, pmesh, model, traffic, first)
    program, _ = first_steps(handle, first, feed, seed, cfg, traffic)
    del handle
    gc.collect()
    ref_mod = ctx["reference"]
    reference = reference_readings(ref_mod, gen, seed, cfg, traffic)
    out = {"program": compare.train_numbers(program, reference)[0],
           "losses": {"program": program["losses"],
                      "reference": reference["losses"]}}
    if with_controls:
        gc.collect()
        control = reference_readings(ref_mod, gen, seed, cfg, traffic,
                                     quant="fp8")
        out["control_fp8"] = compare.train_numbers(control, reference)[0]
        del control
        gc.collect()
        half = reference_readings(ref_mod, gen, seed, cfg, traffic,
                                  rows=int(traffic["batch"]) // 2)
        out["fault_half_batch"] = compare.train_numbers(half, reference)[0]
    return out
