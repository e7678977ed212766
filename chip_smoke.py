#!/usr/bin/env python3
"""Proof that the two main paths start and answer correctly on the chip.

    python chip_smoke.py              # one TPU chip: device, flash, train, serve
    python chip_smoke.py --chips 4    # one four-chip host: the sharded train
                                      # step against the single-device one
    python chip_smoke.py --rehearse [--chips 4]   # tiny widths on the CPU

One process, no child that needs the chip. Every phase raises on failure and
prints one JSON line; the last line of stdout is
``{"ok": true, "device": {"platform", "kind", "count"}}``. Without
``--rehearse`` the script exits non-zero at once unless jax's platform is
``tpu``. The model is ``LlamaConfig``'s own default widths (vocab 32000,
hidden 4096, intermediate 11008, 32 heads x 128) with depth cut to fit one
v5e chip's 16 GiB and seeded random weights. Wall and compile seconds on the
phase lines are set-up facts of this run, not performance numbers.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ARGS = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
ARGS.add_argument("--chips", type=int, choices=(1, 4), default=1)
ARGS.add_argument("--rehearse", action="store_true",
                  help="tiny widths on the CPU (Pallas interpret, virtual "
                       "devices); never prints a tpu ok line")
ARGS.add_argument("--seed", type=int, default=0)

# LlamaConfig's defaults, spelled out so the line the smoke prints is the
# evidence; depth is the only cut. TRAIN_LAYERS / SERVE_LAYERS come from
# compiled.memory_analysis() for a described v5e (PERF.md, PR 24).
FULL = dict(vocab=32000, hidden=4096, inter=11008, heads=32, head_dim=128,
            train_layers=3, serve_layers=8, mesh_layers=2,
            batch=4, seq=2048,
            flash=(1, 2048, 32, 128),
            max_batch=8, block_size=64, chunk_size=128, max_len=1024,
            prompt_lens=(300, 200, 520, 710, 900, 430), shared_prefix=256,
            new_tokens=32)
TINY = dict(vocab=256, hidden=64, inter=128, heads=4, head_dim=16,
            train_layers=2, serve_layers=2, mesh_layers=2,
            batch=4, seq=128,
            flash=(1, 256, 2, 64),
            max_batch=8, block_size=16, chunk_size=32, max_len=128,
            prompt_lens=(40, 20, 52, 71, 90, 43), shared_prefix=32,
            new_tokens=8)
BF16_TOL = 2e-2            # rtol = atol, bf16 against an f32 reference
LOSS_TOL = 5e-3            # x max(1, |loss|)
HBM_BYTES = 16 * 2 ** 30   # one v5e chip


class Compiles:
    """Backend compiles and their seconds, from jax's own monitoring events
    (a persistent-cache hit fires the retrieval event instead)."""

    def __init__(self):
        from jax import monitoring

        self.n = 0
        self.seconds = 0.0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += seconds

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class Phases:
    def __init__(self, compiles, rehearsal):
        self.compiles = compiles
        self.rehearsal = rehearsal

    def emit(self, line):
        if self.rehearsal:
            line["rehearsal"] = True
        print(json.dumps(line), flush=True)

    def run(self, name, fn, *args):
        c = self.compiles
        n0, s0, h0, t0 = c.n, c.seconds, c.cache_hits, time.perf_counter()
        facts = fn(*args)
        self.emit({"phase": name, **facts,
                   "setup": {"wall_s": round(time.perf_counter() - t0, 2),
                             "compile_s": round(c.seconds - s0, 2),
                             "compiles": c.n - n0,
                             "cache_hits": c.cache_hits - h0}})
        return facts


def _peak_bytes(devices):
    stats = [d.memory_stats() for d in devices]
    if any(s is None for s in stats):      # the CPU backend reports none
        return None
    return max(s["peak_bytes_in_use"] for s in stats)


def _widths(sz):
    return {k: sz[k] for k in ("vocab", "hidden", "inter", "heads", "head_dim")}


def _close(got, ref, tol):
    import numpy as np

    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    err = np.abs(got - ref)
    return float(err.max()), bool((err <= tol + tol * np.abs(ref)).all())


# --------------------------------------------------------------------------- #
# phases
# --------------------------------------------------------------------------- #
def phase_device(cache_dir):
    from importlib import metadata

    import jax
    import jaxlib

    d = jax.devices()[0]
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices()), "jax": jax.__version__,
            "jaxlib": jaxlib.__version__, "libtpu": libtpu,
            "compile_cache_dir": cache_dir}


def phase_flash(sz, on_tpu):
    """The Pallas kernel, forward and jax.grad, against _math_sdpa in f32."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from paddle_tpu.nn.functional.flash_attention import _math_sdpa
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_fwd

    B, S, H, D = sz["flash"]
    r = np.random.RandomState(0)
    q, k, v, w = (jnp.asarray(r.standard_normal((B, S, H, D)), jnp.bfloat16)
                  for _ in range(4))

    # w is an argument, not a closure: a closed-over array is embedded in
    # the program as a literal (16 MB here, and again in the cache entry)
    def flash_loss(q, k, v, w):
        out = flash_attention_fwd(q, k, v, causal=True)
        return (out.astype(jnp.float32) * w).sum(), out

    def math_loss(q, k, v, w):
        with jax.default_matmul_precision("highest"):
            out = _math_sdpa(q.astype(jnp.float32), k.astype(jnp.float32),
                             v.astype(jnp.float32), causal=True)
        return (out * w).sum(), out

    flash = jax.jit(jax.value_and_grad(flash_loss, (0, 1, 2), has_aux=True))
    math = jax.jit(jax.value_and_grad(math_loss, (0, 1, 2), has_aux=True))
    hlo = flash.lower(q, k, v, w).compile().as_text()
    kernel_in_hlo = "tpu_custom_call" in hlo
    if on_tpu and not kernel_in_hlo:
        raise AssertionError("flash: no tpu_custom_call in the compiled HLO")
    (_, out_f), g_f = flash(q, k, v, w)
    (_, out_m), g_m = math(q, k, v, w)
    errs = {}
    for name, a, b in [("out", out_f, out_m)] + [
            (n, a, b) for n, a, b in zip(("dq", "dk", "dv"), g_f, g_m)]:
        scale = max(1.0, float(jnp.max(jnp.abs(b))))
        errs[name], ok = _close(a.astype(jnp.float32) / scale, b / scale,
                                BF16_TOL)
        if not ok:
            raise AssertionError(
                f"flash: {name} off the f32 math path by {errs[name]} "
                f"(scaled by {scale}), tolerance {BF16_TOL}")
    return {"shape_BSHD": [B, S, H, D], "dtype": "bfloat16", "causal": True,
            "max_abs_err": errs, "tol": BF16_TOL,
            "tpu_custom_call": kernel_in_hlo}


def _llama(sz, layers, seed, tp=1):
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig(
        vocab_size=sz["vocab"], hidden_size=sz["hidden"],
        intermediate_size=sz["inter"], num_hidden_layers=layers,
        num_attention_heads=sz["heads"], num_key_value_heads=sz["heads"],
        max_position_embeddings=max(sz["seq"], sz["max_len"]),
        dtype="bfloat16", recompute=True, tensor_parallel_degree=tp)
    assert cfg.head_dim == sz["head_dim"]
    paddle.seed(seed)
    model = LlamaForCausalLM(cfg)
    model.to(dtype="bfloat16")
    return model


def _train_batch(sz, seed):
    import numpy as np

    import jax.numpy as jnp

    r = np.random.RandomState(seed)
    shape = (sz["batch"], sz["seq"])
    return (jnp.asarray(r.randint(0, sz["vocab"], shape), jnp.int32),
            jnp.asarray(r.randint(0, sz["vocab"], shape), jnp.int32))


def _loss_fn(m, ids, labels):
    loss, _ = m(ids, labels=labels)
    return loss


def _parallelize(sz, layers, seed, batch, config, tp=1, mesh=None, lr=3e-4):
    import paddle_tpu as paddle
    from paddle_tpu import mesh as pmesh

    model = _llama(sz, layers, seed, tp=tp)
    opt = paddle.optimizer.AdamW(learning_rate=lr,
                                 parameters=model.parameters(),
                                 multi_precision=True)
    n_params = sum(int(p.value.size) for p in model.parameters())
    handle = pmesh.parallelize(model, opt, _loss_fn, batch, config=config,
                               mesh=mesh)
    return handle, n_params


def _state_arrays(handle):
    import jax

    return [a for a in jax.tree_util.tree_leaves(
        (handle._av, handle._mv)) if hasattr(a, "addressable_shards")]


def _bytes_per_device(arrays):
    per = {}
    for a in arrays:
        for s in a.addressable_shards:
            per[s.device.id] = per.get(s.device.id, 0) + s.data.nbytes
    return per


def _run_steps(handle, batch, steps):
    """``steps`` donated steps on one fixed batch; the step's HLO text and
    memory analysis come from one AOT compile of the same program."""
    import jax

    lowered = handle._jitted.lower(*handle._step_args(batch))
    compiled = lowered.compile()
    hlo = compiled.as_text()
    mem = compiled.memory_analysis()
    losses = []
    for _ in range(steps):
        loss = handle.step(*batch)
        losses.append(float(jax.block_until_ready(loss.value)))
    if handle._jitted._cache_size() != 1:
        raise AssertionError(
            f"train step compiled {handle._jitted._cache_size()} times")
    # collectives the step asks for (StableHLO) and what the compiler made of
    # them (HLO; GSPMD's own exist only there, and the TPU compiler may turn
    # a reduce-scatter into an all-reduce)
    asked = lowered.as_text()
    facts = {"losses": losses,
             "collectives_asked": {
                 op: n for op in ("all_reduce", "reduce_scatter",
                                  "all_gather", "all_to_all")
                 if (n := asked.count(f"stablehlo.{op}"))},
             "collectives_compiled": {
                 op: n for op in ("all-reduce", "reduce-scatter",
                                  "all-gather", "all-to-all",
                                  "collective-permute")
                 if (n := hlo.count(f" {op}(") + hlo.count(f" {op}-start("))},
             "program_bytes": {
                 "arguments": int(mem.argument_size_in_bytes),
                 "temporaries": int(mem.temp_size_in_bytes),
                 "outputs": int(mem.output_size_in_bytes),
                 "aliased": int(mem.alias_size_in_bytes)}}
    return facts, hlo


def phase_train(sz, seed, on_tpu):
    import math

    import jax

    batch = _train_batch(sz, seed)
    handle, n_params = _parallelize(sz, sz["train_layers"], seed, batch,
                                    {"dp_degree": 1})
    facts, hlo = _run_steps(handle, batch, 5)
    losses = facts["losses"]
    if not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"train: non-finite loss in {losses}")
    if not losses[4] < losses[0]:
        raise AssertionError(f"train: loss did not fall: {losses}")
    kernel_in_hlo = "tpu_custom_call" in hlo
    if on_tpu and not kernel_in_hlo:
        raise AssertionError(
            "train: no tpu_custom_call in the step's HLO (the math path, "
            "not the kernel, trained)")
    peak = _peak_bytes(jax.devices()[:1])
    return {"entry": "mesh.parallelize(dp_degree=1)",
            "widths": _widths(sz),
            "layers": sz["train_layers"], "params": n_params,
            "batch_x_seq": [sz["batch"], sz["seq"]],
            "dtype": "bfloat16, fp32 masters, recompute",
            **facts, "step_compiled_once": True,
            "tpu_custom_call": kernel_in_hlo,
            "peak_bytes_in_use": peak,
            "hbm_free_share": (None if peak is None
                               else round(1 - peak / HBM_BYTES, 3))}


def _prompts(sz, seed):
    import numpy as np

    r = np.random.RandomState(seed + 1)
    prompts = [r.randint(0, sz["vocab"], n).astype(np.int32)
               for n in sz["prompt_lens"]]
    # the last request shares its first blocks with the first one
    prompts[-1][:sz["shared_prefix"]] = prompts[0][:sz["shared_prefix"]]
    return prompts


def phase_serve(sz, seed, compiles):
    import numpy as np

    import jax

    from paddle_tpu.models.llama_decode import LlamaDecodeEngine
    from paddle_tpu.models.serving import ContinuousBatchingEngine

    model = _llama(sz, sz["serve_layers"], seed)
    model.eval()
    eng = ContinuousBatchingEngine(
        model, max_batch=sz["max_batch"], max_len=sz["max_len"],
        block_size=sz["block_size"], chunk_size=sz["chunk_size"])
    prompts = _prompts(sz, seed)
    new = sz["new_tokens"]
    done = {}
    compiles_at_first_finish = None
    steps = 0

    def drive(until):
        nonlocal compiles_at_first_finish, steps
        while not until():
            for rid, toks in eng.step(max_new_tokens=new):
                done[rid] = np.asarray(toks)
            steps += 1
            if done and compiles_at_first_finish is None:
                compiles_at_first_finish = compiles.n
            if steps > 5000:
                raise AssertionError("serve: engine did not drain")

    rids = [eng.submit(p, max_new_tokens=new) for p in prompts[:-1]]
    # the sharing request arrives once the first one's blocks are in the
    # radix cache, as a second turn of a session does
    drive(lambda: rids[0] in done)
    rids.append(eng.submit(prompts[-1], max_new_tokens=new))
    drive(lambda: eng.num_active == 0 and eng.num_pending == 0)

    if sorted(done) != sorted(rids):
        raise AssertionError(f"serve: finished {sorted(done)} of {rids}")
    programs = sorted(eng._jit_cache)     # each holds one executable
    if len(programs) > 2:
        raise AssertionError(f"serve: engine compiled {programs}")
    compiled_late = compiles.n - compiles_at_first_finish
    if compiled_late:
        raise AssertionError(
            f"serve: {compiled_late} program(s) compiled after the first "
            "request finished")
    if eng.prefix_cache.hits < 1:
        raise AssertionError("serve: the shared prefix never hit the cache")
    peak = _peak_bytes(jax.devices()[:1])

    # greedy reference: the same weights through the dense-cache engine
    ref = LlamaDecodeEngine(model, max_len=sz["max_len"])
    decided = {}
    for i in (1, len(prompts) - 1):
        got = done[rids[i]][-new:]
        want = np.asarray(ref.generate(prompts[i][None],
                                       max_new_tokens=new))[0]
        diff = np.nonzero(got != want)[0]
        if diff.size == 0:
            decided[f"request_{i}"] = "tokens"
            continue
        # seeded random weights give flat logits: a bf16 near-tie may flip
        # an argmax. At the first divergent position the engine's token must
        # score within the bf16 tolerance of the reference's best
        j = int(diff[0])
        ctx = np.concatenate([prompts[i], got[:j]])
        logits = np.asarray(ref.prefill(ctx[None])[0], np.float32)[0]
        gap = float(logits.max() - logits[got[j]])
        tol = BF16_TOL * max(1.0, float(np.abs(logits).max()))
        if gap > tol:
            raise AssertionError(
                f"serve: request {i} leaves the greedy reference at token "
                f"{j}: engine {int(got[j])} scores {gap} under the "
                f"reference's best, tolerance {tol}")
        decided[f"request_{i}"] = (f"logits at token {j}: gap "
                                   f"{round(gap, 5)} <= {round(tol, 5)}")
    return {"entry": "ContinuousBatchingEngine.submit/step",
            "widths": _widths(sz),
            "layers": sz["serve_layers"], "dtype": "bfloat16",
            "max_batch": sz["max_batch"], "block_size": sz["block_size"],
            "chunk_size": sz["chunk_size"], "max_len": sz["max_len"],
            "kv_pool_bytes": int(eng.kv_pool_bytes),
            "prompt_lens": list(sz["prompt_lens"]), "new_tokens": new,
            "finished": len(done), "steps": steps,
            "prefix_cache_hits": int(eng.prefix_cache.hits),
            "programs": programs, "compiled_after_first_finish": 0,
            "reference": "LlamaDecodeEngine.generate (dense cache)",
            "decided_by": decided, "peak_bytes_in_use": peak}


def phase_mesh(sz, seed, emit):
    """Four chips: dp4 + ZeRO-1 and dp2 x tp2 against the one-device step.
    Each layout's facts are printed as it completes (a four-chip call is too
    dear to lose two layouts' results to the third one's failure)."""
    import math

    import jax

    from paddle_tpu import mesh as pmesh
    from paddle_tpu.distributed import fleet

    batch = _train_batch(sz, seed)
    layers, steps = sz["mesh_layers"], 3

    def run(name, config, tp=1, mesh=None):
        # a gentle learning rate: at the train phase's 3e-4 the first AdamW
        # step takes the loss from 11 to 0.3, and what is compared after it
        # is how each layout's rounding was amplified, not whether it is right
        handle, n_params = _parallelize(sz, layers, seed, batch, config,
                                        tp=tp, mesh=mesh, lr=1e-5)
        facts, hlo = _run_steps(handle, batch, steps)
        per_dev = _bytes_per_device(_state_arrays(handle))
        facts.update(
            params=n_params,
            state_bytes_per_device=max(per_dev.values()),
            state_devices=sorted(per_dev),
            param_devices=sorted({s.device.id for a in handle._pv
                                  for s in a.addressable_shards}),
            # everything the process holds on each device now: the live
            # objects' unsharded originals show up on device 0
            bytes_in_use_per_device=[
                (d.memory_stats() or {}).get("bytes_in_use")
                for d in jax.devices()],
            tpu_custom_call="tpu_custom_call" in hlo)
        del handle
        gc.collect()
        emit({"phase": f"mesh.{name}", **facts})
        return facts

    single = run("single", {"dp_degree": 1})
    zero1 = run("dp4_zero1", {"dp_degree": 4, "shard_optimizer": True})
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 2}
    fleet.init(is_collective=True, strategy=strategy)
    hybrid = run("dp2_tp2", {}, tp=2, mesh=pmesh.MeshContext.from_fleet())

    # the bf16 model returns its loss in bf16, whose spacing at ~10 (0.0625)
    # is wider than mesh_bench's 5e-3 x |loss|: one such step is allowed on top
    def tol(ref):
        ref = abs(ref)
        return (LOSS_TOL * max(1.0, ref)
                + 2.0 ** (math.floor(math.log2(max(ref, 1e-30))) - 7))

    for name, r in (("dp4_zero1", zero1), ("dp2_tp2", hybrid)):
        gaps = [abs(a - b) for a, b in zip(r["losses"], single["losses"])]
        r["max_loss_gap"] = max(gaps)
        if any(g > tol(b) for g, b in zip(gaps, single["losses"])):
            raise AssertionError(
                f"mesh: {name} losses {r['losses']} leave the single-device "
                f"{single['losses']} by {gaps}, tolerance "
                f"{[tol(b) for b in single['losses']]}")
        if len(r["state_devices"]) != 4 or len(r["param_devices"]) != 4:
            raise AssertionError(f"mesh: {name} state is not on 4 devices: "
                                 f"{r['state_devices']} {r['param_devices']}")
    share = zero1["state_bytes_per_device"] / single["state_bytes_per_device"]
    if not 0.24 <= share <= 0.27:
        raise AssertionError(f"mesh: ZeRO-1 state share per device {share}")
    if not (zero1["collectives_asked"].get("reduce_scatter")
            and zero1["collectives_asked"].get("all_gather")
            and zero1["collectives_compiled"].get("all-gather")):
        raise AssertionError(
            f"mesh: dp4_zero1 asks for {zero1['collectives_asked']}, "
            f"compiled {zero1['collectives_compiled']}")
    if not hybrid["collectives_compiled"].get("all-reduce"):
        raise AssertionError(f"mesh: dp2_tp2 HLO holds "
                             f"{hybrid['collectives_compiled']}")
    return {"layers": layers, "batch_x_seq": [sz["batch"], sz["seq"]],
            "loss_tol": [tol(b) for b in single["losses"]],
            "max_loss_gap": {"dp4_zero1": zero1["max_loss_gap"],
                             "dp2_tp2": hybrid["max_loss_gap"]},
            "zero1_state_share": round(share, 4),
            "peak_bytes_in_use": _peak_bytes(jax.devices())}


def main(argv=None):
    args = ARGS.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["PADDLE_TPU_PALLAS_INTERPRET"] = "1"
        # all-reduce-promotion: XLA:CPU (jaxlib 0.9.0) aborts in that pass on
        # the bf16 all-reduce of the dp x tp step ("Invalid binary instruction
        # opcode copy"); the TPU compiler has no such pass
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.chips}"
            + " --xla_disable_hlo_passes=all-reduce-promotion")

    import jax

    import paddle_tpu as paddle

    devices = jax.devices()
    platform = devices[0].platform
    if not args.rehearse and platform != "tpu":
        print(f"chip_smoke: platform is {platform!r}, not 'tpu'; use "
              "--rehearse to run tiny widths on the CPU", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but jax sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    on_tpu = platform == "tpu"
    # a rehearsal's CPU programs are not worth keeping
    cache_dir = None if args.rehearse else paddle.device.enable_compile_cache()
    compiles = Compiles()
    phases = Phases(compiles, args.rehearse)
    sz = TINY if args.rehearse else FULL

    phases.run("device", phase_device, cache_dir)
    if args.chips == 4:
        phases.run("mesh", phase_mesh, sz, args.seed, phases.emit)
    else:
        # serve before train: peak_bytes_in_use is the process's peak so
        # far, and the train step's is the larger of the two
        phases.run("flash", phase_flash, sz, on_tpu)
        phases.run("serve", phase_serve, sz, args.seed, compiles)
        gc.collect()
        phases.run("train", phase_train, sz, args.seed, on_tpu)
    phases.emit({"ok": True,
                 "device": {"platform": platform,
                            "kind": devices[0].device_kind,
                            "count": len(devices)}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
