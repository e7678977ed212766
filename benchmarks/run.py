#!/usr/bin/env python3
"""One run of one cell of ``BENCHMARK.json``.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process. The cell's configuration, traffic, driver, builder, generator,
reference, limits and metrics are files found BY NAME (``BENCHMARK.json`` names
the cell's configuration and traffic; the configuration's file names its
``driver``, its ``builder`` (``benchmarks/builders/<name>.py``: the program's
model at the configuration's sizes and its weights from the seed; since PR 31,
when ``common.construct_model`` moved to ``builders/llama.py``) and its
``reference``; the traffic's file names its ``generator``; each metric's file
names its ``reader``), so a new cell, of another model class too, is new files
and new entries, and this file holds no name of any of them. Every one of
those keys is required: a file that lacks one is an error, there is no default.
Without ``--rehearse`` it exits non-zero, and prints no result, unless jax's
platform is ``tpu`` and holds the chips the cell asks for. ``--rehearse`` runs
the same code at the tiny sizes the files give, on the CPU with Pallas in
interpret mode, and its result never names a ``tpu``.

The last line of standard output is the result: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` when traced), then
``compared``: every number compared beside its limit, which are also the last
lines on standard error.
"""
from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _module(kind, name):
    """``benchmarks/<kind>/<name>.py`` as a module."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _merge(base, over):
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) and \
            isinstance(out.get(k), dict) else v
    return out


def _by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"run.py: BENCHMARK.json has no {what} {name!r}")


def load_cell(workload, rehearse):
    bench = _json(ROOT, "BENCHMARK.json")
    cell = _by_name(bench["workloads"], workload, "workload")
    entry = _by_name(bench["configs"], cell["config"], "config")
    cfg = _json(ROOT, entry["file"])
    traffic = _json(HERE, "traffic", cell["traffic"] + ".json")
    limits = _json(HERE, "limits", workload + ".json")
    if rehearse:
        cfg = _merge(cfg, cfg.get("rehearse", {}))
        traffic = _merge(traffic, traffic.get("rehearse", {}))
        limits = _merge(limits, limits.get("rehearse", {}))
    return bench, cell, cfg, traffic, limits


def cell_modules(cfg, traffic):
    """The modules that the cell's files name, as a run's context holds them.
    Every key is required: there is no default builder, reference or
    generator."""
    out = {}
    for key, kind, spec, what in (("builder", "builders", cfg, "configuration"),
                                  ("reference", "reference", cfg, "configuration"),
                                  ("generator", "generators", traffic, "traffic")):
        if key not in spec:
            raise SystemExit(f"run.py: the cell's {what} file names no {key!r}")
        out[key] = _module(kind, spec[key])
    return out


def context(cfg, traffic, seed, seconds, rehearse, tracer=None, fault=None):
    """What a driver's ``run`` takes. ``marks`` gets the window's start."""
    import common

    compiles, marks = common.Compiles(), {}
    return {
        "config": cfg, "traffic": traffic, "seed": seed, "seconds": seconds,
        "phases": common.Phases(compiles), "compiles": compiles,
        "tracer": tracer, "fault": fault, "rehearsal": rehearse,
        **cell_modules(cfg, traffic), "marks": marks,
        "mark_window_start":
            lambda: marks.setdefault("window_start", time.perf_counter()),
    }


def metric_entries(bench, workload, traced):
    """The cell's end-to-end metrics (``--trace 0``) or per-layer metrics
    (``--trace 1``): those that list the cell, or list no cell at all."""
    group = bench["per_layer"] if traced else bench["end_to_end"]
    return [m for m in group
            if "workloads" not in m or workload in m["workloads"]]


def set_environment(rehearse):
    """What has to be in place before jax is imported."""
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["PADDLE_TPU_PALLAS_INTERPRET"] = "1"
    else:
        # the compile cache lives at a fixed path inside the checkout, uncapped:
        # only the checkout outlasts a run, and the path is part of the key
        os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
            ROOT, ".jax_cache", "benchmarks")
        os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
        os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
        os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    for p in (ROOT, HERE):
        if p not in sys.path:
            sys.path.insert(0, p)


def main(argv=None, fault=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU; never prints a tpu device")
    ap.add_argument("--describe-trace", metavar="FILE",
                    help="also write a hand's look at the trace's planes, "
                         "lines and event names to FILE")
    args = ap.parse_args(argv)
    t_start = T_PROCESS_START if argv is None else time.perf_counter()
    bench, cell, cfg, traffic, limits = load_cell(args.workload, args.rehearse)

    set_environment(args.rehearse)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if not args.rehearse and platform != "tpu":
        print(f"run.py: jax's platform is {platform!r}, not 'tpu' "
              "(--rehearse runs tiny sizes on the CPU)", file=sys.stderr)
        return 1
    if len(devices) < cell["chips"]:
        print(f"run.py: the cell asks for {cell['chips']} chip(s), jax sees "
              f"{len(devices)}", file=sys.stderr)
        return 1
    devices = devices[:cell["chips"]]
    kind = devices[0].device_kind
    peaks = _json(HERE, "peaks.json")
    if not args.rehearse and kind not in peaks:
        raise KeyError(f"no published peak for device kind {kind!r} in "
                       "benchmarks/peaks.json")

    import common
    import compare
    import xtrace as trace_mod

    trace_dir = os.path.join(ROOT, ".bench_out", "trace", args.workload)
    tracer = None
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir, exist_ok=True)
        tracer = common.SliceTracer(trace_dir)
    ctx = context(cfg, traffic, args.seed, args.seconds, args.rehearse,
                  tracer, fault)
    phases, compiles, marks = ctx["phases"], ctx["compiles"], ctx["marks"]
    raw = _module("drivers", cfg["driver"]).run(ctx)
    raw["setup_s"] = marks["window_start"] - t_start
    print(json.dumps({"setup_phases": phases.seconds,
                      "setup_s": round(raw["setup_s"], 3),
                      "compiles": compiles.n, "compile_s": round(compiles.seconds, 3),
                      "cache_hits": compiles.cache_hits,
                      "compiles_over_1s": compiles.long,
                      "window_s": raw["window_s"], "notes": raw["notes"]}),
          flush=True)

    device = {"platform": platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": raw["memory_peak_bytes"]}
    env = {"config": cfg, "peaks": peaks.get(kind), "chips": len(devices),
           "module": _module, "device_ops": None, "busy_s": None}
    result = {"correct": False, "attempted": raw["attempted"],
              "failed": raw["failed"], "metrics": {}, "device": device}
    if tracer is not None:
        if tracer.t_stop is None:
            raise trace_mod.TraceError("the window closed before its traced "
                                       "slice began: --seconds is too short")
        xplane = trace_mod.find_xplane(trace_dir)
        if args.describe_trace:
            os.makedirs(os.path.dirname(os.path.abspath(args.describe_trace)),
                        exist_ok=True)
            with open(args.describe_trace, "w") as f:
                f.write(trace_mod.describe(xplane))
        planes = trace_mod.load(xplane)
        dev = trace_mod.device_ops(planes, rehearsal=args.rehearse)
        env["device_ops"] = dev
        # busy and window are both the device's clock (first operation's start
        # to last operation's end), so busy <= window whatever the profiler's
        # edges did; the host's clock between them is a note, for laying old
        # readings (1 - busy / host slice) beside new ones, and divides nothing
        device["busy_s"] = trace_mod.busy_seconds(dev)
        device["window_s"] = trace_mod.window_seconds(dev)
        env["busy_s"], env["traced_window_s"] = device["busy_s"], device["window_s"]
        print(json.dumps({"traced_slice": {
            "busy_s": device["busy_s"], "device_window_s": device["window_s"],
            "host_slice_s": tracer.t_stop - tracer.t_start,
            "programs": trace_mod.programs(planes)}}), flush=True)
        result["breakdown"] = {
            "device_ops": trace_mod.top_ops(dev),
            "idle_gaps": trace_mod.idle_gaps(dev, planes["host"])}
        shutil.rmtree(trace_dir, ignore_errors=True)

    said = []
    for m in metric_entries(bench, args.workload, bool(args.trace)):
        spec = _json(HERE, "metrics", m["name"] + ".json")
        got = _module("readers", spec["reader"]).read(raw, spec.get("params", {}), env)
        if got is None:                       # nothing to read: left out
            continue
        value, note = got if isinstance(got, tuple) else (got, None)
        result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
        if note:
            said.append({m["name"]: note})
    if said:
        print(json.dumps({"metric_notes": said}), flush=True)

    compared, ok = compare.judge(raw["numbers"], limits)
    result["correct"] = bool(ok)
    result["compared"] = {k: {"value": v["value"], "limit": v["limit"]}
                          for k, v in compared.items()}
    for name, v in compared.items():
        print(f"compared {name} = {v['value']!r} limit {v['limit']!r} "
              f"{'ok' if v['ok'] else 'NOT OK'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
