"""A kernel's share of its roofline where its work is ragged and known only
from what the program counted: a served kernel, whose bytes follow the lanes'
lengths step by step.

``params``: ``{"costs": {"module", "function"}, "calls": {call: {"name_regex",
"stats_regex"}}}`` and, optionally, ``"note_counters": [...]``. The events that
the rules pick are summed over the traced slice. The benchmark's own function
(``benchmarks/flops/<module>.py``) is called with the configuration, its
``engine`` group and ``raw["slice_counters"]`` (the increase of the program's
counters between the instants at which the profiler started and stopped: the
very steps whose events the trace holds) and returns the least FLOPs and bytes
of ALL those events together. The share is max(flops / peak FLOP/s, bytes /
peak bytes/s) over the events' summed time. No event, no slice counters, or
nothing counted: nothing returned.
"""
import xtrace


def read(raw, params, env):
    dev, counters = env.get("device_ops"), raw.get("slice_counters")
    if dev is None or env["peaks"] is None or counters is None:
        return None
    events = [e for rule in params["calls"].values()
              for e in xtrace.matching(dev, rule)]
    seconds = sum(du for _, _, du, _ in events) * 1e-9
    if not seconds:
        return None
    cfg = env["config"]
    costs = getattr(env["module"]("flops", params["costs"]["module"]),
                    params["costs"]["function"])(cfg, cfg.get("engine", {}),
                                                 counters)
    if costs is None:
        return None
    ops, nbytes = costs
    t_ops = ops / env["peaks"]["bf16_flops_per_s"]
    t_bytes = nbytes / env["peaks"]["hbm_bytes_per_s"]
    note = {"events": len(events), "seconds": seconds, "flops": ops,
            "bytes": nbytes, "bytes_per_s": nbytes / seconds,
            "bound": "compute" if t_ops >= t_bytes else "memory",
            "slice_seconds": raw.get("slice_seconds")}
    note.update((name, counters[name])
                for name in params.get("note_counters", []) if name in counters)
    return 100.0 * max(t_ops, t_bytes) / seconds, note
