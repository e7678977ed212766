"""A kernel's share of its roofline, from the device trace.

``params``: ``{"costs": {"module", "function"}, "calls": {call: {"name_regex",
"stats_regex"}}}``. For each call kind the events that its rule picks are
summed; the least time the chip could take for them is events x
max(flops / peak FLOP/s, bytes / peak bytes/s) with flops and bytes from the
benchmark's own function for the cell's shapes. The share is least time over
measured time, over all call kinds together. No event found: nothing returned.
"""
import xtrace


def read(raw, params, env):
    dev = env.get("device_ops")
    if dev is None or env["peaks"] is None or "batch" not in raw:
        return None
    flops_mod = env["module"]("flops", params["costs"]["module"])
    costs = getattr(flops_mod, params["costs"]["function"])(
        env["config"], raw["batch"], raw["sequence_length"])
    peak_f = env["peaks"]["bf16_flops_per_s"]
    peak_b = env["peaks"]["hbm_bytes_per_s"]
    least = measured = 0.0
    note = {}
    for call, rule in params["calls"].items():
        events = xtrace.matching(dev, rule)
        if not events:
            continue
        ops, nbytes = costs[call]
        t_ops, t_bytes = ops / peak_f, nbytes / peak_b
        seconds = sum(du for _, _, du, _ in events) * 1e-9
        least += len(events) * max(t_ops, t_bytes)
        measured += seconds
        note[call] = {"events": len(events), "seconds": seconds,
                      "bound": "compute" if t_ops >= t_bytes else "memory",
                      "share_pct": 100.0 * len(events) * max(t_ops, t_bytes)
                      / seconds}
    if not measured:
        return None
    return 100.0 * least / measured, note
