"""A quantile of one of the program's monitor histograms, interpolated inside
the bucket it falls in (the grid's resolution is the reading's), times
``scale``.

``params``: ``{"histogram", "q", "scale"}`` and, optionally, ``"also_q":
[...]`` for further quantiles in the note, which also gives the count and the
share of observations in the grid's first bucket. The numbers are the
program's own export (``paddle_tpu.monitor.snapshot()``), read in the run's
process after the engine is freed, so they run from where the driver switched
the monitor on (the window's opening, traced runs only) to the last step the
process made. No such histogram, or no observation: nothing returned.
"""


def quantile(buckets, q):
    """``buckets``: ``[[upper bound, cumulative count]]`` ending in ``+Inf``.
    A quantile that falls in the first bucket reads that bucket's bound; one
    past the last finite bound reads that bound."""
    total = buckets[-1][1]
    rank = q * total
    below, lower = 0, None
    for le, cum in buckets:
        if cum >= rank and cum > below:
            if lower is None or le == "+Inf":
                return float(le if le != "+Inf" else lower)
            return lower + (le - lower) * (rank - below) / (cum - below)
        below = cum
        if le != "+Inf":
            lower = float(le)
    return lower


def read(raw, params, env):
    snap = env.get("monitor_snapshot")
    if snap is None:
        from paddle_tpu import monitor

        snap = monitor.snapshot()
    metric = snap["metrics"].get(params["histogram"])
    hist = metric and metric["values"].get("")
    if not hist or not hist["count"]:
        return None
    scale = params.get("scale", 1.0)
    buckets = hist["buckets"]
    note = {"count": hist["count"],
            "first_bucket": {"le": buckets[0][0], "share_pct":
                             100.0 * buckets[0][1] / hist["count"]},
            "quantiles": {str(q): quantile(buckets, q) * scale
                          for q in params.get("also_q", [])}}
    return quantile(buckets, params["q"]) * scale, note
