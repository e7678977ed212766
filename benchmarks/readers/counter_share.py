"""A share of one of the program's labelled monitor counters, in percent: the
series whose label ``label`` takes a value in ``numerator`` over those where it
takes one in ``denominator``, summed over the counter's other labels.

``params``: ``{"counter", "label", "numerator": [...], "denominator": [...]}``
and, optionally, ``"note_counters": [...]``: counters whose series the note
prints beside the two sums. The numbers are the program's own export
(``paddle_tpu.monitor.snapshot()``), read in the run's process after the
engine is freed, so they run from where the driver switched the monitor on
(the window's opening, traced runs only) to the last step the process made.
No such counter, or a denominator of zero: nothing returned.
"""


def _snapshot(env):
    if "monitor_snapshot" in env:
        return env["monitor_snapshot"]
    from paddle_tpu import monitor

    return monitor.snapshot()


def series(snapshot, counter):
    """``[({label: value}, count)]`` of one counter of a snapshot."""
    metric = snapshot["metrics"].get(counter)
    if metric is None:
        return []
    return [(dict(kv.split("=", 1) for kv in key.split(",") if kv), value)
            for key, value in metric["values"].items()]


def read(raw, params, env):
    snap = _snapshot(env)
    rows = series(snap, params["counter"])
    label = params["label"]
    top = sum(v for labels, v in rows if labels.get(label) in params["numerator"])
    bottom = sum(v for labels, v in rows
                 if labels.get(label) in params["denominator"])
    if not bottom:
        return None
    note = {"numerator": top, "denominator": bottom}
    note.update((name, dict(snap["metrics"][name]["values"]))
                for name in params.get("note_counters", [])
                if name in snap["metrics"])
    return 100.0 * top / bottom, note
