"""Reduction of a jax profiler trace (``*.xplane.pb``) to what the per-layer
metrics read: device operations with their intervals, the busy union and the
window it lies in (both on the DEVICE's clock), idle gaps named by the host
span that covers them, the programs that ran, and the top lists of
``breakdown``.

Device operations are the events of the line ``XLA Ops`` of every plane named
``/device:TPU:<n>``; the programs that ran are the events of its line ``XLA
Modules``. A rehearsal on the CPU has no such plane; there (and only
when asked) the host-side events that carry an ``hlo_op`` stat stand in, so
that the code path is exercised, never so that a number is reported. Host spans
are the ``bench.*`` ``TraceAnnotation`` events of the host plane. A trace that
cannot be read, or holds no device operation, is an error: nothing here returns
an empty result for a trace it could not make sense of, and nothing is capped.
"""
from __future__ import annotations

import collections
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."


class TraceError(RuntimeError):
    pass


def find_xplane(directory):
    paths = sorted(glob.glob(os.path.join(directory, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise TraceError(f"no .xplane.pb under {directory}")
    return paths[-1]


def load(path):
    """Planes of the file as plain data:
    ``{"device": {chip: [(name, start_ns, dur_ns, stats)]}, "modules": {chip:
    [name]}, "host": [...]}``."""
    import jax

    if os.path.getsize(path) == 0:
        raise TraceError(f"{path} is empty")
    try:
        data = jax.profiler.ProfileData.from_file(path)
        planes = list(data.planes)
    except Exception as e:  # noqa: BLE001 - any parse failure is the same fault
        raise TraceError(f"cannot read {path}: {e}") from e
    if not planes:
        raise TraceError(f"{path} holds no plane: truncated or not a trace")
    device, modules, host, host_ops = {}, {}, [], []
    for plane in planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                device.setdefault(int(m.group(1)), []).extend(
                    (e.name, float(e.start_ns), float(e.duration_ns),
                     dict(e.stats)) for e in line.events)
            elif m and line.name == MODULES_LINE:
                modules.setdefault(int(m.group(1)), []).extend(
                    e.name for e in line.events)
            elif plane.name.startswith("/host:"):
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append((e.name, float(e.start_ns),
                                     float(e.duration_ns)))
                    elif e.duration_ns > 0:
                        stats = dict(e.stats)
                        if "hlo_op" in stats:
                            host_ops.append((e.name, float(e.start_ns),
                                             float(e.duration_ns), stats))
    return {"device": device, "modules": modules, "host": host,
            "host_ops": host_ops, "planes": [p.name for p in planes]}


def device_ops(planes, rehearsal=False):
    """``{chip: [(name, start_ns, dur_ns, stats)]}``; raises when there is
    none."""
    dev = planes["device"]
    if not dev and rehearsal and planes["host_ops"]:
        dev = {0: planes["host_ops"]}
    if not dev or not any(dev.values()):
        raise TraceError("the trace holds no device operation (planes: "
                         f"{planes['planes']})")
    return dev


def union(intervals):
    """Merged, sorted ``[(start, end)]``."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def busy_seconds(dev):
    """Seconds in which an operation ran, averaged over the chips."""
    per_chip = [sum(e - s for s, e in union((st, st + du) for _, st, du, _ in ops))
                for ops in dev.values()]
    return sum(per_chip) / len(per_chip) * 1e-9


def window_seconds(dev):
    """Seconds from the start of a chip's first operation to the end of its
    last, averaged over the chips as ``busy_seconds`` averages: the traced
    slice on the device's own clock. The busy union lies inside it chip by
    chip, so ``busy_seconds(dev) <= window_seconds(dev)`` whatever the host's
    clock read when the profiler started and stopped."""
    per_chip = [max(st + du for _, st, du, _ in ops) - min(st for _, st, _, _ in ops)
                for ops in dev.values()]
    return sum(per_chip) / len(per_chip) * 1e-9


def programs(planes):
    """``{program: events}`` of the first chip's ``XLA Modules`` line, a
    program's name cut before its fingerprint (``jit_step(123)`` is
    ``jit_step``): how often each compiled program ran inside the trace."""
    modules = planes["modules"]
    return dict(collections.Counter(
        name.split("(", 1)[0] for name in (modules[min(modules)] if modules else [])))


def short_name(name, width=96):
    """An HLO instruction's text cut to ``%name opcode result-shape``, without
    its operands: ``%fusion.467 fusion (bf16[4096,32768]{...}, ...``."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:width]
    depth = 0
    for i, ch in enumerate(rest):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == " " and depth == 0:
            opcode = rest[i + 1:].split("(", 1)[0]
            return f"{head} {opcode} {rest[:i]}"[:width]
    return f"{head} {rest}"[:width]


def top_ops(dev, n=10):
    """``[[name, seconds]]`` of the operations that took most time, summed over
    their events and averaged over the chips."""
    total = {}
    for ops in dev.values():
        for name, _, du, _ in ops:
            total[name] = total.get(name, 0.0) + du
    rows = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[short_name(name), ns * 1e-9 / len(dev)] for name, ns in rows]


def idle_gaps(dev, host, n=10):
    """``[[host span, seconds]]``: the first chip's idle time between its first
    and last operation, given to the ``bench.*`` span that covers most of each
    gap (``(no host span)`` where none does), summed by span name."""
    ops = dev[min(dev)]
    merged = union((st, st + du) for _, st, du, _ in ops)
    total = {}
    for (_, a_end), (b_start, _) in zip(merged, merged[1:]):
        best, cover = "(no host span)", 0.0
        for name, st, du in host:
            c = min(b_start, st + du) - max(a_end, st)
            if c > cover:
                best, cover = name, c
        total[best] = total.get(best, 0.0) + (b_start - a_end)
    rows = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns * 1e-9] for name, ns in rows]


def matching(dev, rule):
    """Events of the first chip that a metric's rule picks: ``name_regex``
    against the event's name and, where given, ``stats_regex`` as
    ``{stat: regex}`` against its stats."""
    name_re = re.compile(rule["name_regex"])
    stat_res = {k: re.compile(v) for k, v in rule.get("stats_regex", {}).items()}
    out = []
    for name, st, du, stats in dev[min(dev)]:
        if not name_re.search(name):
            continue
        if all(k in stats and r.search(str(stats[k])) for k, r in stat_res.items()):
            out.append((name, st, du, stats))
    return out


def describe(path, limit=12):
    """A hand's look at a trace: planes, lines, and the first events of each."""
    import jax

    rows = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        rows.append(f"PLANE {plane.name}")
        for line in plane.lines:
            events = list(line.events)
            rows.append(f"  LINE {line.name!r}: {len(events)} events")
            seen, count = {}, collections.Counter(e.name for e in events)
            for e in events:
                seen.setdefault(e.name, e)
            for name, e in list(seen.items())[:limit]:
                rows.append(f"    {name[:90]!r} x{count[name]} start={e.start_ns} "
                            f"dur={e.duration_ns} stats={dict(e.stats)}"[:600])
            calls = {}
            for e in events:
                if "custom-call" in e.name or "custom_call" in e.name:
                    n, d = calls.get(e.name, (0, 0.0))
                    calls[e.name] = (n + 1, d + e.duration_ns)
            for name, (n, d) in calls.items():
                rows.append(f"    CUSTOM-CALL x{n} total_ns={d} {name[:700]!r}")
    return "\n".join(rows)
