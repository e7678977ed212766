"""XLA device-trace (xplane) ingestion: merge device spans into the host
chrome trace and aggregate per-op device time.

Reference analog: the reference merges its host tracer and CUPTI device
tracer into ONE chrome timeline
(paddle/fluid/platform/profiler/chrometracing_logger.cc) and reports per-op
device-time tables (python/paddle/profiler/profiler_statistic.py). On TPU
the device tracer is XLA's own profiler: jax.profiler.start_trace writes an
.xplane.pb whose planes carry the per-kernel device spans. This module reads
it back via jax.profiler.ProfileData (no TensorBoard needed) and translates
event times onto the host clock so both layers land in one timeline.

Clock model: collect_device_events normalizes every event onto a
trace-relative clock (earliest collected event = 0): the raw xplane epoch
differs across tracers (trace start on some, PROCESS start on others), so
the only portable anchor is the trace's own first event. The Profiler
records host perf_counter_ns immediately after
jax.profiler.start_trace returns (xla_t0_ns); device-absolute =
xla_t0_ns + event.start_ns — the same translate-to-host-clock correlation
the reference applies to CUPTI timestamps.

The reader is ``jax.profiler.ProfileData``, which the jax wheel bundles.
"""
from __future__ import annotations

import glob
import os

__all__ = ["collect_device_events", "device_op_stats"]

# lines/events that are scheduler noise rather than op execution
_SKIP_EVENT_PREFIXES = ("ThreadpoolListener::", "TaskDispatcher::", "end: ")
_SKIP_LINE_NAMES = ("python",)


def _iter_xplane_files(trace_dir):
    return sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))


def _is_device_plane(name):
    return name.startswith("/device:")


def _iter_events_profile_data(path):
    """(plane, line, name, start_ns, dur_ns, stats) via jax's bundled
    xplane reader."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                stats = {}
                try:
                    stats = dict(ev.stats)
                except Exception:  # noqa: BLE001 - stats are optional
                    pass
                yield (plane.name, line.name, ev.name,
                       float(ev.start_ns), float(ev.duration_ns), stats)


def _iter_events(path):
    try:
        return list(_iter_events_profile_data(path))
    except Exception:  # noqa: BLE001 - partial/foreign traces: skip file
        return []


def collect_device_events(trace_dir, limit=200000):
    """Read every device-side op span from the trace dir.

    Returns a list of dicts: {plane, line, name, start_ns, dur_ns, hlo_module}
    with start_ns NORMALIZED to the trace (earliest collected event = 0 —
    the raw epoch is build-dependent, see module docstring). Device planes
    ("/device:TPU:N") contribute every op event; the "/host:CPU" plane
    (XLA-CPU backend, used by the virtual-mesh tests) contributes only
    events carrying an hlo_op stat so python-tracing noise stays out.
    Never raises — an unreadable trace yields []."""
    out = []
    for path in _iter_xplane_files(trace_dir):
        for plane_name, line_name, name, start_ns, dur_ns, stats \
                in _iter_events(path):
            if line_name in _SKIP_LINE_NAMES:
                continue
            if any(name.startswith(p) for p in _SKIP_EVENT_PREFIXES):
                continue
            on_device = _is_device_plane(plane_name)
            if not on_device and "hlo_op" not in stats \
                    and "hlo_module" not in stats:
                continue
            out.append({
                "plane": plane_name,
                "line": line_name,
                "name": name,
                "start_ns": start_ns,
                "dur_ns": dur_ns,
                "hlo_module": stats.get("hlo_module"),
            })
            if len(out) >= limit:
                break
        if len(out) >= limit:
            break
    return _normalize_clock(out)


_CLUSTER_GAP_NS = 5e9   # a >5s hole in device activity marks a foreign epoch


def _normalize_clock(events):
    """Shift start_ns onto a trace-relative clock (earliest event of the
    DOMINANT cluster = 0). A CPU tracer has been seen to stamp a handful of
    events without the session base (they land seconds away from the real
    cluster); anchoring on the raw min would shove the whole timeline off
    the host window. Only GLITCH-sized minorities are dropped: at a >5s
    silence, a side holding under max(16, 1%) of the events is discarded;
    a real multi-burst trace (two serving waves seconds apart) keeps every
    burst, separated by its true gap."""
    if not events:
        return events
    events.sort(key=lambda ev: ev["start_ns"])
    lo, hi = 0, len(events)
    glitch = max(16, len(events) // 100)
    for _ in range(8):
        gap_at, gap = None, _CLUSTER_GAP_NS
        for i in range(lo + 1, hi):
            d = events[i]["start_ns"] - events[i - 1]["start_ns"]
            if d > gap:
                gap_at, gap = i, d
        if gap_at is None:
            break
        left, right = gap_at - lo, hi - gap_at
        if right <= glitch and right < left:
            hi = gap_at
        elif left <= glitch and left < right:
            lo = gap_at
        else:
            break   # both sides real: keep the whole trace
    kept = events[lo:hi]
    t0 = kept[0]["start_ns"]
    for ev in kept:
        ev["start_ns"] -= t0
    return kept


def device_op_stats(device_events):
    """Aggregate device spans per op name (the reference's per-op
    device-time table): calls, total/avg/max ns, share of device time.
    Rows sort by total time descending."""
    agg = {}
    for ev in device_events:
        row = agg.setdefault(ev["name"], {
            "name": ev["name"], "calls": 0, "total_ns": 0.0, "max_ns": 0.0,
            "hlo_module": ev.get("hlo_module")})
        row["calls"] += 1
        row["total_ns"] += ev["dur_ns"]
        row["max_ns"] = max(row["max_ns"], ev["dur_ns"])
    total = sum(r["total_ns"] for r in agg.values()) or 1.0
    rows = sorted(agg.values(), key=lambda r: -r["total_ns"])
    for r in rows:
        r["avg_ns"] = r["total_ns"] / r["calls"]
        r["ratio"] = r["total_ns"] / total
    return rows


def chrome_events(device_events, xla_t0_ns, base_pid=900000):
    """Translate device spans into chrome-trace dicts on the host clock.
    One chrome pid per plane, one tid per line, with metadata naming."""
    pids, tids, out = {}, {}, []
    for ev in device_events:
        if ev["plane"] not in pids:
            pid = base_pid + len(pids)
            pids[ev["plane"]] = pid
            out.append({"name": "process_name", "ph": "M", "pid": pid,
                        "tid": 0, "args": {"name": f"XLA {ev['plane']}"}})
        pid = pids[ev["plane"]]
        lkey = (ev["plane"], ev["line"])
        if lkey not in tids:
            tid = len(tids) + 1
            tids[lkey] = tid
            out.append({"name": "thread_name", "ph": "M", "pid": pid,
                        "tid": tid, "args": {"name": ev["line"]}})
        out.append({
            "name": ev["name"],
            "cat": "DeviceOp",
            "ph": "X",
            "ts": (xla_t0_ns + ev["start_ns"]) / 1e3,
            "dur": max(ev["dur_ns"], 1.0) / 1e3,
            "pid": pid,
            "tid": tids[lkey],
            "args": {k: v for k, v in (("hlo_module", ev["hlo_module"]),)
                     if v},
        })
    return out
