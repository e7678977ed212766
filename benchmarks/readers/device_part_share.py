"""The share of the device's busy time that parts of the serving programs
take, in percent, read from the traced slice and from the program's own map of
its compiled instructions to device parts (``paddle_tpu.monitor.
device_parts()``, built while the monitor is on; ``catalog.PARTS`` names the
parts).

Every instant of the first chip's busy union goes to the innermost event that
covers it, the one that started last: a ``while`` keeps only what its body's
operations leave of it, so nothing is counted twice and the events' self times
sum to the busy union. An event is joined with the map by its instruction's
name and result shape, parsed from its text (``%name = shape opcode(...)`` on
a TPU, layouts left out), or by the ``hlo_op`` and ``hlo_module`` it carries
as stats where it has them (a CPU rehearsal's host events). An event the map
does not know is unmatched; one whose name and shape the maps of several
programs give different parts is ambiguous; both, and what the map puts under
no part, are unnamed.

``params``: ``{"parts": [...]}``, the parts whose self time is summed, or
``{"unnamed": true}``. The note gives every part's seconds, the matched,
ambiguous and unmatched seconds, the events that took most self time and the
unnamed ones among them, and the map's programs, instructions and build
seconds. No map (a program that builds
none) or no device operation: nothing returned.
"""
import heapq
import re

_LAYOUT = re.compile(r"\{[^{}]*\}|/\*.*?\*/")
_CACHE = "device_part_split"


def self_times(events):
    """Nanoseconds of each ``(name, start_ns, dur_ns, stats)`` in which it
    was the innermost of the events that ran: the latest started, of two
    that started together the one that ends first."""
    out = [0.0] * len(events)
    spans = sorted((st, st + du, i) for i, (_, st, du, _) in enumerate(events)
                   if du > 0)
    edges = sorted({t for st, end, _ in spans for t in (st, end)})
    running, j = [], 0
    for a, b in zip(edges, edges[1:]):
        while j < len(spans) and spans[j][0] <= a:
            st, end, i = spans[j]
            heapq.heappush(running, (-st, end, i))
            j += 1
        while running and running[0][1] <= a:
            heapq.heappop(running)
        if running:
            out[running[0][2]] += b - a
    return out


def event_key(name):
    """``(instruction, result shape)`` of a device event's text, or None."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return None
    depth = 0
    for i, ch in enumerate(rest):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == " " and depth == 0:
            rest = rest[:i]
            break
    return head.strip().lstrip("%"), _LAYOUT.sub("", rest).replace(" ", "")


def _maps(env):
    if "device_parts" in env:
        return env["device_parts"]
    from paddle_tpu import monitor

    read = getattr(monitor, "device_parts", None)
    return read() if read is not None else {}


def _build_seconds():
    from paddle_tpu import monitor

    parts = getattr(monitor, "parts", None)
    return getattr(parts, "build_seconds", lambda: None)()


def split(env):
    """The first chip's busy time by part, or None: ``{"parts": {part:
    seconds}, ...}``, cached in ``env`` for every metric of the run."""
    if _CACHE in env:
        return env[_CACHE]
    maps, dev = _maps(env), env.get("device_ops")
    env[_CACHE] = None
    if not maps or not dev:
        return None
    by_shape, by_module = {}, {}
    for program, mapping in maps.items():
        module = program.split(":", 1)[0]
        for ins, (shape, part) in mapping.items():
            by_shape.setdefault((ins, shape), set()).add(part)
            by_module.setdefault((module, ins), set()).add(part)
    events = dev[min(dev)]
    own = self_times(events)
    parts, top = {}, {}
    unnamed = {"no_part": 0.0, "ambiguous": 0.0, "unmatched": 0.0}
    for (name, _, _, stats), ns in zip(events, own):
        if not ns:
            continue
        if "hlo_op" in stats and "hlo_module" in stats:
            found = by_module.get((stats["hlo_module"], stats["hlo_op"]))
        else:
            key = event_key(name)
            found = by_shape.get(key) if key else None
        part = next(iter(found)) if found and len(found) == 1 else None
        if part is not None:
            parts[part] = parts.get(part, 0.0) + ns
        else:
            unnamed["unmatched" if found is None else "ambiguous"
                    if len(found) > 1 else "no_part"] += ns
        label = (name.split(" = ", 1)[0][:48], part)
        top[label] = top.get(label, 0.0) + ns
    busy = sum(own)
    if not busy:
        return None
    matched = busy - unnamed["ambiguous"] - unnamed["unmatched"]
    env[_CACHE] = {
        "busy_s": busy * 1e-9,
        "parts": {p: ns * 1e-9 for p, ns in sorted(parts.items())},
        **{f"{k}_s": ns * 1e-9 for k, ns in unnamed.items()},
        "matched_s": matched * 1e-9,
        "top_self": [[name, part, ns * 1e-9] for (name, part), ns in
                     sorted(top.items(), key=lambda kv: -kv[1])[:8]],
        "top_unnamed": [[name, ns * 1e-9] for (name, part), ns in
                        sorted(top.items(), key=lambda kv: -kv[1])
                        if part is None][:5],
        "map": {"programs": len(maps),
                "instructions": sum(len(m) for m in maps.values()),
                "build_s": _build_seconds()}}
    return env[_CACHE]


def read(raw, params, env):
    got = split(env)
    if got is None:
        return None
    unnamed = got["no_part_s"] + got["ambiguous_s"] + got["unmatched_s"]
    seconds = unnamed if params.get("unnamed") else sum(
        got["parts"].get(p, 0.0) for p in params["parts"])
    return 100.0 * seconds / got["busy_s"], got
