"""Device parts: which part of a serving program each compiled instruction is.

The serving programs put each part of a block under a ``jax.named_scope``
whose name ``catalog.PARTS`` declares (``scope(part)``: no other string), so
every instruction of the compiled program carries its part in its
``op_name``. ``record(text)`` reads the optimized HLO of the executable that
runs (``Compiled.as_text()``) into ``{instruction: (result shape, part)}``:

- an instruction takes the innermost declared part on its own ``op_name``
  (an ``op_name`` may list several paths, joined by ``;``: the one part they
  name, or none where they name two);
- one that has none and calls a computation (a fusion, an asynchronous
  start) takes the part held by most instructions of that computation;
- an asynchronous ``*-done`` that has none takes its ``*-start``'s;
- one the compiler made (no ``op_name``, or only the name of a program
  argument it copies, which holds no ``/``: a copy to another layout or
  memory space, a prefetch of a weight) takes the part most of its
  consumers hold (a loop's value is followed from the tuple that carries it
  to the body's reads of that element), else the part of its first operand
  that has one (of a program argument or a loop's value: what its consumers
  hold); a parameter, tuple, tuple element or constant takes none itself;
- a ``while``, ``call`` or ``conditional`` takes its own; anything else
  under no part maps to None.

Only the instructions of computations that run as a sequence of operations
(the entry, loop bodies and conditions, branches, called computations) are
mapped: a device trace names those, never what a fusion holds. A result
shape drops its layouts (``bf16[16,4096]{1,0:T(8,128)(2,1)}`` is
``bf16[16,4096]``), so that a device event's text and the map agree.

Maps are built only while the monitor is on: at ``monitor.enable()`` by the
hooks the programs' owners register (``on_enable``; a bound method is held
weakly), and by an owner that compiles while the monitor is on. They are a
plain dict, ``device_parts()`` (``monitor.device_parts()``), keyed by program
(``<module>:<digest of its text>``), readable after the owner is freed;
``monitor.reset()`` drops them. They are never in a snapshot or a flight
dump. Stdlib-only at import: ``scope`` imports jax when first called.
"""
from __future__ import annotations

import collections
import hashlib
import logging
import math
import re
import threading
import time
import weakref

from . import catalog

__all__ = ["scope", "part_of", "parse", "record", "device_parts",
           "build_seconds", "generation", "on_enable", "shape_key",
           "relayout_bytes"]

_log = logging.getLogger("paddle_tpu.monitor")

_metrics_state = None           # the monitor's _State (set by the package)
_lock = threading.Lock()
_maps = {}                      # program -> {instruction: (shape, part)}
_hooks = []                     # weak references to enable-time builders
_book = {"generation": 0, "build_s": 0.0}

_INSTR = re.compile(r"^\s+(?:ROOT\s+)?%([^\s=]+)\s+=\s+(.*)$")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_REFS = re.compile(r"\b(calls|to_apply|body|condition|branch_computations|"
                   r"true_computation|false_computation)="
                   r"(\{[^}]*\}|%[\w.\-]+)")
_NAME = re.compile(r"%([\w.\-]+)")
_LAYOUT = re.compile(r"\{[^{}]*\}|/\*.*?\*/")
# references whose computation runs as operations of its own in a trace
_SEQUENTIAL = ("body", "condition", "branch_computations",
               "true_computation", "false_computation")


def scope(part):
    """``jax.named_scope(part)`` for a part ``catalog.PARTS`` declares; any
    other name is an error."""
    if part not in catalog.PARTS:
        raise KeyError(f"{part!r} is no device part of monitor/catalog.py "
                       "PARTS")
    import jax

    return jax.named_scope(part)


def part_of(op_name):
    """The innermost declared part on an ``op_name`` path, or None; where
    the ``op_name`` joins several paths with ``;``, the one part they name
    (None where they name two)."""
    found = set()
    for path in op_name.split(";"):
        part = None
        for name in path.split("/"):
            if name in catalog.PARTS:
                part = name
        if part is not None:
            found.add(part)
    return found.pop() if len(found) == 1 else None


def shape_key(shape):
    """A result shape's text without its layouts, comments and spaces."""
    return _LAYOUT.sub("", shape).replace(" ", "")


def _split(rest, at=" "):
    """``(text before, text after)`` the first ``at`` outside brackets."""
    depth = 0
    for i, ch in enumerate(rest):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
            if depth == 0 and at == ")":
                return rest[:i], rest[i + 1:]
        elif ch == at and depth == 0:
            return rest[:i], rest[i + 1:]
    return rest, ""


class _Instr:
    """One instruction as the rules read it."""

    __slots__ = ("shape", "opcode", "part", "callees", "operands", "named",
                 "comp", "index", "body")

    def __init__(self, shape, opcode, part, callees, operands, named, comp,
                 index, body):
        self.shape, self.opcode, self.part = shape, opcode, part
        self.callees, self.operands, self.named = callees, operands, named
        self.comp, self.index, self.body = comp, index, body


# instructions that only route values: they take no part by inference
_STRUCTURAL = ("parameter", "tuple", "get-tuple-element", "constant")
_INDEX = re.compile(r"\bindex=(\d+)")
_BODY = re.compile(r"\bbody=%([\w.\-]+)")


def _instructions(text):
    """``(computation, whether it is the entry, whether the instruction is
    its root, name, result shape, opcode, operands' text, attributes'
    text)`` of each instruction of one HLO module's text, in order."""
    comp, is_entry = None, False
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            # a computation's header starts at the line's first column
            if line[:1].strip() and not line.startswith(("}", "HloModule",
                                                          "//")):
                words = line.split()
                is_entry = words[0] == "ENTRY"
                comp = words[1 if is_entry else 0].lstrip("%")
            continue
        name, rhs = m.groups()
        shape, rest = _split(rhs)
        opcode, _, args = rest.partition("(")
        operands, attrs = _split("(" + args, ")")
        yield (comp, is_entry, line.lstrip().startswith("ROOT"), name, shape,
               opcode.strip(), operands, attrs)


def parse(text):
    """``{instruction: (result shape, part)}`` of one HLO module's text, for
    the instructions of its computations that run as operations."""
    comps, info, users = {}, {}, {}
    entry, sequential, roots, params = None, set(), {}, {}
    for comp, is_entry, root, name, shape, opcode, operands, attrs in \
            _instructions(text):
        if is_entry:
            entry = comp
        own = _OP_NAME.search(attrs)
        callees = []
        for attr, ref in _REFS.findall(attrs):
            names = _NAME.findall(ref)
            if attr == "calls":
                callees += names
            elif attr in _SEQUENTIAL or (attr == "to_apply"
                                         and opcode == "call"):
                sequential.update(names)
        index, body = _INDEX.search(attrs), _BODY.search(attrs)
        info[name] = _Instr(
            shape_key(shape), opcode, part_of(own.group(1)) if own else None,
            callees, _NAME.findall(operands),
            own is not None and "/" in own.group(1), comp,
            int(index.group(1)) if index else None,
            body.group(1) if body else None)
        for o in info[name].operands:
            users.setdefault(o, []).append(name)
        if comp is not None:
            comps.setdefault(comp, []).append(name)
            if root:
                roots[comp] = name
            if opcode == "parameter":
                params[comp] = name
    sequential.add(entry)
    loops = {i.body for i in info.values() if i.opcode == "while"}
    memo = {}

    def consumers(name):
        """What reads ``name``'s value: through an asynchronous ``*-done``
        to what reads that, and through the tuples that carry it into a
        loop (the loop's initial value, or its body's next one) to the
        body's reads of that element."""
        seen, todo = set(), [name]
        while todo:
            value = todo.pop()
            for u in users.get(value, ()):
                i = info[u]
                if u in seen or i.opcode == "get-tuple-element":
                    continue
                seen.add(u)
                if i.opcode.endswith("-done"):     # an asynchronous pair
                    todo.append(u)
                    continue
                if i.opcode != "tuple":
                    yield u
                    continue
                slots = {k for k, o in enumerate(i.operands) if o == value}
                bodies = [info[w].body for w in users.get(u, ())
                          if info[w].opcode == "while"]
                if i.comp in loops and roots.get(i.comp) == u:
                    bodies.append(i.comp)
                for b in bodies:
                    for g in users.get(params.get(b), ()):
                        if info[g].index in slots and g not in seen:
                            seen.add(g)
                            todo.append(g)

    def most(names):
        votes = collections.Counter(p for i in names
                                    if (p := resolve(i)) is not None)
        return votes.most_common(1)[0][0] if votes else None

    def read_by(name):
        """``name``'s part, or for a program argument or a loop's value the
        part most of its consumers hold."""
        i = info.get(name)
        if i is not None and i.opcode in ("parameter", "get-tuple-element"):
            return most(consumers(name))
        return resolve(name)

    def resolve(name):
        i = info.get(name)
        if i is None or i.opcode in _STRUCTURAL:
            return None if i is None else i.part
        if name in memo:
            return memo[name]
        memo[name] = None                       # a cycle reads no part
        part = i.part
        if part is None and i.callees:
            part = most(n for c in i.callees for n in comps.get(c, ()))
        if part is None and i.opcode.endswith("-done") and i.operands:
            part = resolve(i.operands[0])
        if part is None and not i.named:        # the compiler's own
            part = most(consumers(name)) or next(
                (p for o in i.operands if (p := read_by(o)) is not None),
                None)
        memo[name] = part
        return part

    return {name: (info[name].shape, resolve(name))
            for comp in sequential if comp in comps for name in comps[comp]}


_ARRAY = re.compile(r"([a-z]\w*)\[([\d,]*)\](\{[^{}]*\})?")
_SPACE = re.compile(r"S\(\d+\)")
_PARAM = re.compile(r"\d+")
# instructions whose value is (a piece of) what they read, in some layout
# or memory space: a weight stays a weight through them
_MOVES = ("bitcast", "copy", "copy-start", "copy-done", "slice", "slice-start",
          "slice-done", "dynamic-slice")


def _layout(shape):
    """``(dims, element bytes, layout without memory space)`` of the first
    array of a result shape."""
    m = _ARRAY.search(shape)
    if m is None:
        return (), 0, ""
    dims = tuple(int(d) for d in m.group(2).split(",") if d)
    bits = _PARAM.search(m.group(1))
    width = int(bits.group()) // 8 if bits else 1          # pred: a byte
    return dims, width, _SPACE.sub("", m.group(3) or "")


def relayout_bytes(text, argument):
    """Bytes of the program argument ``argument`` (a pytree: its leaves are
    the entry parameters whose ``op_name`` is ``argument`` or starts with
    ``argument[``, as jit names them) that one HLO module's ``copy`` /
    ``copy-start`` instructions write in another layout than they read,
    each instruction counted once where it stands (a loop body's, once).
    A weight is followed through what only moves it (``_MOVES``, a
    ``ConcatBitcast`` of its slices, a tuple element, a loop's carried
    value, a fusion's or call's parameter); a change of memory space alone
    is a prefetch, not a relayout."""
    info, callers, entry = {}, {}, None
    for comp, is_entry, _, name, shape, opcode, operands, attrs in \
            _instructions(text):
        if is_entry:
            entry = comp
        own = _OP_NAME.search(attrs)
        index = _INDEX.search(attrs)
        number = _PARAM.match(operands[1:]) if opcode == "parameter" else None
        info[name] = (shape, opcode, _NAME.findall(operands), comp,
                      own.group(1) if own else "",
                      int(index.group(1)) if index else
                      int(number.group()) if number else None,
                      'custom_call_target="ConcatBitcast"' in attrs)
        for _, ref in _REFS.findall(attrs):
            for callee in _NAME.findall(ref):
                callers[callee] = name
    memo = {}

    def element(name, k):
        """What element ``k`` of a tuple-valued instruction is made of."""
        i = info.get(name)
        if i is None:
            return frozenset()
        if i[1] == "tuple":
            return sources(i[2][k]) if k < len(i[2]) else frozenset()
        if i[1] == "while":                 # what the loop carries
            return element(i[2][0], k)
        caller = info.get(callers.get(i[3]))
        if i[1] == "parameter" and caller is not None \
                and caller[1] == "while":
            return element(caller[2][0], k)
        return frozenset()

    def sources(name):
        """The entry parameters ``name``'s value only moves."""
        if name in memo:
            return memo[name]
        memo[name] = frozenset()            # a cycle moves nothing
        i = info.get(name)
        out = frozenset()
        if i is None:
            pass
        elif i[1] == "parameter":
            caller = info.get(callers.get(i[3]))
            if i[3] == entry:
                out = frozenset([name])
            elif caller is not None and caller[1] != "while" \
                    and i[5] is not None and i[5] < len(caller[2]):
                out = sources(caller[2][i[5]])
        elif i[1] == "get-tuple-element" and i[5] is not None:
            out = element(i[2][0], i[5])
        elif i[1] in _MOVES or i[6]:
            out = frozenset().union(*(sources(o) for o in i[2]))
        memo[name] = out
        return out

    def weight(name):
        op = info[name][4]
        return info[name][3] == entry and (op == argument
                                           or op.startswith(argument + "["))

    total = 0
    for name, i in info.items():
        if i[1] not in ("copy", "copy-start") or not i[2] \
                or i[2][0] not in info:
            continue
        dims, width, layout = _layout(i[0])
        if layout == _layout(info[i[2][0]][0])[2]:
            continue
        if any(weight(s) for s in sources(i[2][0])):
            total += width * math.prod(dims)
    return total


def record(text):
    """Map one compiled program (its optimized HLO text) while the monitor
    is on; nothing otherwise. Returns the program's key, or None."""
    if _metrics_state is None or not _metrics_state.on:
        return None
    module = text.split(None, 2)[1].rstrip(",") \
        if text.startswith("HloModule") else "program"
    key = f"{module}:{hashlib.sha1(text.encode()).hexdigest()[:12]}"
    with _lock:
        if key in _maps:
            return key
    t0 = time.perf_counter()
    mapping = parse(text)
    with _lock:
        _maps[key] = mapping
        _book["build_s"] += time.perf_counter() - t0
    return key


def device_parts():
    """``{program: {instruction: (result shape, part)}}`` of every program
    mapped since the last ``monitor.reset()``."""
    with _lock:
        return dict(_maps)


def build_seconds():
    """Seconds spent parsing programs into maps since the last reset."""
    return _book["build_s"]


def generation():
    """Bumped by every reset: an owner that mapped its programs under an
    older one maps them again."""
    return _book["generation"]


def on_enable(fn):
    """Call ``fn()`` at every ``monitor.enable()`` while it lives (a bound
    method is held weakly, so its object stays collectable): it maps the
    programs its owner compiled before the monitor was on."""
    ref = weakref.WeakMethod(fn) if hasattr(fn, "__self__") \
        else (lambda: fn)
    with _lock:
        _hooks[:] = [r for r in _hooks if r() is not None] + [ref]


def enabled():
    """Run the enable-time hooks (``monitor.enable()`` calls this). A hook
    that raises is logged and skipped: the monitor turns on regardless."""
    with _lock:
        live = [fn for fn in (r() for r in _hooks) if fn is not None]
    for fn in live:
        try:
            fn()
        except Exception:  # noqa: BLE001 - a map is an aid, never a fault
            _log.warning("device parts: %r failed", fn, exc_info=True)


def reset():
    """Drop every map; owners map their programs again at the next enable
    or compile."""
    with _lock:
        _maps.clear()
        _book["build_s"] = 0.0
        _book["generation"] += 1
