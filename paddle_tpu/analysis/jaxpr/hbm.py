"""GI003's engine: static per-device peak-HBM estimation by liveness
walk over a traced jaxpr ("Memory Safe Computations with XLA", arXiv
2206.14148 — memory-budget reasoning belongs at the traced-program
level, where every buffer's size and lifetime is visible before a single
byte is allocated).

Model (error bars documented in docs/ir_analysis.md):

- every value is priced from its aval, PER DEVICE: program invars scale
  by the local/global byte fraction of the example argument's live
  sharding (a ZeRO-1 state row under ``P('dp')`` costs 1/dp per chip),
  and a ``shard_map`` body's avals are already local, so the two
  accountings meet consistently at the shard_map boundary;
- closure constants (``constvars`` — the serving engine's weights) are
  resident for the whole program;
- a buffer frees when its last consumer runs; a DONATED program invar
  frees at its last use (that is what donation buys), a non-donated
  invar stays caller-owned and resident throughout;
- fusion discount: a single-consumer elementwise/layout intermediate
  never materializes (producer-consumer fusion keeps it in registers);
- call-like eqns (jit, shard_map, remat) recurse, and the inner walk
  may free donated operands mid-body — the ZeRO step's full-precision
  grads die into their reduce-scatters long before the gathered
  updates materialize; ``cond`` contributes its max branch,
  ``while``/``scan`` one iteration (scan carries free per iteration —
  XLA double-buffers them);
- the peak depends on the SCHEDULE, which XLA chooses and we don't:
  the walk therefore brackets it between the program-order upper bound
  (``peak_order_bytes``: every eqn in trace order) and a memory-greedy
  lower bound (``peak_sched_bytes``: ready memory-shrinking eqns run
  eagerly, the limit of a memory-aware list scheduler) and estimates
  ``peak_bytes`` as their midpoint.

The estimate is a model, not a promise. The paired bench row
(``detail.hbm_estimate`` vs :func:`measure_compiled` on the same
program) and the tier-1 tolerance test keep it honest — the DP=8
ZeRO-1 llama step lands within a few percent of the compiler's own
buffer accounting.
"""
from __future__ import annotations

import json
import os

from .ir import AnalysisError, _aval_bytes, is_jit_call, trace

__all__ = ["HBMBudgetExceeded", "estimate", "estimate_fn",
           "assert_hbm_budget", "measure_compiled", "load_budgets",
           "DEFAULT_BUDGETS"]

DEFAULT_BUDGETS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "budgets.json")

# eqns whose single body runs exactly once inline — the walk threads
# liveness (and donation credit) straight through them
_INLINE_CALLS = {"shard_map", "remat", "remat2", "checkpoint",
                 "closed_call", "core_call", "custom_jvp_call",
                 "custom_vjp_call", "custom_vjp_call_jaxpr"}

# single-consumer outputs of these primitives fuse into their consumer
# and never land in HBM (elementwise + layout/bitcast ops)
_FUSABLE = {
    "add", "sub", "mul", "div", "rem", "max", "min", "neg", "abs",
    "exp", "log", "log1p", "expm1", "tanh", "logistic", "rsqrt",
    "sqrt", "pow", "integer_pow", "floor", "ceil", "round", "sign",
    "erf", "erfc", "sin", "cos", "tan", "select_n", "clamp", "and",
    "or", "xor", "not", "eq", "ne", "lt", "le", "gt", "ge",
    "convert_element_type", "stop_gradient", "copy",
    "broadcast_in_dim", "squeeze", "reshape", "transpose", "rev",
    "iota", "is_finite", "square",
}


class HBMBudgetExceeded(AnalysisError):
    """A program's estimated per-device peak exceeds its declared budget."""

    def __init__(self, message, program="", estimate=0, budget=0):
        super().__init__(message, program=program, pass_id="GI003")
        self.estimate = estimate
        self.budget = budget


def _sub_jaxprs(eqn):
    """[(kind, jaxpr)] of an eqn's bodies, unwrapping ClosedJaxpr."""
    subs = []
    for key, val in eqn.params.items():
        items = val if isinstance(val, (tuple, list)) else (val,)
        for item in items:
            inner = getattr(item, "jaxpr", item)
            if hasattr(inner, "eqns"):
                subs.append((key, inner))
    return subs


def _is_var(v):
    return hasattr(v, "aval") and not hasattr(v, "val")


def _walk(jaxpr, invar_bytes, freeable, greedy):
    """Liveness walk of one jaxpr level under one schedule.

    ``invar_bytes[i]`` prices invar i (already per-device); ``freeable[i]``
    marks invars whose buffer this walk may release once their last
    consumer runs (donated program inputs, or outer values dying at the
    call site). With ``greedy=False`` eqns run in trace order (upper
    bound); with ``greedy=True`` any ready eqn that strictly shrinks
    residency runs first (the memory-aware-scheduler lower bound).

    Returns ``(peak, end, freed)``: max/final values of a running total
    that starts at the constvars' bytes and counts allocations minus
    releases (``end`` can be negative when donation frees more than the
    program retains), plus the per-invar freed mask. The CALLER's
    resident input bytes are not included — total peak is
    ``sum(invar_bytes) + peak``.
    """
    eqns = list(jaxpr.eqns)
    n = len(eqns)
    ncons = {}
    for eqn in eqns:
        for v in eqn.invars:
            if _is_var(v):
                ncons[id(v)] = ncons.get(id(v), 0) + 1
    outset = set()
    for v in jaxpr.outvars:
        if _is_var(v):
            ncons[id(v)] = ncons.get(id(v), 0) + 1  # permanent ref
            outset.add(id(v))
    refs = dict(ncons)
    bytes_of = {}
    avail = set()
    running = 0
    for cv in jaxpr.constvars:
        b = _aval_bytes(cv.aval)
        bytes_of[id(cv)] = b
        running += b
        avail.add(id(cv))
    invar_idx = {}
    freeable_ids = set()
    for k, v in enumerate(jaxpr.invars):
        invar_idx[id(v)] = k
        bytes_of[id(v)] = invar_bytes[k]
        avail.add(id(v))
        if freeable[k]:
            freeable_ids.add(id(v))
    freed = [False] * len(jaxpr.invars)
    peak = running
    done = [False] * n

    def _fusable(eqn, has_subs):
        if has_subs or eqn.primitive.name not in _FUSABLE:
            return False
        ovs = eqn.outvars
        return (len(ovs) == 1 and _is_var(ovs[0])
                and ncons.get(id(ovs[0]), 0) <= 1
                and id(ovs[0]) not in outset)

    def _deps_ok(i):
        return all((not _is_var(v)) or id(v) in avail
                   for v in eqns[i].invars)

    def _dying_frees(eqn):
        """Bytes released if ``eqn`` ran now (operands at refcount 0)."""
        f = 0
        seen = set()
        for v in eqn.invars:
            if not _is_var(v) or id(v) in seen:
                continue
            seen.add(id(v))
            cnt = sum(1 for x in eqn.invars
                      if _is_var(x) and id(x) == id(v))
            if refs.get(id(v), 0) - cnt == 0:
                k = invar_idx.get(id(v))
                if k is None or (id(v) in freeable_ids and not freed[k]):
                    f += bytes_of.get(id(v), 0)
        return f

    def _consume(eqn, skip_free=()):
        nonlocal running
        for v in eqn.invars:
            if not _is_var(v):
                continue
            vid = id(v)
            refs[vid] -= 1
            if refs[vid] != 0:
                continue
            k = invar_idx.get(vid)
            if k is not None:
                if vid in freeable_ids and not freed[k]:
                    freed[k] = True
                    if vid not in skip_free:
                        running -= bytes_of[vid]
            elif vid not in skip_free:
                running -= bytes_of.get(vid, 0)

    def _execute(i):
        nonlocal running, peak
        eqn = eqns[i]
        name = eqn.primitive.name
        subs = _sub_jaxprs(eqn)
        if subs and (name in _INLINE_CALLS or is_jit_call(eqn)):
            _kind, sub = subs[0]
            consumed = list(eqn.invars)[-len(sub.invars):] \
                if len(eqn.invars) >= len(sub.invars) else list(eqn.invars)
            # price inner invars at the OUTER accounted bytes (a fused
            # 0-priced operand must free as 0; a fraction-scaled program
            # invar frees at its per-device price), falling back to the
            # inner aval only when no outer var backs the slot
            sub_bytes = []
            for j, iv in enumerate(sub.invars):
                ov = consumed[j] if j < len(consumed) else None
                if ov is not None and _is_var(ov) and id(ov) in bytes_of:
                    sub_bytes.append(bytes_of[id(ov)])
                else:
                    sub_bytes.append(_aval_bytes(iv.aval))
            sub_free = []
            seen_ops = set()    # a duplicated operand frees ONCE inside
            for j in range(len(sub.invars)):
                ok = False
                if j < len(consumed) and _is_var(consumed[j]):
                    vid = id(consumed[j])
                    cnt = sum(1 for x in eqn.invars
                              if _is_var(x) and id(x) == vid)
                    k = invar_idx.get(vid)
                    dies = refs.get(vid, 0) - cnt == 0
                    ok = (dies and vid not in seen_ops
                          and (k is None
                               or (vid in freeable_ids
                                   and not freed[k])))
                    seen_ops.add(vid)
                sub_free.append(ok)
            sp, se, sf = _walk(sub, sub_bytes, sub_free, greedy)
            peak = max(peak, running + sp)
            # operands the inner walk already released must not be
            # subtracted again here (se carries their credit)
            inner_freed = {id(consumed[j]) for j, f in enumerate(sf)
                           if f and j < len(consumed)
                           and _is_var(consumed[j])}
            _consume(eqn, skip_free=inner_freed)
            running += se
            for ov, iv in zip(eqn.outvars, sub.outvars):
                if _is_var(ov):
                    bytes_of[id(ov)] = _aval_bytes(iv.aval)
                    avail.add(id(ov))
        else:
            if subs:
                sub_peak = 0
                for _kind, sub in subs:
                    sub_bytes = [_aval_bytes(v.aval) for v in sub.invars]
                    if name == "scan":
                        nc = eqn.params.get("num_consts", 0)
                        sfree = [False] * nc \
                            + [True] * (len(sub.invars) - nc)
                    else:
                        sfree = [False] * len(sub.invars)
                    sp, _se, _sf = _walk(sub, sub_bytes, sfree, greedy)
                    sub_peak = max(sub_peak, sp)
                peak = max(peak, running + sub_peak)
            fusable = _fusable(eqn, bool(subs))
            _consume(eqn)
            for ov in eqn.outvars:
                if _is_var(ov):
                    b = 0 if fusable else _aval_bytes(ov.aval)
                    bytes_of[id(ov)] = b
                    running += b
                    avail.add(id(ov))
            peak = max(peak, running)
        done[i] = True

    cursor = 0
    while cursor < n:
        if greedy:
            progress = True
            while progress:
                progress = False
                for i in range(n):
                    if not done[i] and _deps_ok(i):
                        eqn = eqns[i]
                        alloc = 0 if _fusable(
                            eqn, bool(_sub_jaxprs(eqn))) else sum(
                            _aval_bytes(ov.aval) for ov in eqn.outvars
                            if _is_var(ov))
                        if alloc - _dying_frees(eqn) < 0:
                            _execute(i)
                            progress = True
        while cursor < n and done[cursor]:
            cursor += 1
        if cursor < n:
            _execute(cursor)
    return peak, running, freed


def estimate(program):
    """Per-device HBM estimate of one :class:`~.ir.ProgramIR`.

    Returns a dict: ``peak_bytes`` (the midpoint estimate
    ``assert_hbm_budget`` gates), ``peak_order_bytes`` /
    ``peak_sched_bytes`` (the program-order upper and memory-greedy
    lower schedule bounds), ``args_bytes`` / ``consts_bytes`` /
    ``donated_bytes`` components, ``resident_end_bytes`` (the
    steady-state footprint between calls), and ``n_eqns`` walked.
    """
    jaxpr = program.jaxpr
    invar_bytes = [program.invar_bytes(i)
                   for i in range(len(jaxpr.invars))]
    donated = list(program.donated)
    hi, _end_hi, _freed_hi = _walk(jaxpr, invar_bytes, donated, False)
    lo, end, freed = _walk(jaxpr, invar_bytes, donated, True)
    args = sum(invar_bytes)
    consts = sum(_aval_bytes(cv.aval) for cv in jaxpr.constvars)
    dset = sum(b for b, d in zip(invar_bytes, program.donated) if d)
    kept_args = sum(b for b, f in zip(invar_bytes, freed) if not f)
    return {
        "program": program.name,
        "peak_bytes": int(args + (hi + lo) / 2),
        "peak_order_bytes": int(args + hi),
        "peak_sched_bytes": int(args + lo),
        "args_bytes": int(args),
        "consts_bytes": int(consts),
        "donated_bytes": int(dset),
        "resident_end_bytes": int(max(kept_args + end, 0)),
        "n_eqns": _count_eqns(jaxpr),
    }


def _count_eqns(jaxpr):
    n = len(jaxpr.eqns)
    for eqn in jaxpr.eqns:
        for _k, sub in _sub_jaxprs(eqn):
            n += _count_eqns(sub)
    return n


def estimate_fn(fn, args, name="<fn>", donate_argnums=None):
    """Trace ``fn(*args)`` and estimate — the one-call API."""
    return estimate(trace(fn, args, name, donate_argnums=donate_argnums))


def assert_hbm_budget(fn, args, budget, name="<fn>", donate_argnums=None):
    """Raise :class:`HBMBudgetExceeded` when the static per-device peak
    of ``fn(*args)`` exceeds ``budget`` bytes; returns the estimate dict
    otherwise. The static half of the memory-budget remat planner
    (ROADMAP item 3): budgets are declared, not discovered OOM-first."""
    est = estimate_fn(fn, args, name=name, donate_argnums=donate_argnums)
    if est["peak_bytes"] > int(budget):
        raise HBMBudgetExceeded(
            f"program '{name}': estimated per-device peak "
            f"{est['peak_bytes']} bytes exceeds budget {int(budget)} "
            f"bytes (args={est['args_bytes']}, consts="
            f"{est['consts_bytes']})",
            program=name, estimate=est["peak_bytes"], budget=int(budget))
    return est


def measure_compiled(fn, args):
    """COMPILER-measured buffer bytes of the live program: lower+compile
    ``fn(*args)`` (the one non-trace-only surface in this package) and
    read the executable's own memory analysis. ``peak_bytes`` is
    arguments + temporaries + outputs − aliased (donated outputs reuse
    argument buffers) — the measured twin the estimator is held to
    within tolerance by the tier-1 test. Caveat: backends may embed large
    closure constants in the executable image instead of the buffer
    tables, so const-heavy programs can measure BELOW their true
    device residency — the estimator counts them."""
    ma = fn.lower(*args).compile().memory_analysis()
    arg = int(ma.argument_size_in_bytes)
    temp = int(ma.temp_size_in_bytes)
    out = int(ma.output_size_in_bytes)
    alias = int(ma.alias_size_in_bytes)
    return {"argument_bytes": arg, "temp_bytes": temp,
            "output_bytes": out, "alias_bytes": alias,
            "peak_bytes": arg + temp + out - alias}


def load_budgets(path=None):
    """The per-program budget manifest: {program: budget_bytes}. Missing
    file -> empty manifest (callers decide whether that is an error)."""
    path = DEFAULT_BUDGETS if path is None else path
    if not path or not os.path.exists(path):
        return {}
    with open(path, encoding="utf-8") as f:
        data = json.load(f)
    return {name: int(row["budget_bytes"])
            for name, row in data.get("programs", {}).items()}
