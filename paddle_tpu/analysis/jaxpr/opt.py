"""graftopt: jaxpr→jaxpr transform engine — the TRANSFORM half of
ROADMAP item 3 (graftir is the analysis half).

graftir's passes NAME what the traced programs waste ("Operator Fusion
in XLA", arXiv 2301.13062: the fusion classes XLA's heuristics leave on
the table); this module REWRITES the jaxpr so the waste is gone before
XLA ever sees it. Every rewrite is semantics-preserving by construction
— the tier-1 tests pin optimized-vs-unoptimized outputs BIT-exact —
and the rewritten program re-analyzes clean under
GI001–GI004 (the ``check_opt_parity`` CI row):

- ``convert-roundtrip`` — a value cast to a WIDER type and straight
  back (``bf16 -> f32 -> bf16``) is the identity; both casts and the
  intermediate buffer are dropped. Only value-preserving round trips
  are eliminated by default: ``f32 -> bf16 -> f32`` truncates the
  mantissa, so removing it would CHANGE bits (GI004 flags it, a human
  fixes the source; ``allow_lossy=True`` opts into the bit-changing
  rewrite for callers that want the arXiv 2301.13062 behavior);
- ``cse`` — duplicated expensive subexpressions (same primitive, same
  params, same operands — literal operands compared by value, which
  the GI004 lint now matches) collapse onto the first computation.
  XLA CSEs within a fusion region but not reliably across region
  boundaries; at the jaxpr level the rewrite is exact and free;
- ``sharding-coalesce`` — when one eqn's operands are pinned to
  DISAGREEING ``sharding_constraint`` specs, GSPMD must insert a
  reshard collective to reconcile them. ``with_sharding_constraint``
  is semantically the identity, so the minority pins are bypassed
  (the consumer reads the pre-pin value) and the disagreement — and
  its implied collective — disappears;
- ``dce`` — eqns whose outputs nothing consumes (including the
  carcasses the rewrites above orphan) are dropped, level by level;
- ``outline`` — maximal runs of elementwise/layout eqns fold into ONE
  ``closed_call`` sub-jaxpr (a single fused closure), so the optimizer
  update and attention epilogue present as one fusible region instead
  of a scatter of top-level eqns. Bit-exact: the inner ops are the
  same ops in the same order.

All rewrites recurse through call-like eqns (pjit / shard_map / scan /
cond / while / remat bodies) without ever changing a sub-jaxpr's
interface, so pjit sharding/donation params stay valid. The engine is
trace-level only — no compile, no dispatch; :func:`optimize_jitted`
rebuilds a runnable (re-jitted, donation-preserving) callable from the
rewritten jaxpr for the serving/mesh drills.

Importing this module costs stdlib only; jax loads on first use.
"""
from __future__ import annotations

from .ir import AnalysisError, ProgramIR, is_jit_call
from .passes import EXPENSIVE_PRIMS as _CSE_PRIMS
from .passes import eqn_structural_key as _cse_key

__all__ = ["AppliedRewrite", "OptimizeResult", "DEFAULT_REWRITES",
           "optimize_closed", "optimize_jaxpr", "optimize_program",
           "optimize_jitted", "count_eqns", "bit_exact"]

#: rewrite ids in application order (dce runs after the substitution
#: rewrites so their orphaned producers are collected; outline runs
#: last, over the cleaned level)
DEFAULT_REWRITES = ("convert-roundtrip", "cse", "sharding-coalesce",
                    "dce", "outline")

#: minimum run length an outlined fused closure must replace — shorter
#: runs gain nothing over leaving the eqns inline
_OUTLINE_MIN = 3


class AppliedRewrite:
    """One applied transform at a program location (the applied-rewrite
    table ``tools/ir_report.py --optimize`` prints)."""

    __slots__ = ("rule", "program", "where", "detail")

    def __init__(self, rule, program, where, detail):
        self.rule = rule
        self.program = program
        self.where = where
        self.detail = detail

    def as_dict(self):
        return {"rule": self.rule, "program": self.program,
                "where": self.where, "detail": self.detail}

    def __repr__(self):
        loc = f"[{self.where}]" if self.where else ""
        return f"{self.program}{loc}: {self.rule} {self.detail}"


class OptimizeResult:
    """What one optimization pass did: the applied-rewrite list plus the
    before/after eqn counts (the dispatch-region accounting the fusion
    bench gates on)."""

    __slots__ = ("name", "applied", "eqns_before", "eqns_after",
                 "regions_before", "regions_after")

    def __init__(self, name, applied, eqns_before, eqns_after,
                 regions_before=None, regions_after=None):
        self.name = name
        self.applied = list(applied)
        self.eqns_before = eqns_before
        self.eqns_after = eqns_after
        self.regions_before = (eqns_before if regions_before is None
                               else regions_before)
        self.regions_after = (eqns_after if regions_after is None
                              else regions_after)

    def by_rule(self):
        out = {}
        for a in self.applied:
            out[a.rule] = out.get(a.rule, 0) + 1
        return out

    def as_dict(self):
        return {"program": self.name, "rewrites": self.by_rule(),
                "eqns_before": self.eqns_before,
                "eqns_after": self.eqns_after,
                "regions_before": self.regions_before,
                "regions_after": self.regions_after,
                "applied": [a.as_dict() for a in self.applied]}


class _Ctx:
    __slots__ = ("program", "rules", "allow_lossy", "applied")

    def __init__(self, program, rules, allow_lossy):
        self.program = program
        self.rules = frozenset(rules)
        self.allow_lossy = allow_lossy
        self.applied = []

    def record(self, rule, where, detail):
        self.applied.append(AppliedRewrite(rule, self.program, where,
                                           detail))


def _is_var(v):
    from jax.extend.core import Var

    return isinstance(v, Var)


def _is_drop(v):
    from jax._src.core import DropVar

    return isinstance(v, DropVar)


def _lossless_roundtrip(src_dtype, mid_dtype):
    """True when ``src -> mid -> src`` is the identity for EVERY value:
    the mid type exactly represents all of src (float widening, int
    widening, int-into-big-enough-float-mantissa, bool into anything).
    Everything else (notably ``f32 -> bf16 -> f32``) changes bits and
    is only rewritten under ``allow_lossy``."""
    import numpy as np

    import jax.numpy as jnp

    src, mid = np.dtype(src_dtype), np.dtype(mid_dtype)
    if src == mid:
        return True

    def _kind(d):
        # jnp.issubdtype, not np: bfloat16 (ml_dtypes) is not a numpy
        # float subtype but IS the case this rule exists for
        if d == np.bool_:
            return "b"
        if jnp.issubdtype(d, jnp.floating):
            return "f"
        if jnp.issubdtype(d, jnp.signedinteger):
            return "i"
        if jnp.issubdtype(d, jnp.unsignedinteger):
            return "u"
        return "?"

    ks, km = _kind(src), _kind(mid)
    if ks == "b":
        return km in ("b", "i", "u", "f")
    if ks in ("i", "u"):
        if km == ks:
            return mid.itemsize >= src.itemsize
        if km == "i" and ks == "u":
            return mid.itemsize > src.itemsize
        if km == "f":
            # value bits of the int must fit the float's mantissa
            bits = src.itemsize * 8 - (1 if ks == "i" else 0)
            try:
                return int(jnp.finfo(mid).nmant) + 1 >= bits
            except Exception:  # noqa: BLE001 - exotic dtype: stay safe
                return False
        return False
    if ks == "f" and km == "f":
        fs, fm = jnp.finfo(src), jnp.finfo(mid)
        return (int(fm.nmant) >= int(fs.nmant)
                and int(fm.maxexp) >= int(fs.maxexp)
                and int(fm.minexp) <= int(fs.minexp))
    return False


def _sub_slots(eqn):
    """[(param_key, index_or_None, wrapper, jaxpr)] for every sub-jaxpr
    an eqn carries; ``wrapper`` is the ClosedJaxpr when the param wraps
    one (its consts ride along unchanged through a rewrite)."""
    out = []
    for key, val in eqn.params.items():
        items = val if isinstance(val, (tuple, list)) else (val,)
        for i, item in enumerate(items):
            inner = getattr(item, "jaxpr", item)
            if hasattr(inner, "eqns"):
                idx = i if isinstance(val, (tuple, list)) else None
                wrapper = item if item is not inner else None
                out.append((key, idx, wrapper, inner))
    return out


def _rewrite_subjaxprs(eqn, path, i, ctx):
    """Recurse the rewrites into an eqn's sub-jaxprs, rebuilding params.
    Sub-jaxpr interfaces (invars/outvars) are never changed, so the
    enclosing eqn's shardings / donation / carry structure stay valid."""
    from jax.extend.core import ClosedJaxpr

    slots = _sub_slots(eqn)
    if not slots:
        return eqn
    new_params = dict(eqn.params)
    for key, idx, _wrapper, _inner in slots:
        val = new_params[key]
        items = list(val) if isinstance(val, (tuple, list)) else [val]
        j = idx if idx is not None else 0
        item = items[j]
        inner = getattr(item, "jaxpr", item)
        slot = f"{key}[{idx}]" if idx is not None else key
        sub_path = (f"{path}/{eqn.primitive.name}[{i}].{slot}"
                    if path else f"{eqn.primitive.name}[{i}].{slot}")
        new_inner = _rewrite_level(inner, sub_path, ctx)
        if new_inner is not inner:
            if isinstance(item, ClosedJaxpr):
                items[j] = ClosedJaxpr(new_inner, item.consts)
            else:
                items[j] = new_inner
            new_params[key] = (tuple(items)
                               if isinstance(val, (tuple, list))
                               else items[j])
    return eqn.replace(params=new_params)


def _same_aval(a, b):
    return (tuple(getattr(a, "shape", ())) == tuple(getattr(b, "shape", ()))
            and getattr(a, "dtype", None) == getattr(b, "dtype", None)
            and getattr(a, "weak_type", False)
            == getattr(b, "weak_type", False))


def _where(path, name, i):
    return f"{path}/{name}[{i}]" if path else f"{name}[{i}]"


def _rewrite_level(jaxpr, path, ctx):
    """Apply every enabled rewrite to ONE jaxpr level (recursing into
    call-like eqns), returning a new jaxpr — or the original object when
    nothing changed at or below this level."""
    rules = ctx.rules
    sub = {}            # Var -> replacement Var (this level)
    producer = {}       # id(outvar) -> producing eqn (post-rewrite)
    cse_seen = {}       # structural key -> surviving outvar
    pinned = {}         # id(constraint outvar) -> (spec repr, input var)
    new_eqns = []
    changed = False

    for i, eqn in enumerate(jaxpr.eqns):
        invars = [sub.get(v, v) if _is_var(v) else v for v in eqn.invars]
        if invars != list(eqn.invars):
            eqn = eqn.replace(invars=invars)
            changed = True
        name = eqn.primitive.name

        rewritten = _rewrite_subjaxprs(eqn, path, i, ctx)
        if rewritten is not eqn:
            eqn = rewritten
            changed = True
        has_subs = bool(_sub_slots(eqn))

        if name == "sharding_constraint" and len(eqn.outvars) == 1:
            spec = repr(getattr(eqn.params.get("sharding"), "spec",
                                eqn.params.get("sharding")))
            pinned[id(eqn.outvars[0])] = (spec, eqn.invars[0])

        # -- convert-roundtrip ------------------------------------------------
        if ("convert-roundtrip" in rules
                and name == "convert_element_type" and not has_subs
                and not eqn.effects and len(eqn.outvars) == 1
                and _is_var(eqn.invars[0])):
            prev = producer.get(id(eqn.invars[0]))
            if (prev is not None
                    and prev.primitive.name == "convert_element_type"
                    and _is_var(prev.invars[0])):
                origin = prev.invars[0]
                out = eqn.outvars[0]
                if _same_aval(origin.aval, out.aval):
                    mid_dt = getattr(eqn.invars[0].aval, "dtype", None)
                    src_dt = getattr(origin.aval, "dtype", None)
                    if (ctx.allow_lossy
                            or _lossless_roundtrip(src_dt, mid_dt)):
                        sub[out] = origin
                        ctx.record(
                            "convert-roundtrip", _where(path, name, i),
                            f"eliminated {src_dt} -> {mid_dt} -> "
                            f"{src_dt} round trip")
                        changed = True
                        continue

        # -- cse --------------------------------------------------------------
        if ("cse" in rules and name in _CSE_PRIMS and not has_subs
                and not eqn.effects and len(eqn.outvars) == 1
                and not _is_drop(eqn.outvars[0])):
            key = _cse_key(eqn)
            prior = cse_seen.get(key)
            if prior is not None:
                sub[eqn.outvars[0]] = prior
                ctx.record("cse", _where(path, name, i),
                           f"duplicate {name} folded onto its first "
                           "computation")
                changed = True
                continue
            cse_seen[key] = eqn.outvars[0]

        # -- sharding-coalesce ------------------------------------------------
        if ("sharding-coalesce" in rules
                and name != "sharding_constraint" and pinned):
            specs = []
            for v in eqn.invars:
                if _is_var(v) and id(v) in pinned:
                    specs.append(pinned[id(v)][0])
            if len(set(specs)) > 1:
                # keep the MAJORITY spec (first-seen breaks ties) and
                # bypass every operand pinned to anything else — the
                # fewest rewired pins and a deterministic winner
                tally = {}
                for s in specs:
                    tally[s] = tally.get(s, 0) + 1
                keep_spec = max(tally, key=lambda s: (tally[s],
                                                      -specs.index(s)))
                fixed = []
                bypassed = 0
                for v in eqn.invars:
                    if (_is_var(v) and id(v) in pinned
                            and pinned[id(v)][0] != keep_spec):
                        fixed.append(pinned[id(v)][1])
                        bypassed += 1
                    else:
                        fixed.append(v)
                eqn = eqn.replace(invars=fixed)
                ctx.record(
                    "sharding-coalesce", _where(path, name, i),
                    f"bypassed {bypassed} minority pin(s) so operands "
                    f"agree on {keep_spec} (no implied GSPMD reshard)")
                changed = True

        for ov in eqn.outvars:
            if _is_var(ov):
                producer[id(ov)] = eqn
        new_eqns.append(eqn)

    new_out = [sub.get(v, v) if _is_var(v) else v for v in jaxpr.outvars]
    if new_out != list(jaxpr.outvars):
        changed = True

    if "dce" in rules:
        new_eqns, dropped = _dce(new_eqns, new_out)
        if dropped:
            ctx.record("dce", path or "<top>",
                       f"dropped {dropped} dead eqn(s)")
            changed = True

    if "outline" in rules:
        new_eqns, outlined = _outline(jaxpr, new_eqns, new_out, path, ctx)
        if outlined:
            changed = True

    if not changed:
        return jaxpr
    return jaxpr.replace(eqns=new_eqns, outvars=new_out)


def _dce(eqns, outvars):
    """Drop eqns no live value depends on (effectful eqns always stay).
    Returns (kept_eqns, dropped_count)."""
    live = {id(v) for v in outvars if _is_var(v)}
    keep = []
    dropped = 0
    for eqn in reversed(eqns):
        used = any(id(ov) in live for ov in eqn.outvars
                   if _is_var(ov) and not _is_drop(ov))
        if used or eqn.effects:
            keep.append(eqn)
            for v in eqn.invars:
                if _is_var(v):
                    live.add(id(v))
        else:
            dropped += 1
    keep.reverse()
    return keep, dropped


def _outlinable(eqn):
    from .hbm import _FUSABLE

    return (eqn.primitive.name in _FUSABLE and not eqn.effects
            and not _sub_slots(eqn)
            and len(eqn.outvars) == 1 and _is_var(eqn.outvars[0])
            and not _is_drop(eqn.outvars[0]))


def _outline(jaxpr, eqns, outvars, path, ctx, min_len=_OUTLINE_MIN):
    """Fold maximal contiguous runs of elementwise/layout eqns into one
    ``closed_call`` eqn each — the "single fused closure" XLA receives
    as one region. Contiguity keeps the rewrite trivially
    order-preserving; the run's external inputs/outputs become the
    closure's interface."""
    from jax._src.core import new_jaxpr_eqn
    from jax.extend.core import ClosedJaxpr
    from jax.extend.core.primitives import closed_call_p

    out = []
    outlined = 0
    level_out = {id(v) for v in outvars if _is_var(v)}
    i = 0
    n = len(eqns)
    while i < n:
        if not _outlinable(eqns[i]):
            out.append(eqns[i])
            i += 1
            continue
        j = i
        while j < n and _outlinable(eqns[j]):
            j += 1
        run = eqns[i:j]
        if len(run) < min_len:
            out.extend(run)
            i = j
            continue
        inside = {id(e.outvars[0]) for e in run}
        ext_in, seen_in = [], set()
        for e in run:
            for v in e.invars:
                if _is_var(v) and id(v) not in inside \
                        and id(v) not in seen_in:
                    seen_in.add(id(v))
                    ext_in.append(v)
        used_later = set()
        for e in eqns[j:]:
            for v in e.invars:
                if _is_var(v):
                    used_later.add(id(v))
        ext_out = [e.outvars[0] for e in run
                   if id(e.outvars[0]) in used_later
                   or id(e.outvars[0]) in level_out]
        if not ext_out:
            out.extend(run)
            i = j
            continue
        sub_jaxpr = jaxpr.replace(constvars=[], invars=ext_in,
                                  outvars=ext_out, eqns=run,
                                  effects=set(),
                                  debug_info=jaxpr.debug_info
                                  .with_unknown_names())
        closed = ClosedJaxpr(sub_jaxpr, [])
        call = new_jaxpr_eqn(
            ext_in, ext_out, closed_call_p,
            dict(call_jaxpr=closed), closed.effects,
            run[-1].source_info)
        out.append(call)
        outlined += 1
        ctx.record("outline",
                   _where(path, run[0].primitive.name, i),
                   f"folded {len(run)} elementwise eqn(s) into one "
                   "fused closure")
        i = j
    return (out, outlined) if outlined else (eqns, 0)


def count_eqns(jaxpr):
    """Total eqns at every level (an outlined closure counts its body
    too, so this number only drops when a rewrite really DELETED work —
    the CSE/DCE/round-trip accounting)."""
    n = len(jaxpr.eqns)
    for eqn in jaxpr.eqns:
        for _k, _i, _w, sub in _sub_slots(eqn):
            n += count_eqns(sub)
    return n


def count_regions(jaxpr):
    """Fusible-region accounting: like :func:`count_eqns` but an
    outlined ``closed_call`` closure counts as ONE region (its body is
    the single fused computation XLA receives) — the dispatch-count
    number tests/test_ir_opt.py gates on."""
    n = len(jaxpr.eqns)
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "closed_call":
            continue
        for _k, _i, _w, sub in _sub_slots(eqn):
            n += count_regions(sub)
    return n


def optimize_jaxpr(jaxpr, name="<jaxpr>", rules=None, allow_lossy=False):
    """Rewrite one (open) jaxpr. Returns ``(new_jaxpr, [AppliedRewrite])``
    — the input object itself when nothing applied."""
    ctx = _Ctx(name, rules if rules is not None else DEFAULT_REWRITES,
               allow_lossy)
    new = _rewrite_level(jaxpr, "", ctx)
    return new, ctx.applied


def optimize_closed(closed, name="<fn>", rules=None, allow_lossy=False):
    """Rewrite a ClosedJaxpr (consts preserved). Returns
    ``(new_closed, [AppliedRewrite])``."""
    from jax.extend.core import ClosedJaxpr

    new, applied = optimize_jaxpr(closed.jaxpr, name=name, rules=rules,
                                  allow_lossy=allow_lossy)
    if new is closed.jaxpr:
        return closed, applied
    return ClosedJaxpr(new, closed.consts), applied


def optimize_program(program, rules=None, allow_lossy=False):
    """Rewrite a :class:`~.ir.ProgramIR` (the graftir analysis view).
    Returns ``(new ProgramIR, OptimizeResult)``; donation mask, invar
    fractions and meta carry over — rewrites never change the program
    interface — so GI001–GI004 re-analyze the optimized program exactly
    like the original."""
    before = count_eqns(program.jaxpr)
    rbefore = count_regions(program.jaxpr)
    new, applied = optimize_jaxpr(program.jaxpr, name=program.name,
                                  rules=rules, allow_lossy=allow_lossy)
    meta = dict(program.meta)
    meta["optimized"] = True
    out = ProgramIR(program.name, new, program.donated,
                    program.invar_fraction, meta=meta)
    return out, OptimizeResult(program.name, applied, before,
                               count_eqns(new), rbefore,
                               count_regions(new))


def optimize_jitted(fn, args, name="<fn>", rules=None, allow_lossy=False,
                    rejit=True):
    """Trace ``fn(*args)``, rewrite its jaxpr, and rebuild a runnable
    callable with the ORIGINAL call signature and output pytree.

    With ``rejit=True`` (default) the rebuilt program is one
    ``jax.jit`` whose donation mask is lifted from the traced jit eqn
    — the one-compiled-program invariant holds (warm calls never
    recompile; the tier-1 sanitize test pins it). Returns
    ``(opt_fn, OptimizeResult)``. Raises :class:`AnalysisError` when
    the trace fails (same typing as :func:`~.ir.trace`)."""
    import jax
    from jax.extend.core import jaxpr_as_fun

    try:
        closed = jax.make_jaxpr(fn)(*args)
        out_shape = jax.eval_shape(fn, *args)
    except Exception as e:
        raise AnalysisError(
            f"tracing program '{name}' for optimization failed: "
            f"{type(e).__name__}: {e}", program=name) from e
    out_tree = jax.tree_util.tree_structure(out_shape)
    before = count_eqns(closed.jaxpr)
    rbefore = count_regions(closed.jaxpr)
    new_closed, applied = optimize_closed(closed, name=name, rules=rules,
                                          allow_lossy=allow_lossy)
    result = OptimizeResult(name, applied, before,
                            count_eqns(new_closed.jaxpr), rbefore,
                            count_regions(new_closed.jaxpr))

    raw = jaxpr_as_fun(new_closed)
    if rejit:
        donate = _donated_flat_indices(new_closed.jaxpr)
        raw = jax.jit(raw, donate_argnums=donate)

    def opt_fn(*call_args):
        flat = jax.tree_util.tree_leaves(call_args)
        return jax.tree_util.tree_unflatten(out_tree, list(raw(*flat)))

    opt_fn._raw = raw               # the flat-signature jitted program
    opt_fn._result = result
    return opt_fn, result


def _donated_flat_indices(outer_jaxpr):
    """Map a traced jit eqn's ``donated_invars`` mask back onto the
    OUTER jaxpr's invar positions (= the flat argument positions of the
    rebuilt callable), so re-jitting preserves the original donation."""
    donate = []
    pos = {id(v): k for k, v in enumerate(outer_jaxpr.invars)}
    for eqn in outer_jaxpr.eqns:
        if not is_jit_call(eqn):
            continue
        mask = eqn.params.get("donated_invars")
        if not mask:
            continue
        for v, d in zip(eqn.invars, mask):
            if d and _is_var(v) and id(v) in pos:
                donate.append(pos[id(v)])
    return tuple(sorted(set(donate)))


def bit_exact(a, b):
    """True when two output pytrees match leaf-for-leaf, bit for bit
    (shape, dtype and every element) — the fusion verification gate."""
    import jax
    import numpy as np

    fa = jax.tree_util.tree_leaves(a)
    fb = jax.tree_util.tree_leaves(b)
    if len(fa) != len(fb):
        return False
    for x, y in zip(fa, fb):
        x, y = np.asarray(x), np.asarray(y)
        if x.shape != y.shape or x.dtype != y.dtype:
            return False
        if not np.array_equal(x, y, equal_nan=True):
            return False
    return True
