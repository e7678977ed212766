"""graftnum passes GI005–GI007: precision flow over the traced programs.

The framework runs four reduced-precision paths (AMP O2 fp16 master
grads, int8/fp8 quantized grad collectives with error feedback, the int8
paged-KV pools, bf16 training) and GI001–GI004 are dtype-blind. These
passes certify the dtype FLOW:

- GI005 precision-flow — a reduction or dot accumulating in fp16/bf16
  over a large contracted axis loses low-order bits every add (the lossy
  sibling of GI004's convert round-trips), and a downcast feeding a sum
  that then widens threw the bits away BEFORE the accumulation it
  widened for. Severity is axis-size-aware: the element counts ride in
  the message, and tiny reductions stay silent.
- GI006 overflow/underflow hazard — a lightweight abstract value-range
  interpretation of the jaxpr (interval domain, ranges seeded from dtype
  bounds, literals and the bounded transcendentals) flags ``exp`` whose
  input may exceed the output dtype's ``log(max)`` (softmax without the
  max-shift), ``log``/``div``/``rsqrt`` reachable from reduced-precision
  values whose operand interval includes zero with no eps guard, and
  fp16-accumulated dots whose static output bound exceeds fp16's 65504
  dynamic range. The max-shift idiom (``sub(x, reduce_max(x))``), eps
  guards (``add`` of a positive literal), and the softmax denominator
  floor (a sum of max-shifted exponentials contains exp(0)=1) are
  recognized, so stabilized softmax and rms_norm analyze clean.
- GI007 loss-scale coverage — an fp16 gradient crossing a collective
  with no scalar loss-scale factor in its provenance (the static/amp.py
  GradScaler multiplies the loss BEFORE backward, so covered grads carry
  the scale through the reduction), and reduced-precision state
  committed to a donated buffer straight from fp16 arithmetic instead of
  downcast from an fp32 master value. bf16 collectives are exempt by
  design (fp32's exponent range — a precision concern for GI005, not a
  range one), as are int8/fp8 quantized collectives (the PR 13 error
  feedback keeps fp32 residuals and the wire dtype is integral).

The abstract domain is deliberately imprecise (documented in
docs/ir_analysis.md): unknown primitives widen to dtype bounds, loops
and conds are analyzed with conservatively seeded bodies, and ``pjit`` /
``shard_map`` bodies inherit their call-site intervals 1:1.
"""
from __future__ import annotations

import math

from . import collectives as _coll
from .ir import IRPass, is_jit_call

__all__ = ["PrecisionFlow", "NumericHazard", "LossScaleCoverage",
           "REDUCED_FLOATS"]

#: float dtypes with a reduced mantissa (fp16: 11 bits, bf16: 8 bits)
REDUCED_FLOATS = ("float16", "bfloat16")

_FLOAT_MAX = {"float16": 65504.0, "bfloat16": 3.3895314e38,
              "float32": 3.4028235e38, "float64": 1.7976931348623157e308}

#: shape/layout ops that forward their operand's value set unchanged
_PASSTHROUGH = frozenset({
    "broadcast_in_dim", "stop_gradient", "convert_element_type",
    "reshape", "squeeze", "expand_dims", "transpose", "copy", "slice",
    "sharding_constraint", "reduce_precision",
})


def _is_var(v):
    return hasattr(v, "aval") and not hasattr(v, "val")


def _dtype_str(v):
    return str(getattr(getattr(v, "aval", None), "dtype", "?"))


def _is_float(dt):
    return dt in _FLOAT_MAX


def _dtype_max(dt):
    return _FLOAT_MAX.get(dt, math.inf)


def _nelems(shape, axes):
    n = 1
    for a in axes:
        n *= int(shape[a])
    return n


def _shape_of(v):
    return tuple(getattr(getattr(v, "aval", None), "shape", ()))


def _contracted_elems(eqn):
    """Product of the contracting-dim sizes of one dot_general."""
    ((lc, _rc), _batch) = eqn.params["dimension_numbers"]
    return _nelems(_shape_of(eqn.invars[0]), lc)


# -- GI005 --------------------------------------------------------------------

class PrecisionFlow(IRPass):
    """GI005: lossy accumulation dtype flow. Reduced-precision floats
    lose low-order bits on EVERY add of a long reduction — fp16 carries
    11 mantissa bits, so summing ~2^11 like-signed terms already rounds
    away single-element contributions entirely; bf16's 8 bits saturate
    by ~2^8. A downcast feeding a sum that then widens is strictly
    worse: the bits are discarded before the accumulation that the
    widening pretends to protect. Thresholds keep tiny (tier-1-sized)
    reductions silent — severity grows with the reduced element count
    and the count is part of the finding."""

    id = "GI005"
    name = "precision-flow"
    rationale = ("fp16/bf16 accumulation over a large axis rounds away "
                 "low-order contributions; a downcast feeding a widened "
                 "sum discards them before accumulating")

    #: reduced-precision accumulations at or above this many contracted
    #: elements are findings (≈ where fp16's 11 mantissa bits saturate)
    ACCUM_ELEMS = 1024
    #: a downcast→sum→widen chain is lossy at much smaller counts: the
    #: widening proves the caller wanted the precision it threw away
    DOWNCAST_ELEMS = 32

    _REDUCE_PRIMS = ("reduce_sum", "cumsum", "cumlogsumexp", "add_any")

    def check(self, program):
        out = []
        for path, jaxpr in _jaxpr_levels(program.jaxpr):
            self._level(program, path, jaxpr, out)
        return out

    def _level(self, program, path, jaxpr, out):
        producer = {}
        for eqn in jaxpr.eqns:
            for ov in eqn.outvars:
                producer[id(ov)] = eqn

        def _where(i, name):
            return f"{path}/{name}[{i}]" if path else f"{name}[{i}]"

        for i, eqn in enumerate(jaxpr.eqns):
            name = eqn.primitive.name
            if name == "dot_general":
                acc = str(eqn.params.get("preferred_element_type")
                          or _dtype_str(eqn.outvars[0]))
                k = _contracted_elems(eqn)
                if acc in REDUCED_FLOATS and k >= self.ACCUM_ELEMS:
                    out.append(self.finding(
                        program, _where(i, name),
                        f"dot_general accumulates in {acc} over "
                        f"{k} contracted elements (~2^"
                        f"{max(0, k.bit_length() - 1)} adds at "
                        f"{11 if acc == 'float16' else 8} mantissa "
                        "bits) — pass preferred_element_type=float32 "
                        "and downcast the result instead"))
            elif name == "reduce_sum":
                src = eqn.invars[0]
                dt = _dtype_str(src)
                axes = eqn.params.get("axes", ())
                n = _nelems(_shape_of(src), axes)
                if dt in REDUCED_FLOATS and n >= self.ACCUM_ELEMS:
                    out.append(self.finding(
                        program, _where(i, name),
                        f"reduce_sum accumulates in {dt} over {n} "
                        "reduced elements — low-order contributions "
                        "round away; accumulate in float32 and downcast "
                        "the sum"))
                self._downcast_widen(program, path, i, eqn, n, producer,
                                     jaxpr, out)

    def _downcast_widen(self, program, path, i, eqn, n, producer, jaxpr,
                        out):
        """A wide→reduced downcast in the summand's provenance whose sum
        ends up wide again: the widening names the precision the
        downcast discarded (jnp.sum re-upcasts fp16 summands to fp32
        internally, so the downcast hides behind an upcast convert —
        walk the whole convert/pass-through chain). A reduced-precision
        INVAR upcast before the sum is the correct mixed-precision
        spelling and stays silent: only an explicit downcast eqn
        flags."""
        if n < self.DOWNCAST_ELEMS:
            return
        v = eqn.invars[0]
        downcast = None       # (wide_dt, reduced_dt) of the lossy convert
        hops = 0
        while _is_var(v) and hops < 64:
            hops += 1
            prev = producer.get(id(v))
            if prev is None or prev.primitive.name not in _PASSTHROUGH:
                break
            if prev.primitive.name == "convert_element_type":
                in_dt = _dtype_str(prev.invars[0])
                out_dt = _dtype_str(prev.outvars[0])
                if out_dt in REDUCED_FLOATS and _is_float(in_dt) \
                        and in_dt not in REDUCED_FLOATS:
                    downcast = (in_dt, out_dt)
                    break
            v = prev.invars[0]
        if downcast is None:
            return
        # does the accumulated value end up wide? either the sum itself
        # accumulates wide, or a downstream convert widens it again
        sum_dt = _dtype_str(eqn.outvars[0])
        widened = _is_float(sum_dt) and sum_dt not in REDUCED_FLOATS
        if not widened:
            sum_out = eqn.outvars[0]
            for later in jaxpr.eqns:
                if later.primitive.name != "convert_element_type":
                    continue
                if any(_is_var(x) and x is sum_out
                       for x in later.invars):
                    new_dt = _dtype_str(later.outvars[0])
                    widened = _is_float(new_dt) \
                        and new_dt not in REDUCED_FLOATS
                    break
        if widened:
            where = (f"{path}/reduce_sum[{i}]" if path
                     else f"reduce_sum[{i}]")
            out.append(self.finding(
                program, where,
                f"downcast {downcast[0]} -> {downcast[1]} feeds a "
                f"reduce_sum over {n} elements whose result is wide "
                "again — the bits were discarded before the "
                "accumulation the widening was meant to protect; sum "
                "first, downcast after"))


# -- GI006 abstract value-range domain ---------------------------------------

class _VR:
    """One abstract value: interval [lo, hi] over the reals, a
    reduced-precision taint (the value passed through fp16/bf16 at some
    point — the bits are already lossy even after a widening convert),
    and ``sum_floor`` (a provable lower bound for a SUM over the value:
    a max-shifted exponential always contains exp(0)=1, the softmax
    denominator's floor)."""

    __slots__ = ("lo", "hi", "taint", "sum_floor")

    def __init__(self, lo, hi, taint=False, sum_floor=None):
        self.lo = lo
        self.hi = hi
        self.taint = taint
        self.sum_floor = sum_floor


def _dtype_vr(dt, taint=None):
    m = _FLOAT_MAX.get(dt)
    if m is not None:
        return _VR(-m, m, taint if taint is not None
                   else dt in REDUCED_FLOATS)
    if dt.startswith(("int", "uint")):
        bits = int("".join(c for c in dt if c.isdigit()) or 64)
        if dt.startswith("uint"):
            return _VR(0.0, float(2 ** bits - 1))
        return _VR(-float(2 ** (bits - 1)), float(2 ** (bits - 1) - 1))
    if dt == "bool":
        return _VR(0.0, 1.0)
    return _VR(-math.inf, math.inf)


def _lit_vr(v):
    val = getattr(v, "val", None)
    dt = _dtype_str(v)
    try:
        lo = float(val.min()) if hasattr(val, "min") else float(val)
        hi = float(val.max()) if hasattr(val, "max") else float(val)
        if math.isnan(lo) or math.isnan(hi):
            return _dtype_vr(dt)
        return _VR(lo, hi, dt in REDUCED_FLOATS)
    except (TypeError, ValueError):
        return _dtype_vr(dt)


def _mul_bound(*xs):
    """inf-safe product of magnitudes."""
    out = 1.0
    for x in xs:
        if x == 0.0:
            return 0.0
        out = math.inf if math.isinf(x) or math.isinf(out) else out * x
    return out


def _amax(vr):
    return max(abs(vr.lo), abs(vr.hi))


def _add_i(a, b):
    """inf-safe interval endpoint add (inf + -inf -> the conservative
    side is handled by callers pairing lows with lows)."""
    if math.isinf(a) or math.isinf(b):
        if math.isinf(a):
            return a if not math.isinf(b) or a == b else math.nan
        return b
    return a + b


def _jaxpr_levels(jaxpr, path=""):
    yield path, jaxpr
    for i, eqn in enumerate(jaxpr.eqns):
        for slot, sub in _coll.iter_subjaxprs(eqn):
            sub_path = f"{path}/{eqn.primitive.name}[{i}].{slot}" \
                if path else f"{eqn.primitive.name}[{i}].{slot}"
            yield from _jaxpr_levels(sub, sub_path)


def _origin_ctx(v, producer, frame=None):
    """Trace one var back through pass-through ops (and the ``max`` with
    a literal guard jax.nn.softmax inserts) to its source var, returning
    ``(origin, eqn, producer, frame)`` — the last two name the jaxpr
    level the walk stopped in, so callers can keep walking from there.

    ``frame`` is ``(link, parent_producer, parent_frame)`` linking a
    call body's invars to the call-site operands one level up; the walk
    hops it when it reaches a body invar, which is how the max-shift
    recognizer survives the optimizer outlining a softmax fragment into
    a ``closed_call`` whose ``reduce_max`` stayed outside."""
    seen = 0
    while _is_var(v) and seen < 64:
        seen += 1
        eqn = producer.get(id(v))
        if eqn is None:
            if frame is not None:
                link, pprod, pframe = frame
                nxt = link.get(id(v))
                if nxt is not None:
                    v, producer, frame = nxt, pprod, pframe
                    continue
            return v, None, producer, frame
        name = eqn.primitive.name
        if name in _PASSTHROUGH:
            v = eqn.invars[0]
            continue
        if name in ("max", "min"):
            var_ops = [x for x in eqn.invars if _is_var(x)]
            if len(var_ops) == 1:
                v = var_ops[0]
                continue
        if name == "select_n":
            # skip the predicate; follow the lone non-constant case.
            # logsumexp's is_finite guard selects between the running
            # max and a broadcast literal 0.0 — a case whose origin
            # resolves to a literal is a constant, not a data path.
            live = []
            for x in eqn.invars[1:]:
                if not _is_var(x):
                    continue
                o, _, _, _ = _origin_ctx(x, producer, frame)
                if _is_var(o):
                    live.append(x)
            if len(live) == 1:
                v = live[0]
                continue
        return v, eqn, producer, frame
    return v, None, producer, frame


def _origin(v, producer, frame=None):
    """:func:`_origin_ctx` without the level context."""
    o, eqn, _, _ = _origin_ctx(v, producer, frame)
    return o, eqn


class NumericHazard(IRPass):
    """GI006: overflow/underflow hazards under abstract value ranges.
    Every var gets an interval seeded from dtype bounds, literals and
    the bounded transcendentals, then transferred forward through the
    jaxpr; hazards fire where a primitive's domain can be violated —
    with the stabilization idioms (max-shift, eps guard, softmax
    denominator floor) recognized so the clean spellings stay silent."""

    id = "GI006"
    name = "overflow-underflow-hazard"
    rationale = ("exp without max-shift, zero-crossing log/div/rsqrt on "
                 "reduced-precision values and fp16 dots past 65504 "
                 "each turn into inf/nan at run time, not trace time")

    def check(self, program):
        out = []
        producer = {}
        self._level(program, program.jaxpr, "", None, out)
        return out

    # -- the forward walk -----------------------------------------------------
    def _level(self, program, jaxpr, path, seed, out, frame=None):
        """One jaxpr level. ``seed`` maps id(invar) -> _VR from the call
        site (pjit/shard_map), else dtype bounds; ``frame`` links this
        body's invars back to the call-site operands (see
        :func:`_origin_ctx`)."""
        env = {}

        def get(v):
            if not _is_var(v):
                return _lit_vr(v)
            vr = env.get(id(v))
            if vr is None:
                vr = _dtype_vr(_dtype_str(v))
                env[id(v)] = vr
            return vr

        for v in list(jaxpr.invars) + list(jaxpr.constvars):
            env[id(v)] = (seed or {}).get(id(v)) or _dtype_vr(_dtype_str(v))

        producer = {}
        for eqn in jaxpr.eqns:
            for ov in eqn.outvars:
                producer[id(ov)] = eqn

        for i, eqn in enumerate(jaxpr.eqns):
            self._transfer(program, jaxpr, path, i, eqn, env, get,
                           producer, out, frame)
        return env

    def _set(self, env, eqn, vr):
        for ov in eqn.outvars:
            env[id(ov)] = vr

    def _transfer(self, program, jaxpr, path, i, eqn, env, get, producer,
                  out, frame=None):
        name = eqn.primitive.name
        ins = [get(v) for v in eqn.invars]
        taint = any(x.taint for x in ins)
        where = f"{path}/{name}[{i}]" if path else f"{name}[{i}]"
        out_dt = _dtype_str(eqn.outvars[0]) if eqn.outvars else "?"

        if name in _PASSTHROUGH:
            src = ins[0]
            t = src.taint or (name == "convert_element_type"
                              and out_dt in REDUCED_FLOATS)
            self._set(env, eqn, _VR(src.lo, src.hi, t, src.sum_floor))
            return
        if name in ("add", "add_any"):
            lo, hi = _add_i(ins[0].lo, ins[1].lo), _add_i(ins[0].hi,
                                                          ins[1].hi)
            if math.isnan(lo):
                lo = -math.inf
            if math.isnan(hi):
                hi = math.inf
            self._set(env, eqn, _VR(lo, hi, taint))
            return
        if name == "sub":
            if self._is_max_shift(eqn, producer, frame):
                self._set(env, eqn, _VR(-math.inf, 0.0, taint))
                return
            lo, hi = _add_i(ins[0].lo, -ins[1].hi), _add_i(ins[0].hi,
                                                           -ins[1].lo)
            if math.isnan(lo):
                lo = -math.inf
            if math.isnan(hi):
                hi = math.inf
            self._set(env, eqn, _VR(lo, hi, taint))
            return
        if name == "mul":
            cands = []
            for a in (ins[0].lo, ins[0].hi):
                for b in (ins[1].lo, ins[1].hi):
                    p = _mul_bound(abs(a), abs(b))
                    cands.append(-p if (a < 0) != (b < 0) else p)
            same = (len(eqn.invars) == 2 and _is_var(eqn.invars[0])
                    and eqn.invars[0] is eqn.invars[1])
            lo = 0.0 if same else min(cands)
            self._set(env, eqn, _VR(lo, max(cands), taint))
            return
        if name in ("neg",):
            self._set(env, eqn, _VR(-ins[0].hi, -ins[0].lo, taint))
            return
        if name == "abs":
            self._set(env, eqn,
                      _VR(max(0.0, ins[0].lo), _amax(ins[0]), taint))
            return
        if name == "square" or (name == "integer_pow"
                                and eqn.params.get("y", 1) % 2 == 0):
            m = _amax(ins[0])
            self._set(env, eqn, _VR(0.0, _mul_bound(m, m), taint))
            return
        if name == "sqrt":
            hi = math.sqrt(ins[0].hi) if 0 <= ins[0].hi < math.inf \
                else math.inf
            self._set(env, eqn,
                      _VR(math.sqrt(max(0.0, ins[0].lo)), hi, taint))
            return
        if name == "rsqrt":
            if taint and ins[0].lo <= 0.0:
                out.append(self.finding(
                    program, where,
                    f"rsqrt over reduced-precision-derived values whose "
                    f"range [{ins[0].lo:.3g}, {ins[0].hi:.3g}] includes "
                    "zero and below — no eps guard between the lossy "
                    "value and the pole; add the eps before the rsqrt "
                    "(rms_norm's x*rsqrt(mean(x^2)+eps) spelling)"))
            if ins[0].lo > 0.0:
                self._set(env, eqn, _VR(
                    1.0 / math.sqrt(ins[0].hi) if ins[0].hi < math.inf
                    else 0.0,
                    1.0 / math.sqrt(ins[0].lo), taint))
            else:
                self._set(env, eqn, _VR(0.0, math.inf, taint))
            return
        if name == "exp":
            log_max = math.log(_dtype_max(out_dt)) \
                if _is_float(out_dt) else math.inf
            if ins[0].hi > log_max:
                hi_s = "inf" if math.isinf(ins[0].hi) \
                    else f"{ins[0].hi:.3g}"
                out.append(self.finding(
                    program, where,
                    f"exp over values that may reach {hi_s} overflows "
                    f"{out_dt} (exp saturates past input "
                    f"{log_max:.1f}) — subtract the row max first "
                    "(the jax.nn.softmax max-shift); the shifted "
                    "exponent is <= 0 and cannot overflow"))
            shifted = ins[0].hi <= 0.0
            lo = math.exp(ins[0].lo) if ins[0].lo > -700 else 0.0
            hi = math.exp(min(ins[0].hi, 700.0))
            self._set(env, eqn, _VR(lo, hi, taint,
                                    sum_floor=1.0 if shifted else None))
            return
        if name == "log":
            guarded = ins[0].lo > 0.0
            if taint and not guarded:
                out.append(self.finding(
                    program, where,
                    f"log over reduced-precision-derived values whose "
                    f"range [{ins[0].lo:.3g}, {ins[0].hi:.3g}] includes "
                    "zero — fp16/bf16 underflow turns a small positive "
                    "into exactly 0 and the log into -inf; add an eps "
                    "guard before the log"))
            lo = math.log(ins[0].lo) if guarded else -math.inf
            hi = math.log(ins[0].hi) if 0 < ins[0].hi < math.inf \
                else math.inf
            self._set(env, eqn, _VR(lo, hi, taint))
            return
        if name == "div":
            den = ins[1]
            if den.taint and den.lo <= 0.0 <= den.hi \
                    and den.sum_floor is None:
                out.append(self.finding(
                    program, where,
                    f"div by a reduced-precision-derived denominator "
                    f"whose range [{den.lo:.3g}, {den.hi:.3g}] includes "
                    "zero with no eps guard — fp16/bf16 underflow makes "
                    "the zero exact; guard the denominator or keep it "
                    "in float32"))
            if den.sum_floor and den.sum_floor > 0 \
                    and 0.0 <= ins[0].lo and ins[0].hi <= den.sum_floor:
                # x / sum(x-family) with sum >= floor >= max x: the
                # normalized softmax lands in [0, 1]
                self._set(env, eqn, _VR(0.0, 1.0, taint))
                return
            if den.lo > 0.0:
                cands = []
                for a in (ins[0].lo, ins[0].hi):
                    for b in (den.lo, den.hi):
                        if a == 0.0:
                            cands.append(0.0)
                        elif math.isinf(a) and math.isinf(b):
                            cands.extend((0.0, a))
                        elif math.isinf(b):
                            cands.append(0.0)
                        else:
                            cands.append(a / b)
                self._set(env, eqn, _VR(min(cands), max(cands), taint))
                return
            self._set(env, eqn, _dtype_vr(out_dt, taint))
            return
        if name in ("logistic",):
            self._set(env, eqn, _VR(0.0, 1.0, taint))
            return
        if name in ("tanh", "erf", "sin", "cos", "sign"):
            self._set(env, eqn, _VR(-1.0, 1.0, taint))
            return
        if name in ("reduce_max", "reduce_min", "max", "min",
                    "reduce_and", "reduce_or", "clamp", "select_n",
                    "concatenate", "pad", "gather", "dynamic_slice",
                    "scatter", "scatter-add", "sort", "rev"):
            los = [x.lo for x in ins] or [-math.inf]
            his = [x.hi for x in ins] or [math.inf]
            self._set(env, eqn, _VR(min(los), max(his), taint))
            return
        if name == "reduce_sum":
            src = ins[0]
            n = _nelems(_shape_of(eqn.invars[0]),
                        eqn.params.get("axes", ()))
            lo = _mul_bound(abs(src.lo), n) * (-1 if src.lo < 0 else 1) \
                if src.lo != 0 else 0.0
            hi = _mul_bound(abs(src.hi), n) * (-1 if src.hi < 0 else 1) \
                if src.hi != 0 else 0.0
            if src.sum_floor is not None:
                lo = max(lo, src.sum_floor)
            self._set(env, eqn, _VR(lo, max(lo, hi), src.taint,
                                    sum_floor=src.sum_floor))
            return
        if name == "dot_general":
            k = _contracted_elems(eqn)
            bound = _mul_bound(_amax(ins[0]), _amax(ins[1]), k)
            if out_dt == "float16" and bound > _FLOAT_MAX["float16"]:
                b_s = "inf" if math.isinf(bound) else f"{bound:.3g}"
                out.append(self.finding(
                    program, where,
                    f"fp16-accumulated dot_general's static output "
                    f"bound {b_s} over {k} contracted elements exceeds "
                    "fp16's 65504 dynamic range — accumulate with "
                    "preferred_element_type=float32 or bound the "
                    "operands first"))
            if math.isinf(bound):
                self._set(env, eqn, _dtype_vr(out_dt, taint))
            else:
                self._set(env, eqn, _VR(-bound, bound, taint))
            return
        if name == "iota":
            n = max((int(d) for d in _shape_of(eqn.outvars[0])),
                    default=1)
            self._set(env, eqn, _VR(0.0, float(max(0, n - 1))))
            return
        if is_jit_call(eqn) or name in (
                "shard_map", "custom_jvp_call", "custom_vjp_call",
                "custom_vjp_call_jaxpr", "remat", "checkpoint",
                "closed_call", "core_call"):
            self._call(program, path, i, eqn, ins, env, out,
                       producer, frame)
            return
        subs = list(_coll.iter_subjaxprs(eqn))
        if subs:
            # loops/conds: conservative body seeding, outputs widen
            for slot, sub in subs:
                sub_path = f"{path}/{name}[{i}].{slot}" if path \
                    else f"{name}[{i}].{slot}"
                self._level(program, sub, sub_path, None, out)
            for ov in eqn.outvars:
                env[id(ov)] = _dtype_vr(_dtype_str(ov), taint or None)
            return
        # unknown primitive: dtype bounds, taint propagates
        for ov in eqn.outvars:
            env[id(ov)] = _dtype_vr(_dtype_str(ov))
            env[id(ov)].taint = env[id(ov)].taint or taint

    def _call(self, program, path, i, eqn, ins, env, out,
              producer=None, frame=None):
        """pjit/shard_map, closed_call and the custom-call wrappers
        forward call-site intervals into the body 1:1 and map the body's
        outvar intervals back; the body also gets a frame linking its
        invars to the call-site operands so the max-shift recognizer
        works across the inlining boundary jax (and the graftir outline
        rewrite) puts around every jitted sub-function."""
        name = eqn.primitive.name
        subs = list(_coll.iter_subjaxprs(eqn))
        sub_env = None
        for slot, sub in subs:
            sub_path = f"{path}/{name}[{i}].{slot}" if path \
                else f"{name}[{i}].{slot}"
            seed = sub_frame = None
            if len(sub.invars) == len(eqn.invars):
                seed = {id(v): vr for v, vr in zip(sub.invars, ins)}
                sub_frame = ({id(v): a for v, a
                              in zip(sub.invars, eqn.invars)},
                             producer, frame)
            sub_env = self._level(program, sub, sub_path, seed, out,
                                  sub_frame)
            if seed is not None and len(sub.outvars) == len(eqn.outvars):
                for ov, sv in zip(eqn.outvars, sub.outvars):
                    got = sub_env.get(id(sv)) if _is_var(sv) \
                        else _lit_vr(sv)
                    if got is not None:
                        env[id(ov)] = got
                return
        taint = any(x.taint for x in ins)
        for ov in eqn.outvars:
            env[id(ov)] = _dtype_vr(_dtype_str(ov))
            env[id(ov)].taint = env[id(ov)].taint or taint

    def _is_max_shift(self, eqn, producer, frame=None):
        """sub(x, reduce_max(x)) through broadcast/stop_gradient/convert
        — the stabilized-softmax shift: the result is provably <= 0.
        The reduce_max may sit one or more call levels up (outlined
        closures); the origin walk hops those frames, and the walk from
        the reduce_max's operand restarts in the level it was found."""
        lhs_o, _ = _origin(eqn.invars[0], producer, frame)
        _, rhs_eqn, rprod, rframe = _origin_ctx(eqn.invars[1], producer,
                                                frame)
        if rhs_eqn is None or rhs_eqn.primitive.name != "reduce_max":
            return False
        max_src, _ = _origin(rhs_eqn.invars[0], rprod, rframe)
        return max_src is lhs_o


# -- GI007 --------------------------------------------------------------------

class LossScaleCoverage(IRPass):
    """GI007: the loss-scale region must COVER every fp16 gradient
    reduction and no reduced-precision state may be committed without a
    master copy. The static/amp.py GradScaler multiplies the loss by S
    before backward, so every covered grad's provenance carries a scalar
    scale factor through the collective; the PR 13 quantized collectives
    are exempt by dtype (int8/fp8 wire with fp32 error-feedback
    residuals), and bf16 is exempt by design (fp32's exponent range
    needs no scaling — its mantissa loss is GI005's department)."""

    id = "GI007"
    name = "loss-scale-coverage"
    rationale = ("an unscaled fp16 gradient underflows in the collective "
                 "reduction; fp16 state committed without an fp32 master "
                 "copy never recovers the bits")

    def check(self, program):
        out = []
        for path, jaxpr in _jaxpr_levels(program.jaxpr):
            self._collectives(program, path, jaxpr, out)
        self._committed_state(program, out)
        return out

    def _collectives(self, program, path, jaxpr, out):
        producer = {}
        for eqn in jaxpr.eqns:
            for ov in eqn.outvars:
                producer[id(ov)] = eqn
        for i, eqn in enumerate(jaxpr.eqns):
            name = eqn.primitive.name
            canon = _coll.COLLECTIVE_PRIMITIVES.get(name)
            if canon is None:
                continue
            for v in eqn.invars:
                if _dtype_str(v) != "float16":
                    continue
                # A rank-0 scalar crossing a collective is replication
                # bookkeeping (the loss-scale factor itself riding a
                # shard_map pbroadcast), not a gradient tensor — the
                # underflow hazard this pass guards against needs a
                # reduced tensor of per-parameter cotangents.
                if not _shape_of(v):
                    continue
                if self._scaled(v, producer):
                    continue
                where = f"{path}/{name}[{i}]" if path else f"{name}[{i}]"
                out.append(self.finding(
                    program, where,
                    f"float16 value crosses collective {canon} with no "
                    "scalar loss-scale factor in its provenance — "
                    "gradients this small underflow to zero in the "
                    "reduction; scale the loss before backward "
                    "(static/amp.py GradScaler) so the scale rides "
                    "through the collective, or reduce in float32"))

    def _scaled(self, v, producer, limit=4096):
        """BFS the provenance for a mul/div by a scalar float — the
        loss-scale factor the GradScaler threads through the cotangent
        chain. Reaching a level invar without one = uncovered
        (documented imprecision: a scale applied in an OUTER jaxpr
        level is not seen; keep the scale inside the step program)."""
        seen, stack = set(), [v]
        while stack and len(seen) < limit:
            cur = stack.pop()
            if id(cur) in seen or not _is_var(cur):
                continue
            seen.add(id(cur))
            eqn = producer.get(id(cur))
            if eqn is None:
                continue
            if eqn.primitive.name in ("mul", "div"):
                for op in eqn.invars:
                    if _shape_of(op) == () and \
                            _is_float(_dtype_str(op)):
                        return True
            stack.extend(eqn.invars)
        return False

    def _committed_state(self, program, out):
        """A donated fp16/bf16 invar aliasing an output that was NOT
        downcast from a wider float means reduced-precision state is
        the only copy — every step re-rounds it (no fp32 master)."""
        jaxpr = program.jaxpr
        donated = program.donated
        if len(donated) != len(jaxpr.invars) or not any(donated):
            return
        producer = {}
        for eqn in jaxpr.eqns:
            for ov in eqn.outvars:
                producer[id(ov)] = eqn

        def _key(v):
            aval = getattr(v, "aval", None)
            return (tuple(getattr(aval, "shape", ())),
                    str(getattr(aval, "dtype", "?")))

        donated_keys = {}
        for idx, (v, d) in enumerate(zip(jaxpr.invars, donated)):
            if d and _dtype_str(v) in REDUCED_FLOATS:
                donated_keys.setdefault(_key(v), idx)
        if not donated_keys:
            return
        for ov in jaxpr.outvars:
            if not _is_var(ov):
                continue
            idx = donated_keys.get(_key(ov))
            if idx is None:
                continue
            eqn = producer.get(id(ov))
            if eqn is None:
                continue
            if eqn.primitive.name == "convert_element_type":
                src_dt = _dtype_str(eqn.invars[0])
                if _is_float(src_dt) and src_dt not in REDUCED_FLOATS:
                    continue        # downcast from an fp32 master: covered
            k = _key(ov)
            out.append(self.finding(
                program, f"invar[{idx}]",
                f"donated {k[1]}{list(k[0])} state is committed "
                f"straight from {k[1]} arithmetic "
                f"({eqn.primitive.name}) with no fp32 master copy — "
                "each step re-rounds the state and the update never "
                "accumulates below one ulp; keep an fp32 master and "
                "downcast after the update (static/amp.py O2)"))
            donated_keys.pop(k, None)
