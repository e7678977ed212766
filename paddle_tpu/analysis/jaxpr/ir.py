"""graftir engine core: traced-program wrapper, jaxpr walk, findings,
baseline.

graftlint (``analysis/core.py``) walks source ASTs; this engine walks the
traced IR that actually runs on the device — the jaxpr of a jitted
callable, obtained by ``jax.make_jaxpr`` (abstract tracing only: no XLA
compile, no device dispatch). The vocabulary mirrors graftlint's:

- an :class:`IRFinding` is one pass violation at a program location
  (``program`` + a ``where`` path like ``shard_map[3]/cond[7].branches[1]``);
- findings are silenced by a checked-in baseline
  (``analysis/jaxpr/baseline.json``, same shrink-only JSON schema as the
  lint baseline) keyed by a location-free fingerprint — eqn indices
  churn with every model edit, messages don't — or per-call by passing a
  reduced pass list (jaxprs carry no comments, so there are no inline
  suppressions);
- a crashing pass never fails a build opaquely: :func:`analyze_program`
  wraps it in a typed :class:`AnalysisError` carrying the program name
  and pass id, and the ``ir.analyze`` fault point drills exactly that
  isolation.

Imports stay lazy: pulling in this module costs stdlib only, jax is
touched the first time a callable is traced.
"""
from __future__ import annotations

import collections
import json
import os

from .. import faultinject as _fi

__all__ = ["AnalysisError", "IRFinding", "IRPass", "ProgramIR", "trace",
           "analyze_program", "partition_findings", "load_baseline",
           "write_baseline", "DEFAULT_BASELINE"]

DEFAULT_BASELINE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "baseline.json")


class AnalysisError(RuntimeError):
    """A graftir pass (or the trace feeding it) crashed. Typed so CI rows
    and callers can isolate WHICH program's analysis died instead of
    failing the build opaquely."""

    def __init__(self, message, program="", pass_id=""):
        super().__init__(message)
        self.program = program
        self.pass_id = pass_id


class IRFinding:
    """One pass violation at a traced-program location."""

    __slots__ = ("rule", "program", "where", "message")

    def __init__(self, rule, program, where, message):
        self.rule = rule
        self.program = program
        self.where = where      # jaxpr path, "" for whole-program findings
        self.message = message

    @property
    def fingerprint(self):
        """Baseline key: rule + program + message, NO eqn path — eqn
        indices shift whenever the model grows a layer; the finding
        survives unrelated edits and disappears exactly when the
        offending computation does."""
        return f"{self.rule}:{self.program}:{self.message}"

    def as_dict(self):
        return {"rule": self.rule, "program": self.program,
                "where": self.where, "message": self.message}

    def __repr__(self):
        loc = f"[{self.where}]" if self.where else ""
        return f"{self.program}{loc}: {self.rule} {self.message}"


class IRPass:
    """Base of GI0xx passes: ``check(program)`` -> [IRFinding]."""

    id = "GI000"
    name = "base"
    rationale = ""

    def check(self, program):
        raise NotImplementedError

    def finding(self, program, where, message):
        return IRFinding(self.id, program.name, where, message)


def _aval_bytes(aval):
    """Buffer bytes of one abstract value; 0 for tokens/opaque avals."""
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    n = 1
    for d in shape:
        n *= int(d)
    return n * dtype.itemsize


class ProgramIR:
    """One traced program under analysis: the jaxpr, its donation mask,
    and the per-invar per-device byte fractions taken from the example
    arguments' live shardings.

    ``jaxpr`` is the PROGRAM jaxpr (the body of the top-level pjit when
    the callable was jitted — that eqn carries ``donated_invars``, the
    ground truth the runtime actually aliases by). ``donated[i]`` flags
    program invar i; ``invar_fraction[i]`` is local/global bytes of the
    example argument backing it (1.0 when unsharded or unknown), so the
    HBM walk prices a ZeRO-sharded state row at 1/dp per device exactly
    like the runtime does.
    """

    __slots__ = ("name", "jaxpr", "donated", "invar_fraction", "meta")

    def __init__(self, name, jaxpr, donated, invar_fraction, meta=None):
        self.name = name
        self.jaxpr = jaxpr
        self.donated = tuple(donated)
        self.invar_fraction = tuple(invar_fraction)
        self.meta = dict(meta or {})

    def invar_bytes(self, i, per_device=True):
        b = _aval_bytes(self.jaxpr.invars[i].aval)
        return b * (self.invar_fraction[i] if per_device else 1.0)


def _fraction_of(arg):
    """local-shard/global byte fraction of one example argument."""
    sharding = getattr(arg, "sharding", None)
    shape = getattr(arg, "shape", None)
    if sharding is None or shape is None or not hasattr(
            sharding, "shard_shape"):
        return 1.0
    try:
        local = sharding.shard_shape(tuple(shape))
    except Exception:  # noqa: BLE001 - fall back to replicated pricing
        return 1.0
    num = den = 1
    for a, b in zip(local, shape):
        num *= int(a)
        den *= int(b)
    return num / den if den else 1.0


def is_jit_call(eqn):
    """True for the eqn a ``jax.jit`` callable traces to. Matched against
    the primitive OBJECT, not its name: the name has changed between jax
    releases (a string match then sees nothing, silently), while a moved
    or renamed object fails this import loudly."""
    from jax.extend.core.primitives import jit_p

    return eqn.primitive is jit_p


def trace(fn, args, name, donate_argnums=None):
    """Trace ``fn(*args)`` to a :class:`ProgramIR` (abstract eval only —
    no compile, no dispatch). A jitted ``fn`` contributes its REAL
    donation mask via the top-level pjit eqn; for a plain callable pass
    ``donate_argnums`` to declare the intended donation of whole tree
    arguments."""
    import jax

    try:
        closed = jax.make_jaxpr(fn)(*args)
    except Exception as e:
        raise AnalysisError(
            f"tracing program '{name}' failed: {type(e).__name__}: {e}",
            program=name) from e
    jaxpr = closed.jaxpr
    flat_args = jax.tree_util.tree_leaves(args)
    fractions = {id(v): _fraction_of(a)
                 for v, a in zip(jaxpr.invars, flat_args)}

    # a jitted callable traces to ONE jit eqn wrapping the program; its
    # params carry the donation mask the runtime actually aliases by
    if (len(jaxpr.eqns) == 1 and is_jit_call(jaxpr.eqns[0])
            and list(jaxpr.eqns[0].outvars) == list(jaxpr.outvars)):
        eqn = jaxpr.eqns[0]
        inner = eqn.params["jaxpr"].jaxpr
        donated = tuple(eqn.params.get("donated_invars",
                                       (False,) * len(inner.invars)))
        frac = tuple(fractions.get(id(v), 1.0) for v in eqn.invars)
        return ProgramIR(name, inner, donated, frac,
                         meta={"jitted": True,
                               "n_outer_invars": len(jaxpr.invars)})

    donated = [False] * len(jaxpr.invars)
    if donate_argnums:
        offset = 0
        for i, a in enumerate(args):
            n = len(jax.tree_util.tree_leaves(a))
            if i in tuple(donate_argnums):
                for k in range(offset, offset + n):
                    donated[k] = True
            offset += n
    frac = tuple(fractions.get(id(v), 1.0) for v in jaxpr.invars)
    return ProgramIR(name, jaxpr, donated, frac, meta={"jitted": False})


def analyze_program(program, passes):
    """Run every pass over one program; returns all findings. A crashing
    pass raises a typed :class:`AnalysisError` naming the program and
    pass — the isolation the ``ir.analyze`` fault point drills, so a
    broken analyzer can never fail CI opaquely."""
    findings = []
    for p in passes:
        try:
            _fi.fire("ir.analyze")
            findings.extend(p.check(program))
        except AnalysisError:
            raise
        except Exception as e:  # noqa: BLE001 - re-typed, never opaque
            raise AnalysisError(
                f"pass {p.id} ({p.name}) crashed analyzing program "
                f"'{program.name}': {type(e).__name__}: {e}",
                program=program.name, pass_id=p.id) from e
    findings.sort(key=lambda f: (f.program, f.where, f.rule, f.message))
    return findings


def partition_findings(findings, baseline):
    """(new, baselined) under the fingerprint multiset — each baseline
    entry absorbs exactly as many occurrences as were grandfathered
    (same semantics as graftlint's ``partition``)."""
    budget = collections.Counter(baseline)
    new, base = [], []
    for f in findings:
        if budget[f.fingerprint] > 0:
            budget[f.fingerprint] -= 1
            base.append(f)
        else:
            new.append(f)
    return new, base


def load_baseline(path=None):
    """Fingerprint multiset from a baseline file; empty when absent."""
    path = DEFAULT_BASELINE if path is None else path
    if not path or not os.path.exists(path):
        return collections.Counter()
    with open(path, encoding="utf-8") as f:
        data = json.load(f)
    return collections.Counter(data.get("fingerprints", []))


def write_baseline(path, findings):
    data = {
        "comment": "graftir grandfathered findings — shrink, never grow. "
                   "Regenerate with: python -m paddle_tpu.analysis.jaxpr "
                   "--update-baseline",
        "fingerprints": sorted(f.fingerprint for f in findings),
    }
    with open(path, "w", encoding="utf-8") as f:
        json.dump(data, f, indent=1, sort_keys=True)
        f.write("\n")
    return path
