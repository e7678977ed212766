"""Op dispatch: the eager hot path.

Reference analog: the generated `*_ad_func` forwards (fluid/eager/auto_code_generator/
generator/eager_gen.py:367) that do AMP cast -> type promotion -> kernel dispatch -> GradNode
creation, and the generated C++ API's kernel selection (phi/api/generator/api_base.py:1327).
TPU-first redesign: every op is a pure jax function; "kernel launch" is jax primitive dispatch
(each primitive is a cached tiny XLA executable); when grad is required the op is linearized
with jax.vjp and the pullback recorded on the Python tape. Under graph capture the same
functions trace into one HLO program, so there is exactly one op implementation for eager,
jit, and SPMD execution.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from ..autograd import tape
from ..framework import capture as _capture
from ..framework import flags
from ..framework.core import Tensor

_REGISTRY = {}


class OpDef:
    __slots__ = ("name", "fn", "differentiable", "amp_category")

    def __init__(self, name, fn, differentiable=True, amp_category=None):
        self.name = name
        self.fn = fn
        self.differentiable = differentiable
        self.amp_category = amp_category


def register_op(name, fn, differentiable=True, amp_category=None):
    opdef = OpDef(name, fn, differentiable, amp_category)
    _REGISTRY[name] = opdef
    return opdef


def get_registry():
    return dict(_REGISTRY)


def _is_tensor(x):
    return isinstance(x, Tensor)


_AMP = None  # lazily bound amp.auto_cast module (hot-path import guard)
_SAVED_HOOKS = []  # autograd.saved_tensors_hooks (pack, unpack) stack
_INEXACT_MEMO = {}

# mesh/spmd_rules.SpecPropagator install slot: sharding-spec propagation +
# explicit resharding through defop dispatch. One-slot disabled guard (same
# discipline as graftsan): when None the cost is a single load per dispatch.
_MESH_RULES = [None]


def _inexact(dt):
    r = _INEXACT_MEMO.get(dt)
    if r is None:
        r = _INEXACT_MEMO[dt] = bool(
            jnp.issubdtype(np.dtype(dt), jnp.inexact))
    return r


class _LazyVjp:
    """Deferred pullback: the linearization runs at BACKWARD time through a
    per-signature jit cache instead of retracing jax.vjp on every forward call.

    The reference keeps the eager per-op hot path in C++ (~us, SURVEY §3.1);
    here the equivalent is: forward = plain primitive dispatch, backward =
    jit-cached (one trace+compile per (op, treedef, static-args, avals)
    signature, then cache hits). Holds the op's input values as residuals —
    the same lifetime the eager pullback closure would have."""

    __slots__ = ("bwd", "vals", "_unpack")

    def __init__(self, bwd, vals):
        self.bwd = bwd
        if _SAVED_HOOKS:
            pack, self._unpack = _SAVED_HOOKS[-1]
            self.vals = [pack(Tensor(v)) for v in vals]
        else:
            self._unpack = None
            self.vals = vals

    def __call__(self, cots):
        vals = self.vals
        if self._unpack is not None:
            unpacked = [self._unpack(v) for v in vals]
            vals = [u.value if isinstance(u, Tensor) else u for u in unpacked]
        return self.bwd(tuple(vals), tuple(cots))


@functools.lru_cache(maxsize=8192)
def _cached_pos_fns(opdef, n_leaves, static_items, t_idx, stop_flags,
                    flags_epoch):
    """Positional-call variant of _cached_op_fns: all args are flat (no
    nested containers, no kwargs), so the rebuilt buffer feeds fn(*buf)
    directly — no tree flatten/unflatten on the hot path."""
    fn = opdef.fn

    def pure(*tvals):
        buf = [None] * n_leaves
        for i, _ty, v in static_items:
            buf[i] = v
        for i, v, sg in zip(t_idx, tvals, stop_flags):
            buf[i] = (jax.lax.stop_gradient(v)
                      if sg and isinstance(v, jax.core.Tracer) else v)
        out = fn(*buf)
        return out if isinstance(out, tuple) else (out,)

    # stable per-signature identity: the tape's master-grad path may key a
    # jit cache on this function object (tape._master_bwd)
    pure.master_cacheable = True

    @jax.jit
    def bwd(tvals, cots):
        return jax.vjp(pure, *tvals)[1](cots)

    return pure, bwd


@functools.lru_cache(maxsize=8192)
def _cached_op_fns(opdef, treedef, n_leaves, static_items, t_idx, stop_flags,
                   flags_epoch):
    """One stable (pure, jitted-backward) pair per op-call signature, so jax.jit's
    own (fn, avals) cache turns repeated backward passes into cache hits.
    ``flags_epoch`` keys the cache on the global flags generation: ops that read
    a flag at trace time (e.g. tpu_matmul_precision) retrace after set_flags
    instead of replaying a stale compiled backward."""
    fn = opdef.fn

    def pure(*tvals):
        buf = [None] * n_leaves
        for i, _ty, v in static_items:
            buf[i] = v
        for i, v, sg in zip(t_idx, tvals, stop_flags):
            # stop_gradient is a ~17us eager no-op on concrete values; it
            # only carries meaning under a trace (the jitted bwd / vjp),
            # where v is a Tracer
            buf[i] = (jax.lax.stop_gradient(v)
                      if sg and isinstance(v, jax.core.Tracer) else v)
        a, k = jax.tree_util.tree_unflatten(treedef, buf)
        out = fn(*a, **k)
        return out if isinstance(out, tuple) else (out,)

    pure.master_cacheable = True   # stable identity (see _cached_pos_fns)

    # note the rematerialization tradeoff: this backward re-runs the primal to
    # rebuild residuals (fwd FLOPs x2 per differentiable op) in exchange for
    # removing the ~ms Python retrace from every forward call. For eager loops
    # over very large single ops set FLAGS_eager_cached_vjp=False to restore
    # forward-time residual capture.
    @jax.jit
    def bwd(tvals, cots):
        return jax.vjp(pure, *tvals)[1](cots)

    return pure, bwd


_NAN_INF_HOOK = [None]  # lazily bound to amp.debugging._scan_op_outputs


def _scan_nan_inf(name, vals):
    """Per-op NaN/Inf scan behind FLAGS check_nan_inf. The scan body
    lives in amp/debugging and rides the compiled device-side finite
    check of analysis/numerics (numsan's kernel) — one bool to host per
    scanned output, replacing the old per-element host scan this module
    used to carry."""
    hook = _NAN_INF_HOOK[0]
    if hook is None:
        from ..amp import debugging as _dbg

        hook = _NAN_INF_HOOK[0] = _dbg._scan_op_outputs
    hook(name, vals)


_DBG_OP_STATS = None  # lazily bound to amp.debugging._OP_STATS (hot-path guard)


def _maybe_record_op_stats(name, vals):
    global _DBG_OP_STATS
    if _DBG_OP_STATS is None:
        from ..amp import debugging as _dbg

        _DBG_OP_STATS = _dbg._OP_STATS
    if _DBG_OP_STATS[0] is not None:
        from ..amp.debugging import _record_op_call

        _record_op_call(name, vals)


def _finish_outputs(opdef, name, out_vals, requires_grad, vjp_fn, pure,
                    t_leaves, stop_flags):
    """Shared dispatch postlude: nan scan, op stats, output Tensor wrap with
    stop_gradient propagation, tape record."""
    if flags.flag("check_nan_inf"):
        _scan_nan_inf(name, out_vals)
    _maybe_record_op_stats(name, out_vals)

    if tape.in_functional_mode():
        rg_out = (
            opdef.differentiable and tape.grad_flag()
            and any(not sg for sg in stop_flags)
        )
    else:
        rg_out = requires_grad
    outputs = []
    for v in out_vals:
        sg = not (rg_out and _inexact(v.dtype))
        outputs.append(Tensor(v, stop_gradient=sg))

    if requires_grad:
        out_avals = [tape.OutAval(v.shape, v.dtype) for v in out_vals]
        tape.record(name, t_leaves, vjp_fn, pure, out_avals, outputs)
    if _MESH_RULES[0] is not None:
        _MESH_RULES[0].post(name, outputs)
    return outputs


_PROF = None   # (collector, Operator event type), resolved on first use


def _prof():
    global _PROF
    if _PROF is None:
        from ..profiler.profiler import TracerEventType, _collector

        _PROF = (_collector, TracerEventType.Operator)
    return _PROF


_MON = None    # (monitor._state, op-calls counter, latency histogram, clock,
#                trace._state, trace module)


def _mon():
    global _MON
    if _MON is None:
        from .. import monitor as _m

        _MON = (_m._state,
                _m.counter("paddle_tpu_dispatch_op_calls_total",
                           labelnames=("op",)),
                _m.histogram("paddle_tpu_dispatch_latency_ns"),
                _m.now_ns, _m.trace._state, _m.trace)
    return _MON


def _trace_ticket(trace):
    """SAMPLED dispatch spans: 1-in-N dispatches land a ``dispatch.op``
    span (N = trace.dispatch_sample_every()). The ticket is drawn BEFORE
    any timing so the 63-in-64 unsampled dispatches pay one atomic count
    bump + a modulo, not two clock reads — the enabled-mode span tax
    stays a fraction of the per-op cost."""
    return next(trace._dispatch_tick) % trace._DISPATCH_SAMPLE_EVERY == 0


def apply(opdef: OpDef, *args, **kwargs):
    """Dispatch one op call. Tensor leaves anywhere in args/kwargs are traced
    inputs. While a Profiler RECORD window is open, every dispatch emits an
    Operator host span (the reference records an event per generated op
    forward, eager_gen.py record-event preamble); the merged chrome trace
    then shows these host defop spans over the XLA device kernel spans.
    With the monitor enabled the same span lands in the dispatch-latency
    histogram and bumps the per-op call counter — one clock (monitor.now_ns)
    feeds both consumers."""
    prof = _prof()
    mon = _mon()
    if prof[0].enabled or mon[0].on or mon[4].on:
        trace_this = mon[4].on and _trace_ticket(mon[5])
        if prof[0].enabled or mon[0].on or trace_this:
            now_ns = mon[3]
            t0 = now_ns()
            try:
                return _apply_impl(opdef, *args, **kwargs)
            finally:
                t1 = now_ns()
                if mon[0].on:
                    mon[1].labels(opdef.name).inc()
                    mon[2].observe_ns(t1 - t0)
                if trace_this:
                    mon[5].record_span(
                        "dispatch.op", t0, t1,
                        attrs={"op": opdef.name,
                               "sample_every": mon[5]._DISPATCH_SAMPLE_EVERY})
                if prof[0].enabled:
                    prof[0].emit(f"op::{opdef.name}", prof[1], t0, t1)
    return _apply_impl(opdef, *args, **kwargs)


def _apply_impl(opdef: OpDef, *args, **kwargs):
    # ---- AMP auto-cast (O1/O2), mirroring eager_gen.py:645 AMP_LOGIC_TEMPLATE ----
    global _AMP
    if _AMP is None:
        from ..amp.auto_cast import _amp_state, amp_cast_inputs

        _AMP = (_amp_state, amp_cast_inputs)
    if _AMP[0]() is not None:
        args, kwargs = _AMP[1](opdef, args, kwargs)

    # ---- SPMD spec propagation (mesh/spmd_rules.py): reshard inputs whose
    # placements disagree with the op's sharding rule, remember the inferred
    # output specs for _finish_outputs ----
    if _MESH_RULES[0] is not None:
        args, kwargs = _MESH_RULES[0].pre(opdef.name, args, kwargs)

    # ---- fast path: flat positional call (the overwhelmingly common shape:
    # no kwargs, no nested containers) skips tree flatten/unflatten and calls
    # fn(*buf) directly; capture mode takes the generic path (it records the
    # treedef) ----
    if not kwargs and (not _capture._ANY_ACTIVE or _capture.active() is None):
        flat_ok = True
        t_idx = []
        t_leaves = []
        for i, a in enumerate(args):
            if isinstance(a, Tensor):
                t_idx.append(i)
                t_leaves.append(a)
            elif isinstance(a, (list, tuple, dict)):
                flat_ok = False
                break
        if flat_ok:
            vals = [t._value for t in t_leaves]
            stop_flags = [t.stop_gradient for t in t_leaves]
            requires_grad = (
                opdef.differentiable
                and tape.is_grad_enabled()
                and any(not sg for sg in stop_flags)
            )
            pure = None
            try:
                if flags.flag("eager_cached_vjp"):
                    t_set = set(t_idx)
                    static_items = tuple(
                        (i, type(a).__name__, a)
                        for i, a in enumerate(args) if i not in t_set)
                    pure, bwd = _cached_pos_fns(
                        opdef, len(args), static_items, tuple(t_idx),
                        tuple(stop_flags), flags.epoch())
            except TypeError:
                pure = None  # unhashable static arg -> generic path
            if pure is not None:
                out_vals = pure(*vals)
                vjp_fn = _LazyVjp(bwd, vals) if requires_grad else None
                outputs = _finish_outputs(
                    opdef, opdef.name, out_vals, requires_grad, vjp_fn,
                    pure, t_leaves, stop_flags)
                if len(outputs) == 1:
                    return outputs[0]
                return tuple(outputs)

    leaves, treedef = jax.tree_util.tree_flatten(
        (args, kwargs), is_leaf=_is_tensor
    )
    t_idx = [i for i, l in enumerate(leaves) if _is_tensor(l)]
    t_leaves = [leaves[i] for i in t_idx]
    vals = [t.value for t in t_leaves]
    stop_flags = [t.stop_gradient for t in t_leaves]

    fn = opdef.fn

    def make_pure():
        def pure(*tvals):
            buf = list(leaves)
            for i, v, sg in zip(t_idx, tvals, stop_flags):
                buf[i] = (jax.lax.stop_gradient(v)
                          if sg and isinstance(v, jax.core.Tracer) else v)
            a, k = jax.tree_util.tree_unflatten(treedef, buf)
            out = fn(*a, **k)
            return out if isinstance(out, tuple) else (out,)

        return pure

    requires_grad = (
        opdef.differentiable
        and tape.is_grad_enabled()
        and any(not sg for sg in stop_flags)
    )

    vjp_fn = None
    if requires_grad:
        # fast path: per-signature cached (pure, jitted-bwd) — the forward runs
        # plain primitive dispatch; linearization is deferred to backward where
        # the jit cache amortizes it. Unhashable static leaves (raw arrays in
        # kwargs) fall back to the direct jax.vjp path.
        t_set = set(t_idx)
        try:
            if not flags.flag("eager_cached_vjp"):
                raise TypeError  # operator opt-out -> direct-vjp path
            # the type name is part of the key: hash(True)==hash(1)==hash(1.0)
            # would otherwise alias specializations across scalar Python types
            static_items = tuple(
                (i, type(l).__name__, l)
                for i, l in enumerate(leaves) if i not in t_set)
            pure, bwd = _cached_op_fns(
                opdef, treedef, len(leaves), static_items,
                tuple(t_idx), tuple(stop_flags), flags.epoch())
        except TypeError:
            pure = None
        if pure is not None:
            out_vals = pure(*vals)
            vjp_fn = _LazyVjp(bwd, vals)
        else:
            pure = make_pure()
            out_vals, vjp_fn = jax.vjp(pure, *vals)
    else:
        pure = make_pure()
        out_vals = pure(*vals)

    # Under graph capture the tape is off but the outer jax.vjp differentiates
    # the whole trace: stop_gradient must then propagate from inputs (paddle
    # semantics: an output requires grad iff any input does) — handled inside
    # _finish_outputs via the functional-mode grad_flag branch.
    outputs = _finish_outputs(opdef, opdef.name, out_vals, requires_grad,
                              vjp_fn, pure, t_leaves, stop_flags)

    if _capture._ANY_ACTIVE:
        _capture.record("op", (opdef, leaves, treedef, t_idx),
                        t_leaves, outputs)

    if len(outputs) == 1:
        return outputs[0]
    return tuple(outputs)


def apply_raw(name, fn, tensor_args, n_outs=1):
    """Tape-aware call where fn takes raw positional values (used by create_graph replay
    and PyLayer)."""
    vals = [t.value for t in tensor_args]
    stop_flags = [t.stop_gradient for t in tensor_args]

    def pure(*tvals):
        tvals = [jax.lax.stop_gradient(v)
                 if sg and isinstance(v, jax.core.Tracer) else v
                 for v, sg in zip(tvals, stop_flags)]
        out = fn(*tvals)
        return tuple(out) if isinstance(out, (tuple, list)) else (out,)

    requires_grad = tape.is_grad_enabled() and any(not sg for sg in stop_flags)
    if requires_grad:
        out_vals, vjp_fn = jax.vjp(pure, *vals)
    else:
        out_vals = pure(*vals)
    if tape.in_functional_mode():
        rg_out = tape.grad_flag() and any(not sg for sg in stop_flags)
    else:
        rg_out = requires_grad
    outputs = []
    for v in out_vals:
        sg = not (rg_out and _inexact(v.dtype))
        outputs.append(Tensor(v, stop_gradient=sg))
    if requires_grad:
        out_avals = [tape.OutAval(v.shape, v.dtype) for v in out_vals]
        tape.record(name, list(tensor_args), vjp_fn, pure, out_avals, outputs)
    if _capture._ANY_ACTIVE:
        _capture.record("raw", (name, fn), list(tensor_args), outputs)
    return tuple(outputs)


def defop(name, differentiable=True, amp_category=None):
    """Decorator: define an op from its pure jax function and return the public wrapper.

    The wrapped function receives raw jax values in place of Tensors; the public wrapper
    accepts Tensors/python scalars and returns Tensors with autograd wired.
    """

    def deco(fn):
        opdef = register_op(name, fn, differentiable, amp_category)

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            kwargs.pop("name", None)  # paddle APIs accept a cosmetic name= kwarg
            return apply(opdef, *args, **kwargs)

        wrapper.opdef = opdef
        return wrapper

    return deco
