"""Lower fleet hybrid configs onto mesh axes and run the REAL train step.

Reference analog: the reference's semi-auto ``parallelize`` /
``to_distributed`` entry points plan dp/mp/pp over a ProcessMesh and then
hand execution to the static-graph engine. TPU-first redesign: execution is
ONE ``shard_map``-wrapped, donated, jitted step over the
``jax.sharding.Mesh``:

- the data-parallel axis is MANUAL: the body computes local-batch gradients
  and hand-places the collectives — ``lax.pmean`` grad all-reduce, or the
  ZeRO-1 ``psum_scatter``/``all_gather`` pair when ``shard_optimizer=True``
  (each DP replica updates 1/dp of every parameter and holds 1/dp of the
  optimizer state, arXiv 2004.13336);
- the tensor-parallel axis stays AUTO: the fleet mpu TP layers'
  ``with_sharding_constraint`` annotations keep riding GSPMD inside the
  body, so dp x mp composes without a second code path.

The live Layer/Optimizer objects are threaded functionally: parameter,
accumulator and master values enter the step as arguments, are bound to the
live objects for the trace and restored after it — the tape runs inside the
shard_map trace, so eager model code IS the distributed program.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

# the collective census shares ONE vocabulary with graftir's GI001 pass
# (PR 11 factored the PR 8 private regex out of this module)
from ..analysis import sanitizers as _sanitizers
from ..analysis.jaxpr import collectives as _collectives
from ..framework import random as rng
from ..framework.core import Tensor
from . import comm_opt, zero
from .context import MeshContext

__all__ = ["build_mesh_step", "MeshParallel", "parallelize"]


def _dp_axis_of(ctx):
    """The data-parallel axis: the one literally named 'dp' when the mesh has
    it (fleet's global mesh orders pp before dp — size alone must not pick
    the pipeline axis), else the first non-trivial manual axis."""
    if "dp" in ctx.manual_axes:
        return "dp"
    for name in ctx.manual_axes:
        if ctx.axis_size(name) > 1:
            return name
    return ctx.manual_axes[0] if ctx.manual_axes else ctx.axis_names[0]


def _mesh_spec(x):
    """The PartitionSpec ``x`` is laid out with over a mesh, or None when it
    is unsharded (single-device, or replicated on every mesh axis)."""
    from jax.sharding import NamedSharding

    sh = getattr(x, "sharding", None)
    if isinstance(sh, NamedSharding) and any(
            e is not None for e in tuple(sh.spec)):
        return sh.spec
    return None


def build_mesh_step(model, optimizer, loss_fn, ctx, batch, *,
                    shard_optimizer=False, dp_axis=None, comm=None):
    """One donated fused train step under shard_map over ``ctx``'s mesh.

    Returns ``(jitted, state_fn, params, meta)``:

    - ``jitted(param_values, acc_values, master_values[, residuals],
      *batch)`` -> ``(loss, new_params, new_accs, new_masters[,
      new_residuals])`` with the state args donated (the residual lists
      exist only when ``comm`` compresses with error feedback);
    - ``state_fn()`` -> the initial state value lists (ZeRO states
      already in their sharded ``(dp, k)`` layout, residuals zeroed);
    - ``params`` -> the live Parameter objects (rebind after the run);
    - ``meta`` -> dict with ``dp_axis``/``degree``/``sharded`` flags plus
      the resolved ``comm`` knobs and the trace-time ``comm_runtime``
      holder (bucket assignment, wire bytes).

    ``batch`` is an example global batch (arrays or Tensors) used to fix the
    per-argument partition specs; every later call must keep its ranks.
    ``loss_fn(model, *batch_tensors)`` returns the scalar loss Tensor.
    ``comm`` is a :class:`~paddle_tpu.mesh.comm_opt.CommOptConfig`; the
    default (None / all-off) keeps the legacy per-param fp32 exchange
    bit-for-bit.
    """
    dp_axis = dp_axis or _dp_axis_of(ctx)
    degree = ctx.axis_size(dp_axis)
    mesh = ctx.jax_mesh

    requested = comm.describe() if comm is not None else None
    if comm is not None and comm.active:
        # the comm.quantize fault-point fire site: flag degrades THIS
        # build to the uncompressed reduction (drilled in tier-1)
        mode = comm_opt.resolve_compression(comm.compression)
        comm_eff = comm_opt.CommOptConfig(
            compression=mode, error_feedback=comm.error_feedback,
            overlap=comm.overlap, bucket_bytes=comm.bucket_bytes)
        if not comm_eff.active:
            comm_eff = None
    else:
        comm_eff = None
    use_res = comm_eff is not None and comm_eff.use_residuals
    comm_info = {}      # filled at trace time by the body (host-side)

    if shard_optimizer and getattr(optimizer, "_grad_clip", None) is not None:
        raise ValueError(
            "shard_optimizer=True cannot run a global-norm grad clip inside "
            "per-replica slices (each replica would clip by a different "
            "norm); clip gradients before the step or disable the clip")

    params = [p for _, p in model.named_parameters()]
    for p in params:
        if id(p) not in optimizer._accumulators:
            optimizer._accumulators[id(p)] = optimizer._init_state(p)
        if (optimizer._use_master_weights
                and id(p) not in optimizer._master_weights):
            optimizer._master_weights[id(p)] = p.value.astype(jnp.float32)
    acc_keys = [sorted(optimizer._accumulators[id(p)].keys()) for p in params]
    use_masters = optimizer._use_master_weights
    # a state shards iff it is the param-elementwise kind (same shape);
    # scalar/odd-shaped states stay replicated and update identically on
    # every replica
    acc_sharded = [
        [shard_optimizer
         and optimizer._accumulators[id(p)][k].shape == tuple(p.shape)
         for k in ks]
        for p, ks in zip(params, acc_keys)]
    shapes = [tuple(p.shape) for p in params]

    def _exchange_grads(param_values, res_values):
        """The communication-efficient gradient exchange: bucketed (in
        reverse-autodiff completion order, recorded by the leaf hooks),
        optionally quantized with error feedback. Returns the per-param
        ``sliced`` flags (ZeRO bookkeeping) and the new residual list.
        Runs INSIDE the trace — every collective it emits depends only
        on its own bucket's gradients, so XLA can overlap a bucket's
        communication with the remaining backward compute."""
        with_grad = [i for i, p in enumerate(params)
                     if p.grad is not None]
        seq = comm_info.pop("_seq", {})
        order = sorted(with_grad, key=lambda i: seq.get(i, i))
        nbytes = {i: int(np.prod(shapes[i]) if shapes[i] else 1) * 4
                  for i in with_grad}
        buckets = comm_opt.assign_buckets(
            order, nbytes, comm_eff.bucket_bytes, comm_eff.overlap)
        want = "slice" if shard_optimizer else "full"
        mode = comm_eff.compression
        wire_total = 0
        baseline = 0
        reduced, new_res = {}, {}
        for bucket in buckets:
            blocks = []
            for i in bucket:
                blk = comm_opt.blockify(params[i].grad.value, degree)
                if use_res:
                    blk = blk + res_values[i][0]
                blocks.append(blk)
                baseline += 4 * degree * blk.shape[1] if shard_optimizer \
                    else nbytes[i]
            outs, local_dq, wire = comm_opt.bucket_reduce(
                blocks, dp_axis, degree, mode, want)
            wire_total += wire
            for i, out, blk, dq in zip(bucket, outs, blocks, local_dq):
                reduced[i] = out
                if use_res:
                    new_res[i] = blk - dq
        comm_info.update({
            "buckets": [[i for i in b] for b in buckets],
            "bucket_count": len(buckets),
            "compressed_bytes": int(wire_total),
            "uncompressed_bytes": int(baseline),
            "compression": mode,
            "overlap": comm_eff.overlap,
            "error_feedback": use_res,
        })
        sliced = []
        for i, p in enumerate(params):
            if i not in reduced:
                sliced.append(False)          # frozen: stays whole
                continue
            if shard_optimizer:
                p._replace_value(zero.local_slice(param_values[i],
                                                  dp_axis, degree))
                p.grad = Tensor(reduced[i].astype(p.grad.value.dtype))
                sliced.append(True)
            else:
                full = comm_opt.unblockify(reduced[i], shapes[i])
                p.grad = Tensor(full.astype(p.grad.value.dtype))
                sliced.append(False)
        return sliced, new_res

    def mesh_train_step(param_values, acc_values, master_values, *rest):
        # (the function's name is the compiled program's: a device trace
        # lists the step as jit_mesh_train_step)
        if use_res:
            res_values, batch_vals = rest[0], rest[1:]
        else:
            res_values, batch_vals = [], rest
        with rng.trace_key(jax.random.PRNGKey(0)):
            saved_p = [(p, p._value) for p in params]
            saved_a = {id(p): dict(optimizer._accumulators[id(p)])
                       for p in params}
            saved_m = dict(optimizer._master_weights)
            hook_handles = []
            try:
                for p, v in zip(params, param_values):
                    p._replace_value(v)
                if comm_eff is not None:
                    # record reverse-autodiff COMPLETION order: the leaf
                    # hook fires on every cotangent accumulation; the
                    # last fire per param is its completion tick, and
                    # bucket assignment follows that order
                    seq, tick = {}, [0]
                    comm_info["_seq"] = seq

                    def _mk(idx):
                        def _hook(g, _i=idx):
                            tick[0] += 1
                            seq[_i] = tick[0]
                            return None
                        return _hook

                    for i, p in enumerate(params):
                        if not p.stop_gradient:
                            hook_handles.append(
                                p.register_hook(_mk(i)))
                loss = loss_fn(model, *[Tensor(b) for b in batch_vals])
                loss.backward()
                for h in hook_handles:
                    h.remove()
                hook_handles = []
                new_res_map = {}
                if comm_eff is not None:
                    sliced, new_res_map = _exchange_grads(param_values,
                                                          res_values)
                    if shard_optimizer:
                        for p, ks, vs, sh in zip(params, acc_keys,
                                                 acc_values, acc_sharded):
                            for k, v, s in zip(ks, vs, sh):
                                optimizer._accumulators[id(p)][k] = \
                                    v.reshape(-1) if s else v
                        if use_masters:
                            for p, mv in zip(params, master_values):
                                optimizer._master_weights[id(p)] = \
                                    mv.reshape(-1)
                    else:
                        for p, ks, vs in zip(params, acc_keys, acc_values):
                            for k, v in zip(ks, vs):
                                optimizer._accumulators[id(p)][k] = v
                        if use_masters:
                            for p, mv in zip(params, master_values):
                                optimizer._master_weights[id(p)] = mv
                elif shard_optimizer:
                    # ZeRO-1: reduce-scatter grads, update this replica's
                    # slice of params/state, all-gather updated params
                    sliced = []
                    for p, pv in zip(params, param_values):
                        g = p.grad
                        if g is None:
                            sliced.append(False)  # frozen: stays whole
                            continue
                        gs = zero.scatter_grad(g.value, dp_axis, degree)
                        p._replace_value(zero.local_slice(pv, dp_axis,
                                                          degree))
                        p.grad = Tensor(gs)
                        sliced.append(True)
                    for p, ks, vs, sh in zip(params, acc_keys, acc_values,
                                             acc_sharded):
                        for k, v, s in zip(ks, vs, sh):
                            optimizer._accumulators[id(p)][k] = \
                                v.reshape(-1) if s else v
                    if use_masters:
                        # masters arrive pre-sharded (dp, k): the local view
                        # IS this replica's slice
                        for p, mv in zip(params, master_values):
                            optimizer._master_weights[id(p)] = mv.reshape(-1)
                else:
                    # plain DP: all-reduce (mean) grads; every replica runs
                    # the identical full update
                    sliced = [False] * len(params)
                    for p in params:
                        if p.grad is not None:
                            p.grad = Tensor(jax.lax.pmean(p.grad.value,
                                                          dp_axis))
                    for p, ks, vs in zip(params, acc_keys, acc_values):
                        for k, v in zip(ks, vs):
                            optimizer._accumulators[id(p)][k] = v
                    if use_masters:
                        for p, mv in zip(params, master_values):
                            optimizer._master_weights[id(p)] = mv
                optimizer.step()
                optimizer.clear_grad()
                if shard_optimizer:
                    new_p = [zero.gather_param(p._value, dp_axis, shape,
                                               dtype=pv.dtype)
                             if s else p._value
                             for p, shape, pv, s in zip(params, shapes,
                                                        param_values, sliced)]
                    new_a = [[optimizer._accumulators[id(p)][k]
                              .reshape(1, -1) if s
                              else optimizer._accumulators[id(p)][k]
                              for k, s in zip(ks, sh)]
                             for p, ks, sh in zip(params, acc_keys,
                                                  acc_sharded)]
                    new_m = ([optimizer._master_weights[id(p)]
                              .reshape(1, -1) for p in params]
                             if use_masters else master_values)
                else:
                    new_p = [_pin(p._value, p, sp)
                             for p, sp in zip(params, tp_specs)]
                    new_a = [[_pin(optimizer._accumulators[id(p)][k], p, sp)
                              for k in ks]
                             for p, ks, sp in zip(params, acc_keys, tp_specs)]
                    new_m = ([_pin(optimizer._master_weights[id(p)], p, sp)
                              for p, sp in zip(params, tp_specs)]
                             if use_masters else master_values)
                out = (jax.lax.pmean(loss.value, dp_axis), new_p, new_a,
                       new_m)
                if use_res:
                    new_r = [new_res_map[i][None] if i in new_res_map
                             else res_values[i]
                             for i in range(len(params))]
                    out = out + (new_r,)
                return out
            finally:
                for h in hook_handles:
                    h.remove()
                for p, v in saved_p:
                    p._replace_value(v)
                for p in params:
                    optimizer._accumulators[id(p)] = saved_a[id(p)]
                optimizer._master_weights = saved_m

    # the auto (GSPMD) sharding each parameter arrives with, e.g. a TP
    # weight's P(None, 'mp'). The step's outputs are pinned to it: left to
    # itself GSPMD may hand a state back sharded on another dimension (seen
    # on the TPU compiler for the square o_proj moments), and the next call
    # — its inputs now laid out differently — compiles a second program
    tp_specs = [_mesh_spec(p.value) for p in params]

    def _pin(v, like, spec):
        if spec is None or v.shape != tuple(like.shape):
            return v
        from ..distributed.fleet.mpu.mp_ops import _constrain

        return _constrain(v, mesh, spec)

    p_specs = [P()] * len(params)
    a_specs = [[P(dp_axis) if s else P() for s in sh]
               for sh in acc_sharded]
    if not use_masters:
        m_specs = P()  # prefix spec: broadcasts over the empty masters list
    elif shard_optimizer:
        m_specs = [P(dp_axis)] * len(params)
    else:
        m_specs = [P()] * len(params)
    b_specs = tuple(
        ctx.batch_spec(np.ndim(b.value if isinstance(b, Tensor) else b),
                       axis=dp_axis)
        for b in batch)
    if use_res:
        # each replica's residual is ITS OWN quantization error: a
        # per-replica (degree, k) block, stacked P(dp) over the mesh
        r_specs = [P(dp_axis)] * len(params)
        in_specs = (p_specs, a_specs, m_specs, r_specs) + b_specs
        out_specs = (P(), p_specs, a_specs, m_specs, r_specs)
        donate = (0, 1, 2, 3)
    else:
        in_specs = (p_specs, a_specs, m_specs) + b_specs
        out_specs = (P(), p_specs, a_specs, m_specs)
        donate = (0, 1, 2)
    sm = shard_map(
        mesh_train_step, mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        axis_names=frozenset(mesh.axis_names) - frozenset(ctx.auto_axes),
        check_vma=False)
    jitted = jax.jit(sm, donate_argnums=donate)

    def _prep(v, like=None):
        """Pre-commit a replicated value to the mesh so the FIRST call's
        input layout already matches the donated outputs' — otherwise the
        second step would pay a one-time layout-stabilization recompile.
        ``like`` is the parameter an optimizer state belongs to: a state of
        its shape takes its mesh sharding (a TP-sharded weight's moments and
        master are TP-sharded too, not a whole replica on every device)."""
        if _mesh_spec(v) is not None:
            return v  # keep an existing mesh sharding (TP params)
        if (like is not None and _mesh_spec(like) is not None
                and v.shape == like.shape):
            return jax.device_put(v, like.sharding)
        return ctx.place(v, spec=P())

    def state_fn():
        pv = [_prep(p.value) for p in params]
        av = []
        for p, ks, sh in zip(params, acc_keys, acc_sharded):
            row = []
            for k, s in zip(ks, sh):
                v = optimizer._accumulators[id(p)][k]
                if s:
                    v = ctx.place(zero.init_sharded_state(v, degree),
                                  spec=P(dp_axis))
                else:
                    v = _prep(v, like=p.value)
                row.append(v)
            av.append(row)
        if use_masters:
            if shard_optimizer:
                mv = [ctx.place(zero.init_sharded_state(
                          optimizer._master_weights[id(p)], degree),
                          spec=P(dp_axis)) for p in params]
            else:
                mv = [_prep(optimizer._master_weights[id(p)], like=p.value)
                      for p in params]
        else:
            mv = []
        if not use_res:
            return pv, av, mv
        rv = []
        for shape in shapes:
            _, k = comm_opt.block_layout(shape, degree)
            rv.append(ctx.place(jnp.zeros((degree, degree, k),
                                          dtype=jnp.float32),
                                spec=P(dp_axis)))
        return pv, av, mv, rv

    meta = {"dp_axis": dp_axis, "degree": degree,
            "shard_optimizer": bool(shard_optimizer),
            "auto_axes": ctx.auto_axes, "acc_sharded": acc_sharded,
            "use_masters": use_masters,
            "use_residuals": use_res,
            "comm": (comm_eff.describe() if comm_eff is not None else None),
            "comm_requested": requested,
            "comm_fault_fallback": bool(
                requested is not None
                and requested.get("compression", "none") != "none"
                and (comm_eff is None
                     or comm_eff.compression == "none")),
            "comm_runtime": comm_info}
    return jitted, state_fn, params, meta


class MeshParallel:
    """The handle ``parallelize()`` returns: a stateful, donated mesh train
    step plus its telemetry (comm.mesh_step spans, the optimizer-state-bytes
    gauge, recompile accounting for graftsan)."""

    def __init__(self, model, optimizer, loss_fn, ctx, batch, *,
                 shard_optimizer=False, recompute_policy=None,
                 hbm_budget=None, comm=None):
        self.model = model
        self.optimizer = optimizer
        self.ctx = ctx
        self.shard_optimizer = bool(shard_optimizer)
        self.remat_plan = None
        if recompute_policy is not None:
            self.remat_plan = _resolve_remat(
                model, optimizer, loss_fn, ctx, batch, recompute_policy,
                hbm_budget, shard_optimizer)
        (self._jitted, state_fn, self.params,
         self.meta) = build_mesh_step(model, optimizer, loss_fn, ctx, batch,
                                      shard_optimizer=shard_optimizer,
                                      comm=comm)
        if self.remat_plan is not None:
            self.meta["remat_plan"] = self.remat_plan
        if self.meta["use_residuals"]:
            self._pv, self._av, self._mv, self._rv = state_fn()
        else:
            (self._pv, self._av, self._mv), self._rv = state_fn(), None
        self._acc_keys = [sorted(optimizer._accumulators[id(p)].keys())
                          for p in self.params]
        by_id = {id(p): n for n, p in model.named_parameters()}
        self.param_names = [by_id.get(id(p), f"param_{i}")
                            for i, p in enumerate(self.params)]
        self._steps = 0
        self._collectives = None
        self._collective_bytes = None
        self._closed_jaxpr = None
        self._hlo_text = None
        self._mon = None
        self._gauge_set = False
        self._comm_ctr = None

    # -- telemetry -----------------------------------------------------------
    def _monitor(self):
        if self._mon is None:
            from .. import monitor as _m

            self._mon = _m
        return self._mon

    def optimizer_state_bytes(self):
        """Per-replica optimizer-state bytes (ZeRO layouts count 1/dp of
        every sharded array per replica)."""
        degree = self.meta["degree"]
        total = 0
        for row, sh in zip(self._av, self.meta["acc_sharded"]):
            for v, s in zip(row, sh):
                total += (v.size * v.dtype.itemsize) // (degree if s else 1)
        for v in self._mv:
            total += (v.size * v.dtype.itemsize) \
                // (degree if self.shard_optimizer else 1)
        return total

    def collective_counts(self, *batch):
        """{collective: count} of the step program, via the shared
        census (``analysis/jaxpr/collectives.py`` — the same vocabulary
        GI001 walks statically). The cheap path parses the StableHLO
        from an AOT lower (trace only — the manual-axis collectives the
        body hand-places are already explicit ops there); only if that
        shows nothing (everything GSPMD-inserted) does it pay a full
        AOT compile for the optimized HLO."""
        if self._collectives is None:
            lowered = self._jitted.lower(*self._step_args(batch))
            # auto axes: GSPMD may insert collectives that exist only in
            # compiled HLO — force the compile so the byte merge in
            # collective_bytes prices them (pure-manual meshes keep the
            # cheap StableHLO path, where the census is already complete)
            self._collectives, self._hlo_text = \
                _collectives.census_lowered_text(
                    lowered, force_compile=bool(self.meta["auto_axes"]))
        return self._collectives

    def step_jaxpr(self, *batch):
        """The traced (closed) jaxpr of this step program, cached after
        the first trace — the input of the jaxpr-walking consumers: the
        byte census and the graftir passes."""
        if self._closed_jaxpr is None:
            self._closed_jaxpr = jax.make_jaxpr(self._jitted)(
                *self._step_args(batch))
        return self._closed_jaxpr

    def collective_bytes(self, *batch):
        """Per-collective BYTES-on-wire of the step program
        (``analysis/jaxpr/collectives.byte_census_jaxpr`` over the
        traced step): ``{collective: {"count", "bytes"}}`` with bytes
        the per-device payload of each hand-placed (manual-axis)
        collective — int8/f8 wire avals of the compressed exchange are
        priced at their true 1 byte/element. Collectives the jaxpr walk
        cannot see (GSPMD-inserted on auto axes, or post-compile
        lowerings of routed device_puts) are priced from the SAME
        compiler text :meth:`collective_counts` already parsed, via
        ``byte_census_hlo`` (entries carry ``priced_by: "hlo"``).
        Cached after the first trace; surfaced as ``<collective>_bytes``
        attrs on ``comm.mesh_step`` spans."""
        if self._collective_bytes is None:
            closed = self.step_jaxpr(*batch)
            census = _collectives.byte_census_jaxpr(closed.jaxpr)
            # merge the HLO-text pricing for ops the jaxpr cannot see
            self.collective_counts(*batch)
            hlo = _collectives.byte_census_hlo(self._hlo_text or "")
            for op, row in hlo.items():
                if op not in census:
                    census[op] = {"count": row["count"],
                                  "bytes": row["bytes"],
                                  "priced_by": "hlo"}
            self._collective_bytes = census
        return self._collective_bytes

    def comm_report(self, *batch):
        """The communication-efficiency report of this step program:
        the trace-time bucket assignment (names, count), compressed
        wire bytes per step vs the uncompressed-equivalent baseline,
        and the resolved knobs. Forces one trace when the step has not
        run yet; None when the handle runs the legacy exchange."""
        if self.meta["comm"] is None:
            return None
        if not self.meta["comm_runtime"] and batch:
            jax.make_jaxpr(self._jitted)(*self._step_args(batch))
        rt = self.meta["comm_runtime"]
        report = {k: v for k, v in rt.items() if not k.startswith("_")}
        if "buckets" in report:
            report["buckets"] = [[self.param_names[i] for i in b]
                                 for b in report["buckets"]]
        if report.get("uncompressed_bytes"):
            report["bytes_ratio"] = round(
                report["compressed_bytes"]
                / report["uncompressed_bytes"], 4)
        report.update(self.meta["comm"])
        report["fault_fallback"] = self.meta["comm_fault_fallback"]
        return report

    def _step_args(self, batch):
        vals = [b.value if isinstance(b, Tensor) else jnp.asarray(b)
                for b in batch]
        if self._rv is not None:
            return [self._pv, self._av, self._mv, self._rv] + vals
        return [self._pv, self._av, self._mv] + vals

    # -- the step ------------------------------------------------------------
    def step(self, *batch):
        """Run one donated mesh train step on a GLOBAL batch; returns the
        global-batch loss as a Tensor (device value, not forced)."""
        _m = self._monitor()
        dp = self.meta["degree"]
        vals = []
        for b in batch:
            v = b.value if isinstance(b, Tensor) else jnp.asarray(b)
            if v.ndim and v.shape[0] % dp:
                raise ValueError(
                    f"global batch dim {v.shape[0]} is not divisible by "
                    f"dp={dp}")
            vals.append(v)
        before = self._jitted._cache_size()
        t0 = _m.now_ns() if _m.trace._state.annotate else 0
        # the call returns once the step is enqueued: mesh.step is the
        # host's part of it, on the profiler's host plane beside the
        # device trace (a no-op with both switches off)
        with _m.trace.phase("mesh.step"):
            if self._rv is not None:
                loss, self._pv, self._av, self._mv, self._rv = \
                    self._jitted(self._pv, self._av, self._mv, self._rv,
                                 *vals)
            else:
                loss, self._pv, self._av, self._mv = self._jitted(
                    self._pv, self._av, self._mv, *vals)
        self._steps += 1
        if _sanitizers._state.numerics:
            regions = [("loss", loss), ("params", self._pv),
                       ("opt_state", (self._av, self._mv))]
            if self._rv is not None:
                regions.append(("residuals", self._rv))
            _sanitizers.numsan_check("mesh.train_step", regions,
                                     step=self._steps)
        if self._jitted._cache_size() > before:
            try:
                from ..analysis import sanitizers as _san

                _san.note_compile(
                    "mesh.step",
                    tuple(v.shape for v in vals))
            except Exception:  # noqa: BLE001 - accounting must not kill a step
                pass
        if t0:
            t1 = _m.now_ns()
            rt = self.meta["comm_runtime"]
            if _m._state.on and not self._gauge_set:
                _m.gauge("paddle_tpu_mesh_optimizer_state_bytes").set(
                    self.optimizer_state_bytes())
                if rt:
                    _m.gauge("paddle_tpu_mesh_grad_buckets").set(
                        rt.get("bucket_count", 0))
                self._gauge_set = True
            if _m._state.on and rt and rt.get("compression",
                                              "none") != "none":
                # the counter is COMPRESSED wire bytes only — an
                # overlap-only step's fp32 exchange must not inflate it
                if self._comm_ctr is None:
                    self._comm_ctr = _m.counter(
                        "paddle_tpu_mesh_comm_compressed_bytes_total")
                self._comm_ctr.inc(rt.get("compressed_bytes", 0))
            if _m.trace._state.on:
                # the census attrs come from what collective_counts() /
                # collective_bytes() have ALREADY cached (called outside
                # a step): a step never lowers or compiles the program
                # to fill them
                attrs = {"dp": dp, "step": self._steps,
                         "zero": self.shard_optimizer}
                attrs.update(self._collectives or {})
                for coll, row in (self._collective_bytes or {}).items():
                    attrs[f"{coll}_bytes"] = row["bytes"]
                _m.trace.record_span("comm.mesh_step", t0, t1, attrs=attrs)
                if rt:
                    _m.trace.record_span(
                        "comm.bucket_reduce", t0, t1,
                        attrs={"buckets": rt.get("bucket_count", 0),
                               "compression": rt.get("compression",
                                                     "none"),
                               "overlap": rt.get("overlap", False),
                               "compressed_bytes":
                                   rt.get("compressed_bytes", 0),
                               "uncompressed_bytes":
                                   rt.get("uncompressed_bytes", 0)})
        return Tensor(loss)

    def set_state(self, pv, av, mv, rv=None):
        """Replace the step's donated state lists (params / accumulators /
        masters / error-feedback residuals) — the warm-restart hook: the
        compiled program and its shardings survive, only the VALUES
        change. Callers (the checkpoint restore path) must hand back
        arrays already placed with the same mesh shardings
        ``state_fn()`` committed, or the next step pays a one-time
        layout recompile. ``rv`` is required iff the step carries
        error-feedback residuals."""
        if (len(pv) != len(self._pv)
                or [len(r) for r in av] != [len(r) for r in self._av]
                or len(mv) != len(self._mv)):
            raise ValueError(
                "set_state: structure mismatch with the live step state")
        if (self._rv is None) != (rv is None) or (
                rv is not None and len(rv) != len(self._rv)):
            raise ValueError(
                "set_state: residual-state mismatch with the live step "
                "(error-feedback residuals are part of train state)")
        self._pv, self._av, self._mv = list(pv), [list(r) for r in av], \
            list(mv)
        if rv is not None:
            self._rv = list(rv)

    def finalize(self):
        """Write the trained values back onto the live Parameter/Optimizer
        objects (the step donated their original buffers)."""
        for p, v in zip(self.params, self._pv):
            p._replace_value(v)
        for p, ks, row, sh in zip(self.params, self._acc_keys, self._av,
                                  self.meta["acc_sharded"]):
            for k, v, s in zip(ks, row, sh):
                if s:
                    n = int(np.prod(p.shape)) if tuple(p.shape) else 1
                    v = jnp.asarray(v).reshape(-1)[:n].reshape(tuple(p.shape))
                self.optimizer._accumulators[id(p)][k] = v
        if self.meta["use_masters"]:
            for p, v in zip(self.params, self._mv):
                if self.shard_optimizer:
                    n = int(np.prod(p.shape)) if tuple(p.shape) else 1
                    v = jnp.asarray(v).reshape(-1)[:n].reshape(tuple(p.shape))
                self.optimizer._master_weights[id(p)] = v
        return self.model


def _resolve_remat(model, optimizer, loss_fn, ctx, batch, policy, budget,
                   shard_optimizer):
    """Resolve a ``recompute_policy`` into applied per-layer remat flags
    and a plan dict (stamped into ``meta['remat_plan']`` and bench
    provenance). ``"none"``/``"all"`` are the legacy endpoints of the
    old boolean; ``"budget"`` runs the graftopt planner against the
    declared HBM headroom (``hbm_budget``, falling back to the
    flagship ``budgets.json`` row for ``mesh.train_step``)."""
    import logging

    from ..analysis.jaxpr import planner as _planner

    candidates = _planner.remat_candidates(model)
    if policy in ("none", "all"):
        sites = range(len(candidates)) if policy == "all" else ()
        names = _planner.apply_remat_plan(candidates, sites)
        plan = {"policy": policy, "sites": names,
                "site_indices": sorted(sites),
                "n_candidates": len(candidates),
                "program": "mesh.train_step"}
    elif policy == "budget":
        if budget is None:
            from ..analysis.jaxpr import load_budgets

            budget = load_budgets().get("mesh.train_step")
        if budget is None:
            raise ValueError(
                "recompute_policy='budget' needs a budget: pass "
                "config={'hbm_budget': bytes} or declare a "
                "mesh.train_step row in analysis/jaxpr/budgets.json")
        plan = _planner.plan_for_mesh_step(
            model, optimizer, loss_fn, ctx, batch, budget,
            shard_optimizer=shard_optimizer)
    else:
        raise ValueError(
            f"unknown recompute_policy {policy!r} "
            "(expected 'none', 'all' or 'budget')")
    logging.getLogger("paddle_tpu.graftopt").info(
        "remat plan (%s): %d/%d site(s) %s, planned peak %s bytes",
        plan["policy"], len(plan["sites"]), plan["n_candidates"],
        plan["sites"], plan.get("planned_peak_bytes", "n/a"))
    return plan


def parallelize(model, optimizer, loss_fn, batch, mesh=None, config=None):
    """Lower a fleet-style hybrid config onto mesh axes and return a
    :class:`MeshParallel` step.

    ``config`` keys (the fleet ``hybrid_configs`` vocabulary):
    ``dp_degree`` (default: all visible devices), ``mp_degree`` (default 1 —
    >1 requires the model to be built with the fleet TP layers under an
    initialized hybrid topology), ``shard_optimizer`` (ZeRO-1 knob, default
    False), ``recompute_policy`` (``'none'`` / ``'all'`` / ``'budget'`` —
    the budget planner replaces the all-or-nothing per-layer
    ``recompute()``; defaults to the model config's own
    ``recompute_policy`` when it declares one) and ``hbm_budget`` (bytes
    of per-device HBM the ``'budget'`` policy plans against; defaults to
    the model config's ``hbm_budget``, then the ``mesh.train_step``
    budgets.json row).

    Communication-efficiency knobs (docs/distributed.md "Communication
    efficiency"; all default to the legacy bit-exact exchange):
    ``grad_compression`` (``'none'`` / ``'int8'`` / ``'fp8'`` —
    quantized grad reduction with per-bucket scales),
    ``error_feedback`` (default True: quantization error carried as
    extra donated residual state, added back before the next quantize —
    residuals ride MeshTrainer checkpoints), ``overlap_grad_comm``
    (bucketed grad collectives fired in reverse-autodiff completion
    order so XLA overlaps comm with the remaining backward compute) and
    ``bucket_bytes`` (bucket size target, default 1 MiB).

    An explicit ``mesh`` (MeshContext) overrides the
    degrees; when fleet is initialized and no mesh/config pins the
    degrees, the fleet topology is adopted.
    """
    config = dict(config or {})
    shard_opt = bool(config.pop("shard_optimizer", False))
    comm = comm_opt.CommOptConfig.from_config(config)
    model_cfg = getattr(model, "config", None)
    policy = config.pop("recompute_policy",
                        getattr(model_cfg, "recompute_policy", None))
    budget = config.pop("hbm_budget",
                        getattr(model_cfg, "hbm_budget", None))
    if mesh is None:
        dp = config.get("dp_degree")
        mp = int(config.get("mp_degree", 1))
        from ..distributed.fleet.topology import get_hybrid_parallel_group

        hcg = get_hybrid_parallel_group()
        if dp is None and hcg is not None:
            mesh = MeshContext.from_fleet(hcg)
        else:
            if dp is None:
                dp = max(1, jax.device_count() // mp)
            mesh = MeshContext.from_degrees(dp=int(dp), mp=mp)
    return MeshParallel(model, optimizer, loss_fn, mesh, batch,
                        shard_optimizer=shard_opt,
                        recompute_policy=policy, hbm_budget=budget,
                        comm=comm)
