"""The intermediate `parallelize` plan API + spawn + misc runtime names.

Reference analogs: python/paddle/distributed/auto_parallel/intermediate/
{parallelize,tensor_parallel,pipeline_parallel}.py (plan classes applied by
name pattern), auto_parallel/api.py set_mesh/get_mesh, and
python/paddle/distributed/spawn.py.

TPU-first: a plan is a sharding annotation. ColWise/RowWise mark the matched
layer's parameters Shard over the mesh's `mp` axis; SequenceParallel* mark
activations Shard on the sequence dim; GSPMD propagates everything else, so
"apply plan" is a handful of device_puts + forward hooks, not a graph pass.
"""
from __future__ import annotations

import fnmatch
import re
from enum import Enum

import numpy as np

import jax

from ..framework.core import Tensor
from ..nn.layer.layers import Layer
from . import api as dist_api
from .placement import Replicate, Shard
from .process_mesh import ProcessMesh

__all__ = ["set_mesh", "get_mesh", "parallelize", "parallelize_step",
           "ColWiseParallel",
           "RowWiseParallel", "SequenceParallelBegin", "SequenceParallelEnd",
           "SequenceParallelEnable", "SequenceParallelDisable",
           "PrepareLayerInput", "PrepareLayerOutput", "SplitPoint",
           "LocalLayer", "to_distributed", "spawn", "is_available"]

_GLOBAL_MESH = [None]


def set_mesh(mesh):
    """auto_parallel/api.py set_mesh: the global mesh parallelize() uses."""
    _GLOBAL_MESH[0] = mesh
    return mesh


def get_mesh():
    if _GLOBAL_MESH[0] is not None:
        return _GLOBAL_MESH[0]
    from .process_mesh import get_current_mesh

    return get_current_mesh()


def _default_mesh():
    if _GLOBAL_MESH[0] is not None:
        return _GLOBAL_MESH[0]
    n = jax.device_count()
    return ProcessMesh(np.arange(n).reshape(1, n), ["dp", "mp"])


def _axis_placements(mesh, axis_name, dim):
    placements = [Replicate()] * mesh.ndim
    if axis_name in mesh.dim_names:
        placements[mesh.dim_names.index(axis_name)] = Shard(dim)
    return placements


class PlanBase:
    def apply(self, layer, mesh, replaced=None):  # pragma: no cover
        raise NotImplementedError

    @staticmethod
    def _swap(layer, pname, new, replaced):
        old = layer._parameters[pname]
        layer._parameters[pname] = new
        if replaced is not None and old is not None:
            replaced[id(old)] = new


class ColWiseParallel(PlanBase):
    """tensor_parallel.py:103 — weight Shard(1), bias Shard(0) over mp."""

    def __init__(self, gather_output=False):
        self.gather_output = gather_output

    def apply(self, layer, mesh, replaced=None):
        for pname, p in list(layer._parameters.items()):
            if p is None:
                continue
            dim = 1 if p.ndim >= 2 else 0
            self._swap(layer, pname, dist_api.shard_tensor(
                p, mesh, _axis_placements(mesh, "mp", dim)), replaced)
        if self.gather_output:
            def gather_hook(lyr, inputs, outputs):
                return dist_api.reshard(
                    outputs, mesh, [Replicate()] * mesh.ndim) \
                    if isinstance(outputs, Tensor) else outputs

            layer.register_forward_post_hook(gather_hook)


class RowWiseParallel(PlanBase):
    """tensor_parallel.py:211 — weight Shard(0) over mp, bias replicated."""

    def __init__(self, is_input_parallel=True):
        self.is_input_parallel = is_input_parallel

    def apply(self, layer, mesh, replaced=None):
        for pname, p in list(layer._parameters.items()):
            if p is None:
                continue
            if p.ndim >= 2:
                self._swap(layer, pname, dist_api.shard_tensor(
                    p, mesh, _axis_placements(mesh, "mp", 0)), replaced)
            else:
                self._swap(layer, pname, dist_api.shard_tensor(
                    p, mesh, [Replicate()] * mesh.ndim), replaced)


class _SeqMark(PlanBase):
    _dim = 1  # (B, S, H): shard S over mp

    def _shard_seq(self, t, mesh):
        if isinstance(t, Tensor) and len(t.shape) >= 2:
            return dist_api.reshard(
                t, mesh, _axis_placements(mesh, "mp", self._dim))
        return t

    def _unshard_seq(self, t, mesh):
        if isinstance(t, Tensor):
            return dist_api.reshard(t, mesh, [Replicate()] * mesh.ndim)
        return t


class SequenceParallelBegin(_SeqMark):
    """tensor_parallel.py:418: outputs leave this layer seq-sharded."""

    def __init__(self, need_transpose=True):
        self.need_transpose = need_transpose

    def apply(self, layer, mesh, replaced=None):
        layer.register_forward_post_hook(
            lambda lyr, inputs, outputs: self._shard_seq(outputs, mesh))


class SequenceParallelEnd(_SeqMark):
    """tensor_parallel.py:470: inputs of this layer go back to whole."""

    def __init__(self, need_transpose=True):
        self.need_transpose = need_transpose

    def apply(self, layer, mesh, replaced=None):
        layer.register_forward_pre_hook(
            lambda lyr, inputs: tuple(self._unshard_seq(t, mesh)
                                      for t in inputs))


class SequenceParallelEnable(_SeqMark):
    """tensor_parallel.py:522: run this layer fully under seq-sharding."""

    def apply(self, layer, mesh, replaced=None):
        layer.register_forward_pre_hook(
            lambda lyr, inputs: tuple(self._shard_seq(t, mesh)
                                      for t in inputs))
        layer.register_forward_post_hook(
            lambda lyr, inputs, outputs: self._shard_seq(outputs, mesh))


class SequenceParallelDisable(_SeqMark):
    """tensor_parallel.py:579: run this layer on whole activations."""

    def __init__(self, need_transpose=True):
        self.need_transpose = need_transpose

    def apply(self, layer, mesh, replaced=None):
        layer.register_forward_pre_hook(
            lambda lyr, inputs: tuple(self._unshard_seq(t, mesh)
                                      for t in inputs))
        layer.register_forward_post_hook(
            lambda lyr, inputs, outputs: self._shard_seq(outputs, mesh))


class PrepareLayerInput(PlanBase):
    """tensor_parallel.py:308: run a user fn over the layer inputs."""

    def __init__(self, fn=None):
        self.fn = fn

    def apply(self, layer, mesh, replaced=None):
        if self.fn is not None:
            hook = self.fn(mesh)  # reference contract: fn(process_mesh)->hook
            layer.register_forward_pre_hook(hook)


class PrepareLayerOutput(PlanBase):
    """tensor_parallel.py:363: run a user fn over the layer outputs."""

    def __init__(self, fn=None):
        self.fn = fn

    def apply(self, layer, mesh, replaced=None):
        if self.fn is not None:
            hook = self.fn(mesh)
            layer.register_forward_post_hook(hook)


class SplitPoint(Enum):
    """pipeline_parallel.py:30 — where pp stages cut relative to the layer."""

    BEGINNING = 0
    END = 1


def _match(name, pattern):
    if name == pattern or fnmatch.fnmatch(name, pattern):
        return True
    try:
        return re.fullmatch(pattern.replace(".", r"\."), name) is not None
    except re.error:
        return False  # not a valid regex: fnmatch already said no


def parallelize(model, optimizer=None, mesh=None, config=None):
    """intermediate/parallelize.py:51 — apply dp/mp/pp config to a
    single-card model. mp plans are sharding annotations applied to matched
    sublayers; dp sharding_level installs the ZeRO state-placement hook;
    pp split points are recorded on the model (the compiled pipeline is the
    fleet path, distributed/pipelining.py)."""
    mesh = mesh or _default_mesh()
    config = config or {}

    mp_cfg = config.get("mp_config") or {}
    plan = mp_cfg.get("parallelize_plan") or {}
    applied = 0
    replaced = {}
    named = dict(model.named_sublayers(include_self=True))
    for pattern, plans in plan.items():
        plans = plans if isinstance(plans, (list, tuple)) else [plans]
        for name, sub in named.items():
            if _match(name, pattern):
                for p in plans:
                    p.apply(sub, mesh, replaced)
                    applied += 1
    model._parallelize_applied = applied
    if optimizer is not None and replaced:
        # an optimizer built before parallelize holds the old Parameter
        # objects: re-point param groups and any existing state (the same
        # contract as group_sharded stage-3)
        inner = getattr(optimizer, "inner_opt", optimizer)
        for pg in getattr(inner, "_param_groups", []):
            pg["params"] = [replaced.get(id(q), q) for q in pg["params"]]
        for attr in ("_accumulators", "_master_weights"):
            table = getattr(inner, attr, None)
            if table:
                for old_id, new in list(replaced.items()):
                    if old_id in table:
                        table[id(new)] = table.pop(old_id)

    dp_cfg = config.get("dp_config") or {}
    level = int(dp_cfg.get("sharding_level") or 0)
    if optimizer is not None and level >= 1 and "dp" in mesh.dim_names:
        from .fleet.hybrid_optimizer import _make_state_shard_fn

        inner = getattr(optimizer, "inner_opt", optimizer)
        inner._shard_fn = _make_state_shard_fn(
            mesh, mesh.dim_names.index("dp"),
            mesh.shape[mesh.dim_names.index("dp")])
        inner._is_dist = True

    pp_cfg = config.get("pp_config") or {}
    if pp_cfg.get("split_spec"):
        model._pp_split_spec = pp_cfg["split_spec"]

    return model, optimizer


class LocalLayer(Layer):
    """auto_parallel LocalLayer: forward runs on LOCAL shards; outputs are
    re-assembled as dist tensors with the declared placements."""

    def __init__(self, out_dist_attrs=None, grad_dist_attrs=None):
        super().__init__()
        self.out_dist_attrs = out_dist_attrs or []

    def __call__(self, *inputs, **kwargs):
        locals_ = [dist_api.local_value(t) if isinstance(t, Tensor)
                   and t._dist_attr is not None else t for t in inputs]
        out = super().__call__(*locals_, **kwargs)
        if self.out_dist_attrs:
            outs = out if isinstance(out, (tuple, list)) else [out]
            wrapped = []
            for o, (m, placements) in zip(outs, self.out_dist_attrs):
                wrapped.append(dist_api.dtensor_from_local(o, m, placements)
                               if isinstance(o, Tensor) else o)
            return wrapped[0] if not isinstance(out, (tuple, list)) \
                else type(out)(wrapped)
        return out


def to_distributed(model, optimizer, dataloader, device_num=None,
                   node_num=None, config=None):
    """auto_parallel to_distributed (the one-call entry): parallelize with
    the global mesh and return (model, optimizer, dataloader)."""
    model, optimizer = parallelize(model, optimizer, config=config)
    return model, optimizer, dataloader


def parallelize_step(model, optimizer, loss_fn, batch, mesh=None,
                     config=None):
    """The EXECUTION form of parallelize: lower the fleet hybrid config
    (dp_degree / mp_degree / shard_optimizer) onto mesh axes and return a
    ``paddle_tpu.mesh.MeshParallel`` handle whose ``step(*batch)`` runs the
    real train step under shard_map with donated sharded state
    (docs/distributed.md). ``parallelize`` above annotates a model's
    placements; this runs it."""
    from ..mesh import parallelize as _mesh_parallelize

    return _mesh_parallelize(model, optimizer, loss_fn, batch, mesh=mesh,
                             config=config)


def is_available():
    """communication/all_reduce.py is_available analog: the distributed
    runtime is always available (single-controller SPMD)."""
    return True


def spawn(func, args=(), nprocs=-1, join=True, daemon=False, **options):
    """distributed/spawn.py: launch func on nprocs processes with the
    launcher's env contract (PADDLE_TRAINER_ID/PADDLE_TRAINERS_NUM/
    PADDLE_MASTER), rendezvous through the TCPStore."""
    import multiprocessing as mp
    import os
    import socket

    if nprocs == -1:
        nprocs = int(os.environ.get("PADDLE_NPROCS", "2"))
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()

    # a chip belongs to ONE process: this parent already holds its backend,
    # so children that asked for the same accelerator would fail or hang.
    # The multi-process path is CPU-only; on chips one process drives every
    # device of the host through paddle_tpu.mesh
    plat = os.environ.get("PADDLE_TPU_PLATFORM") or jax.default_backend()
    if plat != "cpu":
        raise RuntimeError(
            f"spawn: this process holds the {plat!r} backend and a chip "
            "belongs to one process, so its children cannot use it. Run the "
            "multi-process path on the CPU (JAX_PLATFORMS=cpu), or drive all "
            "of the host's chips from one process with paddle_tpu.mesh")

    ctx = mp.get_context("spawn")
    procs = []
    for rank in range(nprocs):
        env = {
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_TRAINERS_NUM": str(nprocs),
            "PADDLE_LOCAL_RANK": str(rank),
            "PADDLE_MASTER": f"127.0.0.1:{port}",
            "MASTER_ADDR": "127.0.0.1",
            "MASTER_PORT": str(port),
        }
        env["PADDLE_TPU_PLATFORM"] = plat
        p = ctx.Process(target=_spawn_entry,
                        args=(func, args, env), daemon=daemon)
        # spawn children inherit the parent env captured at start(): set the
        # per-rank contract around each start
        saved = {k: os.environ.get(k) for k in env}
        os.environ.update(env)
        try:
            p.start()
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
        procs.append(p)
    if join:
        for p in procs:
            p.join()
        bad = [p.exitcode for p in procs if p.exitcode]
        if bad:
            raise RuntimeError(f"spawn: child exit codes {bad}")
    return procs


def _spawn_entry(func, args, env):
    import os

    os.environ.update(env)
    func(*args)
