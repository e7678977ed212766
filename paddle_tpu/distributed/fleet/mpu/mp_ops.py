"""Tensor-parallel communication primitives.

Reference analog: python/paddle/distributed/fleet/layers/mpu/mp_ops.py (_c_identity,
_c_concat, _c_split, _mp_allreduce, _parallel_linear, split) — hand-written collective
ops with custom forward/backward pairs (identity-fwd/allreduce-bwd etc.).

TPU-first redesign: in a GSPMD world these become SHARDING TRANSITIONS on global tensors,
and the backward collective is the transpose XLA derives automatically:
  _c_identity   = constrain replicated   (bwd: psum over mp — GSPMD inserts it)
  _c_split      = constrain Shard(last)  (bwd: all-gather)
  _c_concat     = constrain replicated from Shard(last) (fwd all-gather, bwd slice)
  _mp_allreduce = materialize a partial as replicated (fwd psum, bwd identity)
The helpers work identically in eager (device_put) and inside a jit trace
(lax.with_sharding_constraint), so the same layer code serves both modes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ....framework.core import Tensor
from ....ops._apply import apply_raw
from ...process_mesh import ProcessMesh
from ..topology import get_hybrid_parallel_group


def _mp_mesh_axis(group=None):
    """(jax Mesh, axis name) for the model-parallel axis of the active topology."""
    hcg = get_hybrid_parallel_group()
    if hcg is not None:
        return hcg.global_mesh.jax_mesh(), "mp"
    # no fleet topology: treat the whole device space as one mp axis
    import numpy as np

    mesh = ProcessMesh(np.arange(jax.device_count()), ["mp"])
    return mesh.jax_mesh(), "mp"


def _constrain(v, mesh, spec):
    """Apply a sharding constraint: device_put in eager, with_sharding_constraint traced.

    Inside a shard_map body (e.g. TP layers running within the compiled pipeline's
    manual pp axis) the constraint must be expressed on the context's abstract mesh —
    whose axis types mark the manual axes — with manual axes dropped from the spec;
    a constraint over the concrete mesh would type pp as Auto and fail vma checks."""
    if isinstance(v, jax.core.Tracer):
        am = jax.sharding.get_abstract_mesh()
        manual = set(am.manual_axes)
        if manual:
            cleaned = []
            for entry in tuple(spec):
                if isinstance(entry, (tuple, list)):
                    kept = tuple(a for a in entry if a not in manual)
                    cleaned.append(kept if kept else None)
                else:
                    cleaned.append(None if entry in manual else entry)
            return jax.lax.with_sharding_constraint(
                v, NamedSharding(am, P(*cleaned)))
        return jax.lax.with_sharding_constraint(v, NamedSharding(mesh, spec))
    return jax.device_put(v, NamedSharding(mesh, spec))


def _spec_last_dim(axis, ndim):
    return P(*([None] * (ndim - 1) + [axis]))


def _spec_dim(axis, dim, ndim):
    entries = [None] * ndim
    entries[dim] = axis
    return P(*entries)


def _c_identity(tensor, group=None, skip_c_identity_dynamic=False):
    """Forward identity, backward all-reduce over mp (mp_ops.py _c_identity)."""
    mesh, axis = _mp_mesh_axis(group)

    def fn(v):
        return _constrain(v, mesh, P())

    return apply_raw("c_identity", fn, [tensor])[0]


def _mp_allreduce(tensor, op=None, group=None, use_calc_stream=True,
                  use_model_parallel=True):
    """Forward all-reduce (materialize partial as replicated), backward identity."""
    mesh, axis = _mp_mesh_axis(group)

    def fn(v):
        return _constrain(v, mesh, P())

    return apply_raw("mp_allreduce_sum", fn, [tensor])[0]


def _c_split(tensor, group=None):
    """Keep only this mp-rank's slice of the last dim = constrain Shard(last)."""
    mesh, axis = _mp_mesh_axis(group)

    def fn(v):
        return _constrain(v, mesh, _spec_last_dim(axis, v.ndim))

    return apply_raw("c_split", fn, [tensor])[0]


def _c_concat(tensor, group=None):
    """All-gather the mp-sharded last dim back to a replicated tensor."""
    mesh, axis = _mp_mesh_axis(group)

    def fn(v):
        return _constrain(v, mesh, P())

    return apply_raw("c_concat", fn, [tensor])[0]


def mark_sharded(tensor, dim=-1, group=None, mesh_axis="mp"):
    """Constrain `tensor` to be sharded on `dim` over the given mesh axis."""
    hcg = get_hybrid_parallel_group()
    if hcg is not None:
        mesh = hcg.global_mesh.jax_mesh()
    else:
        mesh, mesh_axis = _mp_mesh_axis(group)

    def fn(v):
        d = dim if dim >= 0 else v.ndim + dim
        return _constrain(v, mesh, _spec_dim(mesh_axis, d, v.ndim))

    return apply_raw("shard_constraint", fn, [tensor])[0]


def mark_replicated(tensor, group=None):
    mesh, _ = _mp_mesh_axis(group)

    def fn(v):
        return _constrain(v, mesh, P())

    return apply_raw("replicate_constraint", fn, [tensor])[0]


def _parallel_linear(x, num_rows, num_cols, axis, param_attr, bias_attr, gather_out,
                     inner_rank, nranks, split_tensor, name, group=None):
    """paddle.distributed.split's linear branch: build a Row/ColumnParallelLinear."""
    from .mp_layers import ColumnParallelLinear, RowParallelLinear

    if axis == 0:
        layer = RowParallelLinear(
            num_rows, num_cols, weight_attr=param_attr, has_bias=bias_attr is not False,
            input_is_parallel=split_tensor, name=name)
    else:
        layer = ColumnParallelLinear(
            num_rows, num_cols, weight_attr=param_attr, has_bias=bias_attr is not False,
            gather_output=gather_out, name=name)
    return layer(x)


def split(x, size, operation, axis=0, num_partitions=1, gather_out=True,
          weight_attr=None, bias_attr=None, name=None):
    """paddle.distributed.split (mp_ops.py split): parallel embedding or linear."""
    from .mp_layers import VocabParallelEmbedding

    if operation == "embedding":
        layer = VocabParallelEmbedding(size[0], size[1], weight_attr=weight_attr,
                                       name=name)
        return layer(x)
    if operation == "linear":
        return _parallel_linear(x, size[0], size[1], axis, weight_attr, bias_attr,
                                gather_out, 0, num_partitions, False, name)
    raise ValueError(f"unsupported split operation {operation!r}")
