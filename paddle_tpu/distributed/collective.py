"""Eager collective communication API + process groups.

Reference analog: the ProcessGroup interface (phi/core/distributed/collective/
process_group.h:48 — AllGather/AllReduce/AllToAll/Broadcast/Reduce/ReduceScatter/Scatter/
Send/Recv with async Task handles) and python/paddle/distributed/communication/*.

TPU-first redesign: there is no NCCL and no per-rank process making its own call — the
framework is single-controller SPMD. A "rank's local tensor" is one row of a globally
addressable array stacked on axis 0 and sharded over the group's devices, so every
collective is a tiny XLA program over that array and the compiler lays the data movement
onto ICI. The same ops run inside `shard_map`-captured code via `paddle_tpu.distributed.
in_jit` (lax.psum & co.), which is the path compiled training steps use. Under real
multi-host, the stacked array spans hosts (jax.make_array_from_process_local_data) and the
same code runs unchanged over ICI+DCN.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..framework.core import Tensor
from . import watchdog as _watchdog


class ReduceOp:
    SUM = 0
    MAX = 1
    MIN = 2
    PROD = 3
    AVG = 4


_REDUCE_FNS = {
    ReduceOp.SUM: lambda v, axis: v.sum(axis=axis),
    ReduceOp.MAX: lambda v, axis: v.max(axis=axis),
    ReduceOp.MIN: lambda v, axis: v.min(axis=axis),
    ReduceOp.PROD: lambda v, axis: v.prod(axis=axis),
    ReduceOp.AVG: lambda v, axis: v.mean(axis=axis),
}


class Group:
    """A communication group: an ordered set of global device ids."""

    def __init__(self, ranks, gid=0, name=None):
        self.ranks = list(int(r) for r in ranks)
        self.id = gid
        self.name = name or f"group_{gid}"
        self._mesh = None

    @property
    def nranks(self):
        return len(self.ranks)

    @property
    def world_size(self):
        return len(self.ranks)

    @property
    def process_group(self):
        return self

    def get_group_rank(self, rank):
        return self.ranks.index(rank) if rank in self.ranks else -1

    def jax_mesh(self):
        if self._mesh is None:
            devices = jax.devices()
            self._mesh = Mesh(
                np.array([devices[r] for r in self.ranks]), axis_names=("g",)
            )
        return self._mesh

    def __repr__(self):
        return f"Group(id={self.id}, ranks={self.ranks})"


_GROUPS = {}
_GROUP_COUNTER = [0]
_DEFAULT_GROUP = [None]


def _world_group():
    if _DEFAULT_GROUP[0] is None:
        _DEFAULT_GROUP[0] = Group(range(jax.device_count()), gid=0, name="world")
        _GROUPS[0] = _DEFAULT_GROUP[0]
    return _DEFAULT_GROUP[0]


def new_group(ranks=None, backend=None, timeout=None):
    """paddle.distributed.new_group (python/paddle/distributed/collective.py)."""
    if ranks is None:
        ranks = list(range(jax.device_count()))
    _GROUP_COUNTER[0] += 1
    g = Group(ranks, gid=_GROUP_COUNTER[0])
    _GROUPS[g.id] = g
    return g


def get_group(gid=0):
    if gid == 0:
        return _world_group()
    return _GROUPS.get(gid)


def destroy_process_group(group=None):
    if group is None:
        _GROUPS.clear()
        _DEFAULT_GROUP[0] = None
    else:
        _GROUPS.pop(group.id, None)


def _resolve_group(group):
    return group if group is not None else _world_group()


def _val(t):
    return t.value if isinstance(t, Tensor) else jnp.asarray(t)


def _stacked_sharding(group):
    return NamedSharding(group.jax_mesh(), P("g"))


def _shard_stacked(v, group):
    """Lay the per-rank stacked array [n, ...] one row per group device."""
    return jax.device_put(v, _stacked_sharding(group))


def stack_locals(tensors_or_arrays, group=None):
    """Build the stacked per-rank representation from a list of local tensors."""
    group = _resolve_group(group)
    vals = [_val(t) for t in tensors_or_arrays]
    return Tensor(_shard_stacked(jnp.stack(vals), group))


def unstack_locals(t, group=None):
    group = _resolve_group(group)
    v = _val(t)
    return [Tensor(v[i]) for i in range(v.shape[0])]


class _Task:
    """Async collective handle (process_group.h:48 Task contract).

    XLA dispatch is already asynchronous: the returned arrays are futures the
    runtime fills in. wait() blocks on device completion; is_completed() polls
    the buffer's ready state without blocking."""

    def __init__(self, result=None):
        self._result = result

    def wait(self, timeout=None):
        if self._result is None:
            return None
        if timeout is None:
            jax.block_until_ready(self._result)
            return self._result
        import time as _time

        deadline = _time.monotonic() + timeout
        while not self.is_completed():
            if _time.monotonic() > deadline:
                raise TimeoutError(
                    f"collective result not ready within {timeout}s")
            _time.sleep(0.001)
        jax.block_until_ready(self._result)  # ready: returns immediately
        return self._result

    def is_completed(self):
        r = self._result
        if r is None:
            return True
        ready = getattr(r, "is_ready", None)
        return bool(ready()) if callable(ready) else True

    def synchronize(self):
        self.wait()


def _maybe_inplace(tensor, new_val, sync_op=True):
    if isinstance(tensor, Tensor):
        tensor._replace_value(new_val)
    return _Task(new_val) if not sync_op else None


# ---------------------------------------------------------------------------
# Collectives over stacked per-rank tensors ([world, ...] with row i = rank i's
# local view). Each one dispatches a REAL jax.lax collective: the stacked array
# is shard_map'd over the group mesh (one row per device) and the body runs
# psum / pmax / pmin / pmean / psum_scatter / all_gather / all_to_all — XLA
# lays the exchange onto ICI exactly like the compiled-training path
# (distributed/in_jit.py). Rows whose leading dim does not match the group (or
# degenerate scalar rows) fall back to the equivalent local math — silently:
# only dispatches that really ran a collective program are counted in
# paddle_tpu_comm_collectives_total{op} and spanned as comm.collective.
# ---------------------------------------------------------------------------
_COMM_MON = None  # (monitor module, collectives counter) — lazy hot-path bind


def _comm_mon():
    global _COMM_MON
    if _COMM_MON is None:
        from .. import monitor as _m

        _COMM_MON = (_m, _m.counter("paddle_tpu_comm_collectives_total",
                                    labelnames=("op",)))
    return _COMM_MON


class _comm_span:
    """comm.collective span + collective counter around one eager dispatch
    (zero-cost when monitor and trace are both off). ``ready=False`` (the
    degenerate local-math fallback) records nothing — the census counts only
    ops that really dispatched a collective program."""

    __slots__ = ("op", "group", "t0")

    def __init__(self, op, group, ready=True):
        self.op = op
        self.group = group if ready else None

    def __enter__(self):
        if self.group is None:
            self.t0 = 0
            return self
        m, _ = _comm_mon()
        self.t0 = m.now_ns() if (m._state.on or m.trace._state.on) else 0
        return self

    def __exit__(self, *exc):
        if not self.t0:
            return False
        m, ctr = _comm_mon()
        t1 = m.now_ns()
        if m._state.on:
            ctr.labels(self.op).inc()
        if m.trace._state.on:
            m.trace.record_span(
                "comm.collective", self.t0, t1,
                attrs={"op": self.op, "group": self.group.name,
                       "nranks": self.group.nranks})
        return False


def _group_program(group, key, builder):
    """One jitted shard_map program per (group, collective signature); jax's
    own jit cache handles per-shape/dtype specialization underneath. When a
    process-wide watchdog is installed (``distributed.watchdog.
    set_default_watchdog`` — the mesh trainer's hang-recovery companion),
    the returned callable runs inside a watched, execution-fenced section:
    the block_until_ready is what lets the scanner OBSERVE a hung
    collective, and it is only paid while a watchdog is armed."""
    progs = group.__dict__.setdefault("_programs", {})
    fn = progs.get(key)
    if fn is None:
        fn = jax.jit(shard_map(builder, mesh=group.jax_mesh(),
                               in_specs=P("g"), out_specs=P("g")))
        progs[key] = fn
    dog = _watchdog._DEFAULT[0]
    if dog is None:
        return fn

    def watched(*args):
        with dog.watch(f"comm.{key[0]}[{group.name}]"):
            out = fn(*args)
            jax.block_until_ready(out)
        return out

    return watched


def _collective_ready(v, group):
    """The stacked layout a real collective needs: one row per group device."""
    return (v.ndim >= 1 and v.shape[0] == group.nranks
            and group.nranks <= jax.device_count())


_LAX_REDUCERS = {
    ReduceOp.SUM: lambda x: lax.psum(x, "g"),
    ReduceOp.MAX: lambda x: lax.pmax(x, "g"),
    ReduceOp.MIN: lambda x: lax.pmin(x, "g"),
    ReduceOp.AVG: lambda x: lax.pmean(x, "g"),
}


def _body_reduce(op, dtype):
    """Reduction of the (1, ...) local row across the group axis, staying
    (1, ...). PROD (no lax primitive) and bool SUM/AVG ride a REAL all-gather
    then reduce rows locally — same wire traffic, exact local-math
    semantics."""
    fn = _LAX_REDUCERS.get(op)
    if fn is not None and not (np.dtype(dtype) == np.bool_
                               and op in (ReduceOp.SUM, ReduceOp.AVG)):
        return fn

    def gather_reduce(x):
        rows = lax.all_gather(x, "g", axis=0, tiled=True)  # (n, ...)
        return _REDUCE_FNS[op](rows, 0)[None]

    return gather_reduce


def _body_reduce_quantized(op, nranks, mode):
    """Quantized all-reduce body (EQuARX-style, mesh/comm_opt.py): the
    local row is blocked into per-destination slices, grid-projected and
    wire-cast to 1 byte/element, exchanged with all_to_all + scales,
    dequant-summed locally, then the reduced slice is requantized and
    all_gathered — both wire legs at 1/4 the fp32 payload."""
    from ..mesh import comm_opt

    def body(x):
        row = x[0]
        # blockify = the ONE (degree, k) destination-row layout rule the
        # mesh exchange uses (zero.padded_slice_len underneath)
        rows = comm_opt.blockify(row, nranks)
        slices, _dq, _wire = comm_opt.bucket_reduce(
            [rows], "g", nranks, mode, "full")
        red = comm_opt.unblockify(slices[0], row.shape)
        if op == ReduceOp.SUM:
            red = red * nranks      # bucket_reduce returns the MEAN
        return red.astype(x.dtype)[None]

    return body


def all_reduce(tensor, op=ReduceOp.SUM, group=None, sync_op=True,
               compression=None):
    """Rows of the stacked tensor are reduced; every rank sees the result.

    ``compression='int8'|'fp8'`` runs the quantized exchange (SUM/AVG of
    float rows only — other ops/dtypes fall back to the exact program);
    the result is approximate at ~1/4 the bytes-on-wire."""
    group = _resolve_group(group)
    v = _val(tensor)
    ready = _collective_ready(v, group)
    mode = "none"
    if (compression is not None and ready
            and op in (ReduceOp.SUM, ReduceOp.AVG)
            and jnp.issubdtype(v.dtype, jnp.floating)):
        from ..mesh import comm_opt

        mode = comm_opt.resolve_compression(str(compression))
    with _comm_span("all_reduce", group, ready):
        if ready and mode != "none":
            prog = _group_program(
                group, ("all_reduce_q", op, mode, str(v.dtype)),
                _body_reduce_quantized(op, group.nranks, mode))
            out = prog(_shard_stacked(v, group))
        elif ready:
            prog = _group_program(group, ("all_reduce", op, str(v.dtype)),
                                  _body_reduce(op, v.dtype))
            out = prog(_shard_stacked(v, group))
        else:
            red = _REDUCE_FNS[op](v, 0)
            out = _shard_stacked(jnp.broadcast_to(red[None], v.shape), group)
    return _maybe_inplace(tensor, out, sync_op)


def reduce(tensor, dst, op=ReduceOp.SUM, group=None, sync_op=True):
    group = _resolve_group(group)
    v = _val(tensor)
    dst_idx = group.get_group_rank(dst)
    if dst_idx < 0:
        raise ValueError(f"reduce dst rank {dst} is not in group {group.ranks}")
    ready = _collective_ready(v, group)
    with _comm_span("reduce", group, ready):
        if ready:
            reducer = _body_reduce(op, v.dtype)

            def body(x):
                red = reducer(x)
                idx = lax.axis_index("g")
                return jnp.where(idx == dst_idx, red.astype(x.dtype), x)

            prog = _group_program(group, ("reduce", op, dst_idx,
                                          str(v.dtype)), body)
            out = prog(_shard_stacked(v, group))
        else:
            red = _REDUCE_FNS[op](v, 0)
            out = _shard_stacked(v.at[dst_idx].set(red), group)
    return _maybe_inplace(tensor, out, sync_op)


def all_gather(tensor_list, tensor, group=None, sync_op=True):
    """Each rank's row is gathered; tensor_list receives the n rows (replicated)."""
    group = _resolve_group(group)
    v = _val(tensor)
    if isinstance(tensor_list, list):
        del tensor_list[:]
        for i in range(v.shape[0]):
            tensor_list.append(Tensor(v[i]))
    return _Task(v) if not sync_op else None


def all_gather_concat(tensor, group=None, axis=0):
    """Functional all-gather: stacked [n, ...] -> concatenated along `axis`, replicated."""
    group = _resolve_group(group)
    v = _val(tensor)
    ready = _collective_ready(v, group) and v.ndim >= 2
    with _comm_span("all_gather", group, ready):
        if ready:

            def body(x):
                # x: (1, row...); gather the rows concatenated along `axis`
                return lax.all_gather(x[0], "g", axis=axis, tiled=True)[None]

            prog = _group_program(group, ("all_gather_concat", axis), body)
            out = prog(_shard_stacked(v, group))
        else:
            parts = [v[i] for i in range(v.shape[0])]
            cat = jnp.concatenate(parts, axis=axis)
            out = _shard_stacked(
                jnp.broadcast_to(cat[None], (v.shape[0],) + cat.shape), group)
    return Tensor(out)


def broadcast(tensor, src, group=None, sync_op=True):
    group = _resolve_group(group)
    v = _val(tensor)
    src_idx = group.get_group_rank(src)
    if src_idx < 0:
        raise ValueError(f"broadcast src rank {src} is not in group {group.ranks}")
    ready = _collective_ready(v, group)
    with _comm_span("broadcast", group, ready):
        if ready:

            def body(x):
                rows = lax.all_gather(x, "g", axis=0, tiled=True)  # (n, ...)
                return rows[src_idx][None]

            prog = _group_program(group, ("broadcast", src_idx), body)
            out = prog(_shard_stacked(v, group))
        else:
            out = _shard_stacked(
                jnp.broadcast_to(v[src_idx][None], v.shape), group)
    return _maybe_inplace(tensor, out, sync_op)


def scatter(tensor, tensor_list=None, src=0, group=None, sync_op=True):
    """src's list of n tensors scattered: rank i receives tensor_list[i]."""
    group = _resolve_group(group)
    if tensor_list is not None:
        vals = jnp.stack([_val(t) for t in tensor_list])
        out = _shard_stacked(vals, group)
        return _maybe_inplace(tensor, out, sync_op)
    v = _val(tensor)
    return _maybe_inplace(tensor, _shard_stacked(v, group), sync_op)


def reduce_scatter(tensor, tensor_or_tensor_list, op=ReduceOp.SUM, group=None, sync_op=True):
    """Reduce rows then scatter slices: rank i gets slice i of the reduction."""
    group = _resolve_group(group)
    src = tensor_or_tensor_list
    n = group.nranks
    if isinstance(src, (list, tuple)):
        v = jnp.stack([jnp.stack([_val(t) for t in src])] * len(src))  # replicated input
    else:
        v = _val(src)  # [n, n*chunk, ...] or [n, n, chunk...]
    ready = (_collective_ready(v, group) and v.ndim >= 2
             and v.shape[1] % n == 0)
    with _comm_span("reduce_scatter", group, ready):
        if ready:
            row_len = v.shape[1]

            def body(x):
                # x: (1, row...); for SUM a native reduce-scatter moves 1/n of
                # the reduction to each member (bool can't psum: it rides the
                # gather path like _body_reduce); other ops gather + reduce +
                # slice (the portable-redistribution fallback)
                if op == ReduceOp.SUM and np.dtype(v.dtype) != np.bool_:
                    sl = lax.psum_scatter(x[0], "g", scatter_dimension=0,
                                          tiled=True)
                else:
                    rows = lax.all_gather(x, "g", axis=0, tiled=True)
                    red = _REDUCE_FNS[op](rows, 0)
                    idx = lax.axis_index("g")
                    sl = lax.dynamic_slice_in_dim(
                        red, idx * (row_len // n), row_len // n, axis=0)
                if row_len == n:
                    sl = sl[0]  # [n, chunk...] rows: member i takes row i
                return sl[None]

            prog = _group_program(group, ("reduce_scatter", op, row_len,
                                          str(v.dtype)), body)
            out = prog(_shard_stacked(v, group))
        else:
            red = _REDUCE_FNS[op](v, 0)
            if red.shape[0] == n:
                out = red  # already [n, chunk...] — row i to rank i
            else:
                out = red.reshape((n, red.shape[0] // n) + red.shape[1:])
            out = _shard_stacked(out, group)
    return _maybe_inplace(tensor, out, sync_op)


def alltoall(out_tensor_list, in_tensor_list, group=None, sync_op=True):
    """in_tensor_list[i][j] row goes to rank j position i: a block transpose."""
    group = _resolve_group(group)
    if isinstance(in_tensor_list, (list, tuple)):
        v = jnp.stack([_val(t) for t in in_tensor_list])
    else:
        v = _val(in_tensor_list)
    n = group.nranks
    ready = (_collective_ready(v, group) and v.ndim >= 2
             and v.shape[1] % n == 0)
    with _comm_span("alltoall", group, ready):
        if ready:

            def body(x):
                # x: (1, n*chunk, ...); lax.all_to_all tiled sends chunk j of
                # this member's row to member j and concatenates the received
                # chunks — the block transpose, on the wire
                return lax.all_to_all(x[0], "g", split_axis=0, concat_axis=0,
                                      tiled=True)[None]

            prog = _group_program(group, ("alltoall", v.shape[1]), body)
            out = prog(_shard_stacked(v, group))
        elif v.ndim >= 2 and v.shape[0] == n and v.shape[1] == n:
            # v: [n_src, n_dst, ...] rows of per-dst chunks -> transpose
            out = _shard_stacked(jnp.swapaxes(v, 0, 1), group)
        else:
            # [n, n*chunk, ...] split-concat form (alltoall_single)
            chunk = v.shape[1] // n
            out = _shard_stacked(
                v.reshape((n, n, chunk) + v.shape[2:])
                .swapaxes(0, 1)
                .reshape((n, n * chunk) + v.shape[2:]), group)
    if isinstance(out_tensor_list, list):
        del out_tensor_list[:]
        for i in range(n):
            out_tensor_list.append(Tensor(out[i]))
        return None
    return _maybe_inplace(out_tensor_list, out, sync_op)


def alltoall_single(out_tensor, in_tensor, in_split_sizes=None, out_split_sizes=None,
                    group=None, sync_op=True):
    group = _resolve_group(group)
    if in_split_sizes is None and out_split_sizes is None:
        return alltoall(out_tensor, in_tensor, group=group, sync_op=sync_op)
    # uneven splits: rank i's row is cut by in_split_sizes; chunk j goes to rank j;
    # rank j's output row is the concat of chunk j from every rank
    v = _val(in_tensor)
    n = group.nranks
    sizes = list(in_split_sizes)
    if len(sizes) != n or sum(sizes) != v.shape[1]:
        raise ValueError(
            f"in_split_sizes {sizes} must have {n} entries summing to {v.shape[1]}"
        )
    offsets = np.cumsum([0] + sizes)
    rows = []
    for j in range(n):
        chunks = [v[i, offsets[j]:offsets[j + 1]] for i in range(n)]
        rows.append(jnp.concatenate(chunks, axis=0))
    widths = {r.shape[0] for r in rows}
    if len(widths) != 1:
        raise ValueError(
            "uneven out row sizes need equal per-rank totals in this stacked "
            f"representation; got {[r.shape[0] for r in rows]}"
        )
    out = _shard_stacked(jnp.stack(rows), group)
    return _maybe_inplace(out_tensor, out, sync_op)


# Single-controller P2P: channels keyed by (src, dst). The caller states which rank it is
# acting as via `p2p_rank(r)` — the PP schedule emulation wraps each simulated rank's slice
# of the schedule in that context. Real multi-host P2P rides collective_permute inside
# compiled steps (distributed.in_jit.shift / ppermute).
_P2P_CHANNEL = {}
_CURRENT_P2P_RANK = [0]


class p2p_rank:
    """Context manager declaring which rank the enclosed send/recv calls act as."""

    def __init__(self, rank):
        self.rank = int(rank)

    def __enter__(self):
        self.prev = _CURRENT_P2P_RANK[0]
        _CURRENT_P2P_RANK[0] = self.rank
        return self

    def __exit__(self, *exc):
        _CURRENT_P2P_RANK[0] = self.prev
        return False


def send(tensor, dst=0, group=None, sync_op=True):
    """P2P: stage the tensor on dst's device (single-controller: a device_put)."""
    group = _resolve_group(group)
    v = _val(tensor)
    g_dst = group.ranks[group.get_group_rank(dst)] if dst in group.ranks else dst
    src = _CURRENT_P2P_RANK[0]
    _P2P_CHANNEL.setdefault((src, g_dst), []).append(
        jax.device_put(v, jax.devices()[g_dst])
    )
    return _Task() if not sync_op else None


def recv(tensor, src=0, group=None, sync_op=True):
    group = _resolve_group(group)
    g_src = group.ranks[group.get_group_rank(src)] if src in group.ranks else src
    chan = _P2P_CHANNEL.get((g_src, _CURRENT_P2P_RANK[0]))
    if not chan:
        raise RuntimeError(
            f"recv(src={g_src}) as rank {_CURRENT_P2P_RANK[0]} with empty channel: "
            "single-controller P2P requires the matching send first (see p2p_rank)"
        )
    v = chan.pop(0)
    return _maybe_inplace(tensor, v, sync_op)


def barrier(group=None):
    """Block until all outstanding device work is flushed."""
    jax.block_until_ready(jax.live_arrays())
    return None


def wait(tensor, group=None, use_calc_stream=True):
    v = _val(tensor)
    jax.block_until_ready(v)


# ---------------------------------------------------------------------------
# Object collectives (host-side; DCN in real deployments)
# ---------------------------------------------------------------------------
_OBJECT_STORE = {}


def all_gather_object(object_list, obj, group=None):
    group = _resolve_group(group)
    del object_list[:]
    object_list.extend([obj] * group.nranks)


def broadcast_object_list(object_list, src=0, group=None):
    return object_list


def gather(tensor, gather_list=None, dst=0, group=None, sync_op=True):
    """Rows gathered to dst (communication/gather.py): dst's gather_list gets
    every rank's row; other ranks' lists are left empty. Single-controller
    stacked-axis semantics: all rows are visible, dst filtering is logical."""
    group = _resolve_group(group)
    v = _val(tensor)
    if isinstance(gather_list, list):
        del gather_list[:]
        for i in range(v.shape[0]):
            gather_list.append(Tensor(v[i]))
    return _Task(v) if not sync_op else None


def get_backend(group=None):
    """communication/group.py get_backend: the collective transport name."""
    import jax

    try:
        platform = jax.devices()[0].platform
    except RuntimeError:
        platform = "cpu"
    return {"tpu": "XLA_ICI", "gpu": "NCCL"}.get(platform, "GLOO")


def isend(tensor, dst=0, group=None):
    """communication/send.py isend: async send returning a waitable Task."""
    return send(tensor, dst=dst, group=group, sync_op=False)


def irecv(tensor, src=0, group=None):
    """communication/recv.py irecv: async recv returning a waitable Task."""
    return recv(tensor, src=src, group=group, sync_op=False)


class P2POp:
    """communication/batch_isend_irecv.py P2POp: one queued p2p operation."""

    def __init__(self, op, tensor, peer, group=None):
        if op not in (isend, irecv, send, recv):
            raise ValueError(
                "op must be paddle.distributed.isend or paddle.distributed."
                "irecv")
        self.op = op
        self.tensor = tensor
        self.peer = peer
        self.group = group


def batch_isend_irecv(p2p_op_list):
    """communication/batch_isend_irecv.py: run queued p2p ops; sends first so
    the single-controller channel is populated before the matching recvs."""
    if not p2p_op_list:
        return []
    if not all(isinstance(p, P2POp) for p in p2p_op_list):
        raise ValueError("batch_isend_irecv expects a list of P2POp")
    # execute sends before recvs (the single-controller channel must be
    # populated first) but return tasks in INPUT order — the reference
    # contract is tasks[i] pairs with p2p_op_list[i]
    tasks = [None] * len(p2p_op_list)
    send_first = sorted(range(len(p2p_op_list)),
                        key=lambda i: p2p_op_list[i].op in (irecv, recv))
    for i in send_first:
        p = p2p_op_list[i]
        t = p.op(p.tensor, p.peer, group=p.group)
        tasks[i] = t if isinstance(t, _Task) else _Task()
    return tasks


def scatter_object_list(out_object_list, in_object_list=None, src=0,
                        group=None):
    """communication/scatter.py scatter_object_list: host-side object scatter
    (single-controller: rank src's list is authoritative)."""
    group = _resolve_group(group)
    rank = _CURRENT_P2P_RANK[0]
    key = ("scatter", id(group))
    if rank == src and in_object_list is not None:
        # only the src rank's list is authoritative (reference contract);
        # other ranks' in_object_list args are ignored
        _OBJECT_STORE[key] = list(in_object_list)
    if rank not in group.ranks:
        # non-member ranks don't participate: leave out_object_list
        # untouched (reference group-membership contract; previously this
        # silently handed rank 0's shard to outsiders)
        return
    data = _OBJECT_STORE.get(key, list(in_object_list or []))
    idx = group.get_group_rank(rank)
    out_object_list[:] = [data[idx]] if data else []
