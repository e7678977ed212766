"""paddle_tpu.mesh — real SPMD mesh execution (ISSUE 8).

Covers: MeshContext lowering + the placement->PartitionSpec mapping, the
per-op SPMD rule registry (propagation + explicit resharding only where
specs disagree), the mesh.collective fault drill, eager collectives backed
by real jax.lax programs, and the acceptance bars: DP=8 / ZeRO-1 training
of the mlp+llama step on the simulated 8-device mesh matching the
single-device run, with zero post-warmup recompiles under graftsan and
>= 1 real collective visible in comm.* spans.
"""
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import mesh as pmesh
from paddle_tpu import monitor
from paddle_tpu.distributed import api as dist_api
from paddle_tpu.distributed.placement import Partial, Replicate, Shard
from paddle_tpu.distributed.process_mesh import ProcessMesh
from paddle_tpu.framework import random as rng
from paddle_tpu.framework.core import Tensor
from paddle_tpu.monitor import trace


def _mlp():
    return paddle.nn.Sequential(
        paddle.nn.Linear(16, 32), paddle.nn.Tanh(),
        paddle.nn.Linear(32, 16))


def _mse(m, x, y):
    return ((m(x) - y) ** 2).mean()


def _build_step(model, optimizer, loss_fn):
    """The single-device reference: one donated, jitted train step (fwd + bwd
    + optimizer) with functional state threading over the live Layer and
    Optimizer objects, no mesh. The plain eager loop is NOT the reference
    here: under jit `optimizer.step()` is traced once, so Adam's step counter
    stays at 1 in this step as in `mesh.parallelize`'s (PERF.md section 7,
    question 1), and the eager loop's third loss already differs by 4%.

    Returns (jitted_step, state_fn):
      jitted_step(param_values, acc_values, master_values, *batch)
        -> (loss_value, new_params, new_accs, new_masters)
      state_fn() -> the current (params, accs, masters) value lists

    ``loss_fn(model, *batch_tensors)`` returns the scalar loss Tensor.
    """
    params = [p for _, p in model.named_parameters()]
    for p in params:
        if id(p) not in optimizer._accumulators:
            optimizer._accumulators[id(p)] = optimizer._init_state(p)
        if (optimizer._use_master_weights
                and id(p) not in optimizer._master_weights):
            optimizer._master_weights[id(p)] = p.value.astype(jnp.float32)
    acc_keys = [sorted(optimizer._accumulators[id(p)].keys()) for p in params]
    use_masters = optimizer._use_master_weights

    def train_step(param_values, acc_values, master_values, *batch):
        with rng.trace_key(jax.random.PRNGKey(0)):
            saved_p = [(p, p._value) for p in params]
            saved_a = {id(p): dict(optimizer._accumulators[id(p)])
                       for p in params}
            saved_m = dict(optimizer._master_weights)
            try:
                for p, v in zip(params, param_values):
                    p._replace_value(v)
                for p, ks, vs in zip(params, acc_keys, acc_values):
                    for k, v in zip(ks, vs):
                        optimizer._accumulators[id(p)][k] = v
                if use_masters:
                    for p, mv in zip(params, master_values):
                        optimizer._master_weights[id(p)] = mv
                loss = loss_fn(model, *[Tensor(b) for b in batch])
                loss.backward()
                optimizer.step()
                optimizer.clear_grad()
                new_p = [p._value for p in params]
                new_a = [[optimizer._accumulators[id(p)][k] for k in ks]
                         for p, ks in zip(params, acc_keys)]
                new_m = ([optimizer._master_weights[id(p)] for p in params]
                         if use_masters else master_values)
                return loss.value, new_p, new_a, new_m
            finally:
                for p, v in saved_p:
                    p._replace_value(v)
                for p in params:
                    optimizer._accumulators[id(p)] = saved_a[id(p)]
                optimizer._master_weights = saved_m

    jitted = jax.jit(train_step, donate_argnums=(0, 1, 2))

    def state_fn():
        pv = [p.value for p in params]
        av = [[optimizer._accumulators[id(p)][k] for k in ks]
              for p, ks in zip(params, acc_keys)]
        mv = ([optimizer._master_weights[id(p)] for p in params]
              if use_masters else [])
        return pv, av, mv

    return jitted, state_fn


def _single_device_losses(factory, loss_fn, batch, steps, lr=1e-2,
                          opt_cls=None):
    paddle.seed(0)
    model = factory()
    opt_cls = opt_cls or paddle.optimizer.Adam
    opt = opt_cls(learning_rate=lr, parameters=model.parameters())
    step, state = _build_step(model, opt, loss_fn)
    pv, av, mv = state()
    losses = []
    for _ in range(steps):
        loss, pv, av, mv = step(pv, av, mv, *batch)
        losses.append(float(loss))
    return losses


class TestMeshContext:
    def test_from_degrees_and_spec_mapping(self, mesh8):
        ctx = pmesh.MeshContext.from_degrees(dp=4, mp=2)
        assert ctx.axis_names == ("dp", "mp")
        assert ctx.axis_size("dp") == 4 and ctx.axis_size("mp") == 2
        assert ctx.manual_axes == ("dp",) and ctx.auto_axes == ("mp",)
        # placement list (per MESH dim) -> PartitionSpec (per TENSOR dim)
        spec = ctx.spec([Shard(0), Shard(1)])
        assert tuple(spec) == ("dp", "mp")
        spec = ctx.spec([Replicate(), Shard(0)])
        assert tuple(spec) == ("mp",)
        # co-shard: two mesh dims on one tensor dim -> tuple entry
        spec = ctx.spec([Shard(1), Shard(1)])
        assert tuple(spec) == (None, ("dp", "mp"))

    def test_placements_spec_round_trip(self, mesh8):
        ctx = pmesh.MeshContext.from_degrees(dp=8)
        pl = [Shard(0), Replicate()]
        assert ctx.placements(ctx.spec(pl)) == pl

    def test_device_count_guard(self, mesh8):
        with pytest.raises(RuntimeError, match="devices"):
            pmesh.MeshContext.from_degrees(dp=jax.device_count() * 2)

    def test_bootstrap_idempotent(self, mesh8):
        env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=8"}
        assert pmesh.bootstrap_virtual_devices(8, env=env)
        assert env["XLA_FLAGS"].count("host_platform_device_count") == 1

    def test_current_context_scope(self, mesh8):
        ctx = pmesh.MeshContext.from_degrees(dp=8)
        assert pmesh.current_mesh_context() is None
        with ctx:
            assert pmesh.current_mesh_context() is ctx
        assert pmesh.current_mesh_context() is None

    def test_batch_spec(self, mesh8):
        ctx = pmesh.MeshContext.from_degrees(dp=8)
        assert tuple(ctx.batch_spec(3)) == ("dp", None, None)


class TestSpmdRules:
    def test_matmul_dp_batch(self):
        req, out = pmesh.propagate(
            "matmul", [("dp", None, None), (None, None)],
            [(8, 16, 32), (32, 64)])
        assert out == [("dp", None, None)]
        assert req[1] == (None, None)  # no reshard needed

    def test_matmul_tp_column(self):
        _, out = pmesh.propagate(
            "matmul", [(None, None), (None, "mp")], [(8, 32), (32, 64)])
        assert out == [(None, "mp")]

    def test_matmul_contract_sharded_vanishes(self):
        # both operands sharded on the contracted dim: specs AGREE (no
        # reshard) and the axis disappears into an XLA all-reduce
        req, out = pmesh.propagate(
            "matmul", [(None, "mp"), ("mp", None)], [(8, 32), (32, 64)])
        assert req[1] == ("mp", None)
        assert out == [(None, None)]

    def test_matmul_mismatch_requires_reshard(self):
        req, _ = pmesh.propagate(
            "matmul", [(None, "dp"), ("mp", None)], [(8, 32), (32, 64)])
        assert req[1][0] == "dp"  # b's contract dim resharded to match a

    def test_norm_forces_whole_last_dim(self):
        for op in ("layer_norm", "rms_norm"):
            req, out = pmesh.propagate(
                op, [("dp", None, "mp"), ("mp",)], [(8, 16, 32), (32,)])
            assert req[0] == ("dp", None, None)
            assert req[1] == (None,)
            assert out == [("dp", None, None)]

    def test_softmax_reduces_on_device(self):
        req, out = pmesh.propagate(
            "softmax", [("dp", None, "mp")], [(8, 16, 32)],
            kwargs={"axis": -1})
        assert req[0] == ("dp", None, None) == out[0]

    def test_elementwise_merge_and_conflict(self):
        req, out = pmesh.propagate(
            "add", [("dp", None), (None, "mp")], [(8, 16), (8, 16)])
        assert out == [("dp", "mp")]
        # conflict: second operand resharded to the first's placement
        req, out = pmesh.propagate(
            "add", [("dp", None), ("mp", None)], [(8, 16), (8, 16)])
        assert out == [("dp", None)]
        assert req[1][0] == "dp"

    def test_reduction_drops_reduced_dims(self):
        _, out = pmesh.propagate("sum", [("dp", "mp")], [(8, 16)],
                                 kwargs={"axis": 1})
        assert out == [("dp",)]
        _, out = pmesh.propagate("mean", [("dp", "mp")], [(8, 16)])
        assert out == [()]  # full reduction

    def test_embedding_flows_hidden_shard(self):
        _, out = pmesh.propagate(
            "embedding_op", [("dp", None), (None, "mp")],
            [(8, 16), (100, 64)])
        assert out == [("dp", None, "mp")]

    def test_transpose_permutes(self):
        _, out = pmesh.propagate(
            "transpose", [("dp", None, "mp")], [(8, 16, 32)],
            kwargs={"perm": [1, 0, 2]})
        assert out == [(None, "dp", "mp")]

    def test_reshape_preserves_leading_or_gathers(self):
        _, out = pmesh.propagate(
            "reshape", [("dp", None, None)], [(8, 4, 16)],
            kwargs={"shape": [8, 64]})
        assert out == [("dp", None)]
        req, out = pmesh.propagate(
            "reshape", [(None, "mp", None)], [(8, 4, 16)],
            kwargs={"shape": [8, 64]})
        assert req[0] == (None, None, None)  # sharded dim folds: gather

    def test_unknown_op_propagates_nothing(self):
        assert pmesh.propagate("no_such_op", [("dp",)], [(8,)]) is None


class TestEagerPropagation:
    @pytest.fixture(autouse=True)
    def _prop(self, mesh8):
        self.ctx = pmesh.MeshContext.from_degrees(dp=8)
        pmesh.enable_propagation()
        yield
        pmesh.disable_propagation()

    def test_specs_flow_through_defop_outputs(self):
        x = dist_api.shard_tensor(
            np.random.randn(16, 32).astype("float32"),
            self.ctx.process_mesh, [Shard(0), Replicate()])
        w = paddle.to_tensor(np.random.randn(32, 8).astype("float32"))
        y = paddle.matmul(x, w)
        assert y._dist_attr is not None
        assert y._dist_attr.placements[0] == Shard(0)
        # chain: elementwise keeps the annotation
        s = (y + y)
        assert s._dist_attr.placements[0] == Shard(0)

    def test_no_dist_inputs_is_a_no_op(self):
        a = paddle.to_tensor(np.ones((4, 4), "float32"))
        out = paddle.matmul(a, a)
        assert out._dist_attr is None

    def test_disagreeing_spec_inserts_reshard_with_telemetry(self):
        mon_was, tr_was = monitor.enabled(), trace.enabled()
        monitor.enable()
        trace.enable()
        try:
            ctr = monitor.counter("paddle_tpu_mesh_reshards_total",
                                  labelnames=("kind",)).labels("all_gather")
            before = ctr.value
            x = dist_api.shard_tensor(
                np.random.randn(16, 32).astype("float32"),
                self.ctx.process_mesh, [Shard(1), Replicate()])
            w = paddle.to_tensor(np.ones(32, "float32"))
            out = paddle.nn.functional.rms_norm(x, w)
            assert ctr.value == before + 1
            assert out._dist_attr.placements == [Replicate(), Replicate()]
            names = [s.name for s in trace.spans()]
            assert "mesh.reshard" in names
        finally:
            if not mon_was:
                monitor.disable()
            if not tr_was:
                trace.disable()

    def test_values_unchanged_by_resharding(self):
        xv = np.random.RandomState(0).randn(16, 32).astype("float32")
        w = np.ones(32, "float32")
        ref = paddle.nn.functional.rms_norm(
            paddle.to_tensor(xv), paddle.to_tensor(w))
        x = dist_api.shard_tensor(xv, self.ctx.process_mesh,
                                  [Shard(1), Replicate()])
        out = paddle.nn.functional.rms_norm(x, paddle.to_tensor(w))
        np.testing.assert_allclose(np.asarray(out.value),
                                   np.asarray(ref.value), rtol=1e-6)

    def test_gradients_flow_through_inserted_reshard(self):
        xv = np.random.RandomState(1).randn(8, 16).astype("float32")
        x = dist_api.shard_tensor(xv, self.ctx.process_mesh,
                                  [Shard(1), Replicate()],
                                  stop_gradient=False)
        w = paddle.to_tensor(np.ones(16, "float32"))
        out = paddle.nn.functional.rms_norm(x, w)
        out.sum().backward()
        assert x.grad is not None
        assert np.all(np.isfinite(np.asarray(x.grad.value)))


class TestReshardFaultDrill:
    def test_mesh_collective_flag_raises_typed_fault(self, mesh8):
        from paddle_tpu.analysis import faultinject as fi

        ctx = pmesh.MeshContext.from_degrees(dp=8)
        pmesh.enable_propagation()
        fi.reset()
        try:
            fi.arm("mesh.collective", action="flag")
            x = dist_api.shard_tensor(
                np.random.randn(16, 32).astype("float32"),
                ctx.process_mesh, [Shard(1), Replicate()])
            w = paddle.to_tensor(np.ones(32, "float32"))
            with pytest.raises(pmesh.ReshardFault) as ei:
                paddle.nn.functional.rms_norm(x, w)
            assert ei.value.axis == "dp"  # the poisoned mesh axis, by name
            assert ei.value.kind == "all_gather"
            assert ("mesh.collective", "flag") in fi.trips()
            # disarmed: the same reshard succeeds
            fi.reset()
            out = paddle.nn.functional.rms_norm(x, w)
            assert out._dist_attr is not None
        finally:
            fi.reset()
            pmesh.disable_propagation()


class TestEagerCollectivesReal:
    """distributed/collective.py now dispatches real jax.lax collective
    programs: semantics unchanged, wire ops real, telemetry attached."""

    def test_all_reduce_program_contains_collective(self, mesh8):
        from paddle_tpu.distributed import collective as C

        v = paddle.to_tensor(np.arange(24, dtype="float32").reshape(8, 3))
        C.all_reduce(v)
        expect = np.arange(24, dtype="float32").reshape(8, 3).sum(0)
        for row in np.asarray(v.value):
            np.testing.assert_allclose(row, expect)
        g = C._world_group()
        prog = g._programs[("all_reduce", C.ReduceOp.SUM, "float32")]
        sharded = jax.device_put(jnp.zeros((8, 3)), C._stacked_sharding(g))
        hlo = prog.lower(sharded).compile().as_text()
        assert "all-reduce" in hlo

    def test_collectives_counted_and_spanned(self, mesh8):
        from paddle_tpu.distributed import collective as C

        mon_was, tr_was = monitor.enabled(), trace.enabled()
        monitor.enable()
        trace.enable()
        try:
            ctr = monitor.counter("paddle_tpu_comm_collectives_total",
                                  labelnames=("op",))
            before = ctr.labels("broadcast").value
            v = paddle.to_tensor(np.arange(8, dtype="float32")[:, None])
            C.broadcast(v, src=3)
            np.testing.assert_allclose(np.asarray(v.value).ravel(),
                                       np.full(8, 3.0))
            assert ctr.labels("broadcast").value == before + 1
            spans = [s for s in trace.spans() if s.name == "comm.collective"]
            assert spans and spans[-1].attrs["op"] == "broadcast"
            assert spans[-1].attrs["nranks"] == 8
        finally:
            if not mon_was:
                monitor.disable()
            if not tr_was:
                trace.disable()

    def test_reduce_scatter_and_alltoall_semantics(self, mesh8):
        from paddle_tpu.distributed import collective as C

        out = paddle.to_tensor(np.zeros((8, 2), "float32"))
        C.reduce_scatter(out, paddle.to_tensor(np.ones((8, 16), "float32")))
        np.testing.assert_allclose(np.asarray(out.value),
                                   np.full((8, 2), 8.0))
        ol = []
        vin = np.arange(64, dtype="float32").reshape(8, 8)
        C.alltoall(ol, paddle.to_tensor(vin))
        np.testing.assert_allclose(np.asarray(ol[0].value), vin[:, 0])
        np.testing.assert_allclose(np.asarray(ol[5].value), vin[:, 5])


class TestMeshTrainParity:
    def test_dp8_mlp_matches_single_device(self, mesh8):
        r = np.random.RandomState(0)
        xb = r.randn(16, 16).astype("float32")
        yb = r.randn(16, 16).astype("float32")
        ref = _single_device_losses(_mlp, _mse, (xb, yb), 3)

        paddle.seed(0)
        m = _mlp()
        opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                    parameters=m.parameters())
        mp = pmesh.parallelize(m, opt, _mse, (xb, yb),
                               config={"dp_degree": 8})
        got = [float(mp.step(xb, yb)) for _ in range(3)]
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)
        assert mp.collective_counts(xb, yb).get("all_reduce", 0) >= 1

    def test_dp8_is_deterministic_bit_exact(self, mesh8):
        r = np.random.RandomState(1)
        xb = r.randn(8, 16).astype("float32")
        yb = r.randn(8, 16).astype("float32")

        def run():
            paddle.seed(0)
            m = _mlp()
            opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                        parameters=m.parameters())
            mp = pmesh.parallelize(m, opt, _mse, (xb, yb),
                                   config={"dp_degree": 8})
            return [float(mp.step(xb, yb)) for _ in range(3)]

        assert run() == run()  # DP bit-exact for the same global batch

    def test_zero1_matches_and_shrinks_state(self, mesh8):
        r = np.random.RandomState(0)
        xb = r.randn(16, 16).astype("float32")
        yb = r.randn(16, 16).astype("float32")
        ref = _single_device_losses(_mlp, _mse, (xb, yb), 3)

        paddle.seed(0)
        m = _mlp()
        opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                    parameters=m.parameters())
        mz = pmesh.parallelize(m, opt, _mse, (xb, yb),
                               config={"dp_degree": 8,
                                       "shard_optimizer": True})
        got = [float(mz.step(xb, yb)) for _ in range(3)]
        np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)
        # the ZeRO-1 exchange is a real reduce-scatter + all-gather pair
        coll = mz.collective_counts(xb, yb)
        assert coll.get("reduce_scatter", 0) >= 1
        assert coll.get("all_gather", 0) >= 1
        # per-replica optimizer state ~1/dp of replicated
        paddle.seed(0)
        m2 = _mlp()
        o2 = paddle.optimizer.Adam(learning_rate=1e-2,
                                   parameters=m2.parameters())
        mp = pmesh.parallelize(m2, o2, _mse, (xb, yb),
                               config={"dp_degree": 8})
        ratio = mz.optimizer_state_bytes() / mp.optimizer_state_bytes()
        assert ratio <= 1 / 8 + 0.02, ratio

    def test_zero1_state_bytes_gauge(self, mesh8):
        mon_was = monitor.enabled()
        monitor.enable()
        try:
            r = np.random.RandomState(0)
            xb = r.randn(8, 16).astype("float32")
            yb = r.randn(8, 16).astype("float32")
            paddle.seed(0)
            m = _mlp()
            opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                        parameters=m.parameters())
            mz = pmesh.parallelize(m, opt, _mse, (xb, yb),
                                   config={"dp_degree": 8,
                                           "shard_optimizer": True})
            mz.step(xb, yb)
            snap = monitor.snapshot()["metrics"]
            gauge = snap["paddle_tpu_mesh_optimizer_state_bytes"]["values"][""]
            assert gauge == mz.optimizer_state_bytes() > 0
        finally:
            if not mon_was:
                monitor.disable()

    def test_shard_optimizer_rejects_global_norm_clip(self, mesh8):
        paddle.seed(0)
        m = _mlp()
        opt = paddle.optimizer.Adam(
            learning_rate=1e-2, parameters=m.parameters(),
            grad_clip=paddle.nn.ClipGradByGlobalNorm(1.0))
        xb = np.zeros((8, 16), "float32")
        with pytest.raises(ValueError, match="shard_optimizer"):
            pmesh.parallelize(m, opt, _mse, (xb, xb),
                              config={"dp_degree": 8,
                                      "shard_optimizer": True})

    def test_batch_divisibility_guard(self, mesh8):
        paddle.seed(0)
        m = _mlp()
        opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                    parameters=m.parameters())
        xb = np.zeros((8, 16), "float32")
        mp = pmesh.parallelize(m, opt, _mse, (xb, xb),
                               config={"dp_degree": 8})
        with pytest.raises(ValueError, match="divisible"):
            mp.step(np.zeros((6, 16), "float32"), np.zeros((6, 16), "float32"))

    def test_finalize_writes_back_trained_state(self, mesh8):
        r = np.random.RandomState(0)
        xb = r.randn(8, 16).astype("float32")
        yb = r.randn(8, 16).astype("float32")
        paddle.seed(0)
        m = _mlp()
        opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                    parameters=m.parameters())
        mz = pmesh.parallelize(m, opt, _mse, (xb, yb),
                               config={"dp_degree": 8,
                                       "shard_optimizer": True})
        mz.step(xb, yb)
        mz.finalize()
        for _, p in m.named_parameters():
            v = np.asarray(p.value)
            assert np.all(np.isfinite(v))
            st = opt._accumulators[id(p)]
            for k, sv in st.items():
                assert sv.shape == tuple(p.shape)  # gathered back whole


class TestMeshLlamaAcceptance:
    """ISSUE 8 acceptance on the real llama step (tiny shape, tier-1)."""

    def _llama(self):
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

        cfg = LlamaConfig(vocab_size=64, hidden_size=32,
                          intermediate_size=64, num_hidden_layers=2,
                          num_attention_heads=2, num_key_value_heads=2,
                          max_position_embeddings=16)
        return LlamaForCausalLM(cfg)

    @staticmethod
    def _loss(m, ids, labels):
        loss, _ = m(ids, labels=labels)
        return loss

    def test_dp8_llama_parity_sanitized_steady_state_comm_spans(self, mesh8):
        """The ISSUE 8 bar in one pass (one compile cycle, tier-1 budget):
        DP=8 llama losses match single-device within fp tolerance, the
        PADDLE_TPU_SANITIZE discipline holds (zero post-warmup recompiles,
        no host-sync trips), and >= 1 real collective is visible in comm.*
        spans."""
        from paddle_tpu.analysis import sanitizers as san

        r = np.random.RandomState(0)
        ids = r.randint(0, 64, (8, 8)).astype("int64")
        labels = r.randint(0, 64, (8, 8, 1)).astype("int64")
        ref = _single_device_losses(self._llama, self._loss, (ids, labels),
                                    4, lr=1e-3,
                                    opt_cls=paddle.optimizer.AdamW)
        paddle.seed(0)
        m = self._llama()
        opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                     parameters=m.parameters())
        mp = pmesh.parallelize(m, opt, self._loss, (ids, labels),
                               config={"dp_degree": 8})
        got = [float(mp.step(ids, labels))]  # warmup: the one allowed compile
        # the census is asked for OUTSIDE a step (a traced step only
        # attaches what is cached; it never lowers the program itself)
        mp.collective_counts(ids, labels)
        tr_was = trace.enabled()
        trace.enable()
        san.reset()
        san.enable("recompile", "hostsync")
        try:
            compiles_before = mp._jitted._cache_size()
            got += [float(mp.step(ids, labels)) for _ in range(3)]
            np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-5)
            assert mp._jitted._cache_size() == compiles_before, \
                "mesh step recompiled post-warmup"
            assert san.trips() == []
            spans = [s for s in trace.spans() if s.name == "comm.mesh_step"]
            assert spans, "no comm.mesh_step span recorded"
            attrs = spans[-1].attrs
            assert attrs["dp"] == 8
            assert attrs.get("all_reduce", 0) >= 1, attrs
        finally:
            # reset() drops counts but leaves ENABLE state untouched — the
            # sentinel must also be disabled or every later to_static test
            # in the session inherits a ticking recompile budget
            san.reset()
            san.disable("recompile", "hostsync")
            if not tr_was:
                trace.disable()


class TestFaultTolerantTraining:
    """ISSUE 10: the training twin of the serving resilience layer —
    kill/hang drills with bit-identical resume from async checkpoints,
    corrupted-checkpoint fallback, the dp 8->4 elastic restore, and the
    watchdog over eager collectives."""

    @pytest.fixture(autouse=True)
    def _clean_faults(self):
        from paddle_tpu.analysis import faultinject as fi

        fi.reset()
        yield
        fi.reset()

    @staticmethod
    def _batch(seed=0):
        r = np.random.RandomState(seed)
        return (r.randn(16, 16).astype("float32"),
                r.randn(16, 16).astype("float32"))

    def _trainer(self, ckpt_dir, batch, dp=8, shard_optimizer=False, **kw):
        paddle.seed(0)
        m = _mlp()
        opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                    parameters=m.parameters())
        return pmesh.MeshTrainer(
            m, opt, _mse, batch,
            config={"dp_degree": dp, "shard_optimizer": shard_optimizer},
            checkpoint=str(ckpt_dir), **kw)

    def test_kill_mid_step_resumes_bit_identical(self, mesh8, tmp_path):
        """THE kill acceptance drill: the step dies mid-run, recover()
        reloads the last committed checkpoint WARM (compiled program
        survives, zero recompiles under the sentinel) and the replayed
        losses are bit-identical to an uninterrupted run."""
        from paddle_tpu.analysis import faultinject as fi
        from paddle_tpu.analysis import sanitizers as san

        batch = self._batch()
        data = lambda step: batch  # noqa: E731
        ref = self._trainer(tmp_path / "ref", batch).fit(
            data, 6, ckpt_every=2)

        t = self._trainer(tmp_path / "chaos", batch)
        san.reset()
        san.enable("recompile")
        fi.arm("mesh.step", action="raise", nth=4)
        try:
            t.fit(data, 6, ckpt_every=2)        # warmup compile is step 1
            compiles = t.handle._jitted._cache_size()
            assert san.trips() == []
        finally:
            san.reset()
            san.disable("recompile")
        assert t.losses == ref                  # bit-identical floats
        assert ("mesh.step", "raise") in fi.trips()
        assert len(t.recovery_stats) == 1
        rec = t.recovery_stats[0]
        assert rec["restored_step"] == 2        # the last committed save
        assert rec["stuck"] == "mesh.step"
        assert compiles == 1, "post-recovery recompile (restart not warm)"

    def test_hang_watchdog_recovers_with_coalesced_dump(self, mesh8,
                                                       tmp_path):
        """The hang drill: a delayed step trips the CommWatchdog; the
        scanner thread recovers (epoch bump), the stuck step wakes into
        the new epoch (TrainStepSuperseded, no state touched), ONE
        coalesced flight dump names BOTH observers, and the resumed
        losses are bit-identical."""
        from paddle_tpu.analysis import faultinject as fi

        batch = self._batch(1)
        data = lambda step: batch  # noqa: E731
        ref = self._trainer(tmp_path / "ref", batch).fit(
            data, 6, ckpt_every=2)

        tr_was = trace.enabled()
        trace.enable()
        t = self._trainer(tmp_path / "chaos", batch, hang_timeout=0.4)
        fi.arm("mesh.step", action="delay", delay_s=1.5, nth=4)
        try:
            got = t.fit(data, 6, ckpt_every=2)
        finally:
            t.close()
            if not tr_was:
                trace.disable()
        assert got == ref
        assert len(t.recovery_stats) == 1
        assert t.last_recovery_dump
        with open(t.last_recovery_dump) as f:
            doc = json.load(f)
        reasons = doc["reasons"]
        assert any("watchdog timeout" in r for r in reasons), reasons
        assert any("mesh train recovery" in r for r in reasons), reasons
        assert t.handle._jitted._cache_size() == 1

    def test_corrupted_checkpoint_falls_back_to_previous(self, mesh8,
                                                         tmp_path):
        """The torn/corrupt drill: the newest checkpoint's bytes are
        poisoned post-digest; a later kill must restore from the
        PREVIOUS committed step, and still replay bit-identical."""
        from paddle_tpu.analysis import faultinject as fi

        batch = self._batch(2)
        data = lambda step: batch  # noqa: E731
        ref = self._trainer(tmp_path / "ref", batch).fit(
            data, 6, ckpt_every=2)

        t = self._trainer(tmp_path / "chaos", batch)
        # writes: anchor(step 0), step 2, step 4(corrupted), then a kill
        fi.arm("ckpt.write", action="flag", nth=3)
        fi.arm("mesh.step", action="raise", nth=6)
        got = t.fit(data, 6, ckpt_every=2)
        assert got == ref
        assert len(t.recovery_stats) == 1
        assert t.recovery_stats[0]["restored_step"] == 2, \
            t.recovery_stats[0]

    def test_torn_write_never_commits(self, mesh8, tmp_path):
        """raise at ckpt.write = the writer dies mid-save: the step is
        never committed; recovery (after a kill) restores the previous
        commit and records the surfaced write error."""
        from paddle_tpu.analysis import faultinject as fi

        batch = self._batch(3)
        data = lambda step: batch  # noqa: E731
        ref = self._trainer(tmp_path / "ref", batch).fit(
            data, 6, ckpt_every=2)

        t = self._trainer(tmp_path / "chaos", batch)
        fi.arm("ckpt.write", action="raise", nth=3)   # step 4's write
        fi.arm("mesh.step", action="raise", nth=6)
        got = t.fit(data, 6, ckpt_every=2)
        assert got == ref
        rec = t.recovery_stats[0]
        assert rec["restored_step"] == 2
        assert rec["write_error"] and "InjectedFault" in rec["write_error"]

    def test_elastic_dp8_to_dp4_restore_continues(self, mesh8, tmp_path):
        """The elastic drill: a ZeRO-1 dp=8 run checkpoints, a FRESH
        dp=4 trainer restores from it (per-replica rows gathered and
        re-sliced onto the new degree) and the continuation's losses
        match an uninterrupted dp=8 run within fp tolerance."""
        batch = self._batch(4)
        data = lambda step: batch  # noqa: E731
        ckpt = tmp_path / "elastic"
        t8 = self._trainer(ckpt, batch, dp=8, shard_optimizer=True)
        t8.fit(data, 3, ckpt_every=1)
        assert t8.manager.latest_step() == 3

        t4 = self._trainer(ckpt, batch, dp=4, shard_optimizer=True)
        cont = t4.fit(data, 6, ckpt_every=1)
        assert t4.step_idx == 6
        assert sorted(cont) == [3, 4, 5]        # resumed AT step 3

        ref = self._trainer(tmp_path / "ref", batch, dp=8,
                            shard_optimizer=True).fit(data, 6,
                                                      ckpt_every=0)
        np.testing.assert_allclose(
            [cont[s] for s in (3, 4, 5)], [ref[s] for s in (3, 4, 5)],
            rtol=2e-4, atol=1e-6)

    def test_elastic_zero_to_plain_restore(self, mesh8, tmp_path):
        """A ZeRO checkpoint also restores into a plain-DP trainer (rows
        gathered to full state) — the layout conversion matrix both
        ways."""
        batch = self._batch(5)
        data = lambda step: batch  # noqa: E731
        ckpt = tmp_path / "mixed"
        tz = self._trainer(ckpt, batch, dp=8, shard_optimizer=True)
        tz.fit(data, 2, ckpt_every=1)
        tp = self._trainer(ckpt, batch, dp=8, shard_optimizer=False)
        cont = tp.fit(data, 4, ckpt_every=1)
        ref = self._trainer(tmp_path / "ref", batch, dp=8,
                            shard_optimizer=True).fit(data, 4,
                                                      ckpt_every=0)
        np.testing.assert_allclose(
            [cont[s] for s in (2, 3)], [ref[s] for s in (2, 3)],
            rtol=2e-4, atol=1e-6)

    def test_recover_telemetry_and_metrics(self, mesh8, tmp_path):
        from paddle_tpu.analysis import faultinject as fi

        batch = self._batch(6)
        data = lambda step: batch  # noqa: E731
        mon_was, tr_was = monitor.enabled(), trace.enabled()
        monitor.enable()
        trace.enable()
        t = self._trainer(tmp_path / "tele", batch)
        fi.arm("mesh.step", action="raise", nth=3)
        try:
            t.fit(data, 4, ckpt_every=1)
            snap = monitor.snapshot()
            rec = snap["metrics"][
                "paddle_tpu_train_recoveries_total"]["values"][""]
            assert rec >= 1
            names = [s.name for s in trace.spans()]
            assert "train.recover" in names
            assert "ckpt.save" in names
        finally:
            if not tr_was:
                trace.disable()
            if not mon_was:
                monitor.disable()

    def test_recovery_budget_exhausts_with_typed_raise(self, mesh8,
                                                       tmp_path):
        """max_recoveries bounds the retry loop: a fault that keeps
        firing eventually propagates instead of looping forever."""
        from paddle_tpu.analysis import faultinject as fi

        batch = self._batch(7)
        data = lambda step: batch  # noqa: E731
        t = self._trainer(tmp_path / "boom", batch, max_recoveries=2,
                          backoff_s=0.01)
        fi.arm("mesh.step", action="raise", nth=1, times=10)
        with pytest.raises(Exception, match="injected fault"):
            t.fit(data, 4, ckpt_every=1)
        assert len(t.recovery_stats) == 2       # budget, then raise

    def test_resume_false_purges_prior_run_commits(self, mesh8, tmp_path):
        """resume=False over a directory with a PRIOR run's checkpoints:
        the old commits are purged, so a recovery in the fresh run can
        never restore_latest_valid() into foreign state."""
        from paddle_tpu.analysis import faultinject as fi

        batch = self._batch(10)
        data = lambda step: batch  # noqa: E731
        ckpt = tmp_path / "shared"
        old = self._trainer(ckpt, batch)
        old.fit(data, 5, ckpt_every=1)          # commits up to step 5
        old.close()

        t = self._trainer(ckpt, batch)
        fi.arm("mesh.step", action="raise", nth=2)
        got = t.fit(data, 3, ckpt_every=1, resume=False)
        assert sorted(got) == [0, 1, 2]
        # the kill at step 1 restored THIS run's commit, not old step 5
        assert t.recovery_stats[0]["restored_step"] <= 1
        assert max(t.manager.steps()) == 3

    def test_hang_without_manager_keeps_scanner_alive(self, mesh8):
        """checkpoint=None + a hang: there is no restore target, so the
        watchdog callback must NOT recover (and must never kill the
        scanner thread with a CheckpointError) — the slow step simply
        completes and training continues."""
        from paddle_tpu.analysis import faultinject as fi

        batch = self._batch(9)
        data = lambda step: batch  # noqa: E731
        paddle.seed(0)
        m = _mlp()
        opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                    parameters=m.parameters())
        t = pmesh.MeshTrainer(m, opt, _mse, batch,
                              config={"dp_degree": 8},
                              checkpoint=None, hang_timeout=0.2)
        fi.arm("mesh.step", action="delay", delay_s=0.8, nth=2)
        try:
            losses = t.fit(data, 3, ckpt_every=0)
        finally:
            dog = t._dog
            t.close()
        assert sorted(losses) == [0, 1, 2]
        assert len(t.recovery_stats) == 0       # nothing to restore from
        assert dog.timed_out                    # the hang WAS observed

    def test_persistent_hang_exhausts_recovery_budget(self, mesh8,
                                                      tmp_path):
        """A step that hangs EVERY time consumes the same bounded
        max_recoveries budget as repeated deaths — fit() raises instead
        of looping through scanner recoveries forever."""
        from paddle_tpu.analysis import faultinject as fi

        batch = self._batch(8)
        data = lambda step: batch  # noqa: E731
        t = self._trainer(tmp_path / "hang", batch, hang_timeout=0.3,
                          max_recoveries=2, backoff_s=0.01)
        fi.arm("mesh.step", action="delay", delay_s=1.2, nth=1, times=50)
        try:
            with pytest.raises(pmesh.TrainStepSuperseded):
                t.fit(data, 4, ckpt_every=1)
        finally:
            t.close()
        assert len(t.recovery_stats) == 3   # budget of 2 + the last raise

    def test_default_watchdog_watches_eager_collectives(self, mesh8):
        """set_default_watchdog arms the eager collective layer: a real
        all_reduce dispatch runs inside a watched section (visible in
        the watchdog's event history)."""
        from paddle_tpu.distributed.watchdog import (CommWatchdog,
                                                     set_default_watchdog)

        from paddle_tpu.distributed import collective as C

        dog = CommWatchdog(timeout=30.0)
        prev = set_default_watchdog(dog)
        try:
            v = paddle.to_tensor(
                np.arange(16, dtype="float32").reshape(8, 2))
            C.all_reduce(v)
            expect = np.arange(16, dtype="float32").reshape(8, 2).sum(0)
            for row in np.asarray(v.value):
                np.testing.assert_allclose(row, expect)
            descs = [d for d, _, _ in dog.events]
            assert any(d.startswith("comm.all_reduce") for d in descs), \
                descs
        finally:
            set_default_watchdog(prev)
            dog.stop()


class TestCommEfficientTraining:
    """ISSUE 13: quantized grad reduction with error feedback + bucketed
    backward-overlapped grad collectives — parity gates, the EF drill,
    residual checkpointing, the comm.quantize fault drill, recompile
    silence and clean graftir re-analysis of the compressed program."""

    @pytest.fixture(autouse=True)
    def _clean_faults(self):
        from paddle_tpu.analysis import faultinject as fi

        fi.reset()
        yield
        fi.reset()

    @staticmethod
    def _batch(seed=0):
        r = np.random.RandomState(seed)
        return (r.randn(16, 16).astype("float32"),
                r.randn(16, 16).astype("float32"))

    def _run(self, cfg, batch, steps=6, mesh8=None, lr=1e-2):
        paddle.seed(0)
        m = _mlp()
        opt = paddle.optimizer.Adam(learning_rate=lr,
                                    parameters=m.parameters())
        h = pmesh.parallelize(m, opt, _mse, batch, config=dict(cfg))
        losses = [float(h.step(*batch)) for _ in range(steps)]
        return h, losses

    def test_int8_parity_and_wire_bytes_at_dp8(self, mesh8):
        batch = self._batch()
        _, base = self._run({"dp_degree": 8, "shard_optimizer": True},
                            batch)
        h, comp = self._run(
            {"dp_degree": 8, "shard_optimizer": True,
             "grad_compression": "int8", "overlap_grad_comm": True,
             "bucket_bytes": 1024}, batch)
        bound = 1e-2 * max(1.0, abs(base[-1]))
        assert abs(comp[-1] - base[-1]) <= bound, (comp[-1], base[-1])
        # the declared acceptance bar: grad-reduction bytes <= 30% of
        # the uncompressed ZeRO exchange, census-measured
        uz, _ = self._run({"dp_degree": 8, "shard_optimizer": True},
                          batch, steps=1)
        cb = h.collective_bytes(*batch)
        ub = uz.collective_bytes(*batch)
        ratio = cb["all_to_all"]["bytes"] / ub["reduce_scatter"]["bytes"]
        assert ratio <= 0.30, (ratio, cb, ub)
        rep = h.comm_report(*batch)
        assert rep["bucket_count"] >= 2
        assert rep["compressed_bytes"] == cb["all_to_all"]["bytes"]
        assert rep["bytes_ratio"] <= 0.30
        # residual state really rides the step (donated in, donated out)
        assert h._rv is not None and len(h._rv) == len(h.params)

    def test_fp8_parity_at_dp8(self, mesh8):
        batch = self._batch(1)
        _, base = self._run({"dp_degree": 8, "shard_optimizer": True},
                            batch)
        h, comp = self._run(
            {"dp_degree": 8, "shard_optimizer": True,
             "grad_compression": "fp8", "overlap_grad_comm": True,
             "bucket_bytes": 1024}, batch)
        bound = 2e-2 * max(1.0, abs(base[-1]))
        assert abs(comp[-1] - base[-1]) <= bound
        # fp8 wire is 1 byte/element too
        cb = h.collective_bytes(*batch)
        assert cb["all_to_all"]["bytes"] < 0.30 * sum(
            4 * int(np.prod(p.shape)) for p in h.params) * 8

    def test_plain_dp_compression_parity(self, mesh8):
        batch = self._batch(2)
        _, base = self._run({"dp_degree": 8}, batch)
        h, comp = self._run(
            {"dp_degree": 8, "grad_compression": "int8",
             "overlap_grad_comm": True, "bucket_bytes": 1024}, batch)
        bound = 1e-2 * max(1.0, abs(base[-1]))
        assert abs(comp[-1] - base[-1]) <= bound
        # the plain-DP compressed exchange is all_to_all + all_gather,
        # both at 1 byte/element
        cb = h.collective_bytes(*batch)
        assert cb["all_to_all"]["count"] >= 2
        assert cb["all_gather"]["count"] >= 2

    def test_overlap_only_is_bit_identical(self, mesh8):
        """compression=none + overlap: the SAME elementwise reductions,
        grouped per-bucket. The exchange itself is bit-identical to the
        legacy per-param one (pinned directly below, on the reduced
        slices); the losses are NOT pinned bit for bit: the bucketed
        program hands the optimizer a slice of the bucket, XLA:CPU then
        fuses the Adam update into one kernel where the legacy program
        gets three, and fused multiply-add contraction differs between
        them by one fp32 ulp (seen on jax 0.9.0 in the ZeRO-1 layout
        from step 2 on). A few ulps is the bound."""
        from jax.sharding import Mesh, PartitionSpec as P
        from paddle_tpu.mesh import comm_opt, zero

        r = np.random.RandomState(0)
        shapes = [(16, 32), (32,), (32, 16), (16,)]
        grads = [r.randn(8, *s).astype("float32") for s in shapes]

        def both(*gs):
            gs = [g[0] for g in gs]           # this replica's gradient
            legacy = [zero.scatter_grad(g, "dp", 8) for g in gs]
            bucketed, _, _ = comm_opt.bucket_reduce(
                [comm_opt.blockify(g, 8) for g in gs], "dp", 8, "none",
                "slice")
            return legacy, bucketed

        legacy, bucketed = jax.jit(jax.shard_map(
            both, mesh=Mesh(np.array(mesh8), ("dp",)),
            in_specs=(P("dp"),) * len(grads), out_specs=P("dp"),
            check_vma=False))(*grads)
        for a, b in zip(legacy, bucketed):
            assert np.array_equal(np.asarray(a), np.asarray(b))

        batch = self._batch(3)
        for extra in ({"shard_optimizer": True}, {}):
            cfg = {"dp_degree": 8, **extra}
            _, base = self._run(cfg, batch)
            h, over = self._run(
                {**cfg, "overlap_grad_comm": True, "bucket_bytes": 1024},
                batch)
            np.testing.assert_allclose(
                over, base, rtol=8 * np.finfo(np.float32).eps, atol=0,
                err_msg=str(extra))
            rep = h.comm_report(*batch)
            assert rep["bucket_count"] >= 2
            assert rep["compression"] == "none"
            # buckets follow reverse-autodiff completion order: the LAST
            # layer's params complete first
            first_bucket = rep["buckets"][0]
            assert any(n.startswith("2.") for n in first_bucket), rep

    def test_compressed_run_is_bit_reproducible(self, mesh8):
        batch = self._batch(4)
        cfg = {"dp_degree": 8, "shard_optimizer": True,
               "grad_compression": "int8", "overlap_grad_comm": True,
               "bucket_bytes": 1024}
        _, a = self._run(cfg, batch)
        _, b = self._run(cfg, batch)
        assert a == b

    def test_error_feedback_drill(self, mesh8):
        """The EF acceptance drill: a loss whose per-quantization-row
        gradients mix one dominant column with small ones. Without
        feedback the small grads round to ZERO every step (|g| <
        scale/2) and those columns never train; with feedback the
        residual accumulates past the threshold — the compressed loss
        tracks fp32 while the no-feedback ablation diverges by orders
        of magnitude more."""
        sv = np.full(64, 0.05, "float32")
        sv[::8] = 1.0

        def model():
            paddle.seed(0)
            return paddle.nn.Linear(1, 64, bias_attr=False)

        def loss_fn(m, x, y):
            s = paddle.to_tensor(sv)
            return (((m(x) - y) * s) ** 2).mean()

        x = np.ones((8, 1), "float32")
        y = np.full((8, 64), 1000.0, "float32")

        def run(cfg, steps=40):
            m = model()
            opt = paddle.optimizer.SGD(learning_rate=10.0,
                                       parameters=m.parameters())
            h = pmesh.parallelize(m, opt, loss_fn, (x, y),
                                  config=dict(cfg))
            return [float(h.step(x, y)) for _ in range(steps)]

        zero_cfg = {"dp_degree": 8, "shard_optimizer": True}
        comp_cfg = {**zero_cfg, "grad_compression": "int8",
                    "overlap_grad_comm": True, "bucket_bytes": 1024}
        base = run(zero_cfg)
        ef = run(comp_cfg)
        noef = run({**comp_cfg, "error_feedback": False})
        gap_ef = abs(ef[-1] - base[-1])
        gap_noef = abs(noef[-1] - base[-1])
        assert gap_ef < 0.1, gap_ef
        assert gap_noef > 1.0, gap_noef
        assert gap_ef < gap_noef / 100, (gap_ef, gap_noef)

    def test_comm_quantize_fault_falls_back_uncompressed(self, mesh8):
        from paddle_tpu.analysis import faultinject as fi

        batch = self._batch(5)
        _, base = self._run({"dp_degree": 8, "shard_optimizer": True},
                            batch)
        fi.arm("comm.quantize", action="flag")
        h, got = self._run(
            {"dp_degree": 8, "shard_optimizer": True,
             "grad_compression": "int8"}, batch)
        assert ("comm.quantize", "flag") in fi.trips()
        assert h.meta["comm_fault_fallback"] is True
        assert h.meta["comm"] is None          # fully degraded build
        assert h._rv is None                   # no residual state either
        # the degraded step IS the uncompressed reduction: bit-identical
        assert got == base
        assert "all_to_all" not in h.collective_bytes(*batch)
        # disarmed: the same config compresses again
        fi.reset()
        h2, _ = self._run(
            {"dp_degree": 8, "shard_optimizer": True,
             "grad_compression": "int8"}, batch, steps=1)
        assert h2.meta["comm_fault_fallback"] is False
        assert "all_to_all" in h2.collective_bytes(*batch)

    def test_residuals_ride_checkpoints_bit_identical_resume(
            self, mesh8, tmp_path):
        """The ISSUE 13 checkpoint satellite: an interrupted+resumed
        COMPRESSED run replays bit-identical losses — which can only
        hold if the error-feedback residual state round-trips through
        CheckpointManager with everything else."""
        from paddle_tpu.analysis import faultinject as fi

        batch = self._batch(6)
        data = lambda step: batch  # noqa: E731
        cfg = {"dp_degree": 8, "shard_optimizer": True,
               "grad_compression": "int8", "overlap_grad_comm": True,
               "bucket_bytes": 1024}

        def trainer(ckpt):
            paddle.seed(0)
            m = _mlp()
            opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                        parameters=m.parameters())
            return pmesh.MeshTrainer(m, opt, _mse, batch,
                                     config=dict(cfg),
                                     checkpoint=str(ckpt))

        ref = trainer(tmp_path / "ref").fit(data, 6, ckpt_every=2)
        t = trainer(tmp_path / "chaos")
        fi.arm("mesh.step", action="raise", nth=4)
        got = t.fit(data, 6, ckpt_every=2)
        assert got == ref                      # bit-identical floats
        assert ("mesh.step", "raise") in fi.trips()
        assert len(t.recovery_stats) == 1
        # the snapshot really carried the residuals
        rc = t.manager.restore_latest_valid()
        resid = [k for k in rc.arrays if k.startswith("resid/")]
        assert len(resid) == len(t.handle.params)

    def test_zero_postwarmup_recompiles_and_telemetry(self, mesh8):
        """The one-compiled-program invariant with compression AND
        overlap on, under the recompile sentinel, plus the new
        telemetry: comm.bucket_reduce spans, the compressed-bytes
        counter and the bucket gauge."""
        from paddle_tpu.analysis import sanitizers as san

        batch = self._batch(7)
        mon_was, tr_was = monitor.enabled(), trace.enabled()
        monitor.enable()
        trace.enable()
        san.reset()
        san.enable("recompile")
        try:
            ctr = monitor.counter(
                "paddle_tpu_mesh_comm_compressed_bytes_total")
            before = ctr.value
            h, _ = self._run(
                {"dp_degree": 8, "shard_optimizer": True,
                 "grad_compression": "int8", "overlap_grad_comm": True,
                 "bucket_bytes": 1024}, batch, steps=5)
            assert h._jitted._cache_size() == 1
            assert san.trips() == []
            rep = h.comm_report(*batch)
            assert ctr.value - before \
                == 5 * rep["compressed_bytes"]
            assert monitor.gauge("paddle_tpu_mesh_grad_buckets").value \
                == rep["bucket_count"]
            spans = [s for s in trace.spans()
                     if s.name == "comm.bucket_reduce"]
            assert spans, "no comm.bucket_reduce spans recorded"
            at = spans[-1].attrs
            assert at["compression"] == "int8" and at["overlap"] is True
            assert at["buckets"] == rep["bucket_count"]
            assert 0 < at["compressed_bytes"] < at["uncompressed_bytes"]
            # a step attaches only the census that is already cached:
            # nothing until collective_bytes() is asked outside a step
            mesh_spans = [s for s in trace.spans()
                          if s.name == "comm.mesh_step"]
            assert "all_to_all_bytes" not in mesh_spans[-1].attrs
            h.collective_bytes(*batch)
            h.step(*batch)
            mesh_spans = [s for s in trace.spans()
                          if s.name == "comm.mesh_step"]
            assert mesh_spans[-1].attrs.get("all_to_all_bytes", 0) > 0
            assert h._jitted._cache_size() == 1
        finally:
            san.reset()
            san.disable("recompile")
            if not tr_was:
                trace.disable()
            if not mon_was:
                monitor.disable()

    def test_compressed_program_reanalyzes_clean(self, mesh8):
        """GI001-GI004 over the compressed+overlapped step program, raw
        AND after graftopt's rewrites — the quantize grid projection
        never emits a lossy convert round-trip, the collective sequence
        stays branch-consistent, donation (incl. the residual lists)
        stays safe."""
        from paddle_tpu.analysis.jaxpr import ir as gir
        from paddle_tpu.analysis.jaxpr import opt as gopt
        from paddle_tpu.analysis.jaxpr.passes import ALL_PASSES

        batch = self._batch(8)
        h, _ = self._run(
            {"dp_degree": 8, "shard_optimizer": True,
             "grad_compression": "int8", "overlap_grad_comm": True,
             "bucket_bytes": 1024}, batch, steps=1)
        args = h._step_args(batch)
        prog = gir.trace(h._jitted, args, "mesh.train_step.compressed")
        findings = gir.analyze_program(prog, ALL_PASSES)
        assert findings == [], [repr(f) for f in findings]
        oprog, res = gopt.optimize_program(prog)
        refind = gir.analyze_program(oprog, ALL_PASSES)
        assert refind == [], [repr(f) for f in refind]
        # fewer fusible regions on the optimized form, like the flagships
        assert gopt.count_regions(oprog.jaxpr) \
            <= gopt.count_regions(prog.jaxpr)
