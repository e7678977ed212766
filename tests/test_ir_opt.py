"""graftopt (paddle_tpu/analysis/jaxpr/opt.py + planner.py): the jaxpr
transform layer, tier-1.

Five contracts under test (ISSUE 12 acceptance):

1. every REWRITE fires on its dirty traced fixture, preserves bits, and
   never fires where it would change them (the lossy convert round trip
   stays unless ``allow_lossy`` opts in);
2. the FLAGSHIP programs — serving mixed step, decode burst, DP=8
   ZeRO-1 mesh train step, built through the production builders —
   optimize BIT-exact, with fewer fusible regions, and the optimized
   programs re-analyze clean under GI001–GI004 (the check_opt_parity
   contract);
3. the BUDGET-driven remat planner: a budget below the unoptimized
   GI003 peak yields a non-empty minimal plan whose estimate fits, the
   compiler-measured bytes confirm it within the existing 15% band,
   losses match the no-remat step, and the same budget always yields
   the same plan (determinism);
4. the sanitize discipline holds on OPTIMIZED programs: zero
   post-warmup recompiles with the optimizer enabled under
   PADDLE_TPU_SANITIZE-style sentinels;
5. the CLI surfaces (``--optimize`` on the module CLI and
   tools/ir_report.py) and the byte-census satellite
   (``collective_bytes`` on the mesh step) behave as documented.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.analysis import jaxpr as gi
from paddle_tpu.analysis.jaxpr import opt as gopt
from paddle_tpu.analysis.jaxpr import planner as gplanner

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _copy(a):
    return jax.tree_util.tree_map(
        lambda x: jnp.array(x) if isinstance(x, jax.Array) else x, a)


# --------------------------------------------------------------------------- #
# 1. per-rewrite fixtures
# --------------------------------------------------------------------------- #
class TestRewriteFixtures:
    def test_lossless_convert_roundtrip_eliminated_bit_exact(self):
        def f(x):
            y = x.astype(jnp.float32).astype(jnp.bfloat16)  # widen+back
            return y * 2

        x = jnp.linspace(-3, 3, 16).astype(jnp.bfloat16)
        fn = jax.jit(f)
        opt_fn, res = gopt.optimize_jitted(fn, (x,), name="rt")
        assert res.by_rule().get("convert-roundtrip", 0) == 1
        assert res.eqns_after < res.eqns_before
        assert gopt.bit_exact(fn(x), opt_fn(x))

    def test_lossy_roundtrip_kept_by_default(self):
        def f(x):
            return x.astype(jnp.bfloat16).astype(jnp.float32) + 1.0

        x = jnp.linspace(-3, 3, 16, dtype=jnp.float32)
        fn = jax.jit(f)
        opt_fn, res = gopt.optimize_jitted(fn, (x,), name="lossy")
        # f32 -> bf16 -> f32 truncates: eliminating it would CHANGE bits
        assert res.by_rule().get("convert-roundtrip", 0) == 0
        assert gopt.bit_exact(fn(x), opt_fn(x))
        # ... unless the caller explicitly opts into the bit-changing form
        _opt2, res2 = gopt.optimize_jitted(fn, (x,), name="lossy2",
                                           allow_lossy=True)
        assert res2.by_rule().get("convert-roundtrip", 0) == 1

    def test_cse_folds_duplicate_dots_bit_exact(self):
        def f(x, w):
            return jnp.dot(x, w) + jnp.dot(x, w)

        x, w = jnp.ones((8, 8)), jnp.full((8, 8), 0.5)
        fn = jax.jit(f)
        opt_fn, res = gopt.optimize_jitted(fn, (x, w), name="cse")
        assert res.by_rule().get("cse", 0) == 1
        assert res.eqns_after < res.eqns_before
        assert gopt.bit_exact(fn(x, w), opt_fn(x, w))

    def test_cse_matches_literal_operands(self):
        # the Adam bias-correction shape: same scalar literal, same var
        def f(s):
            return jnp.power(0.9, s) + jnp.power(0.9, s) * 2.0

        fn = jax.jit(f)
        opt_fn, res = gopt.optimize_jitted(fn, (jnp.float32(3.0),),
                                           name="cselit")
        assert res.by_rule().get("cse", 0) >= 1
        assert gopt.bit_exact(fn(jnp.float32(3.0)),
                              opt_fn(jnp.float32(3.0)))

    def test_dce_drops_dead_eqns(self):
        def f(x):
            _dead = jnp.exp(x) * 3.0  # noqa: F841 - traced but unused
            return x + 1.0

        fn = jax.jit(f)
        x = jnp.ones((4,))
        opt_fn, res = gopt.optimize_jitted(fn, (x,), name="dce")
        assert res.by_rule().get("dce", 0) >= 1
        assert res.eqns_after < res.eqns_before
        assert gopt.bit_exact(fn(x), opt_fn(x))

    def test_outline_folds_elementwise_chain(self):
        def f(x):
            y = jnp.tanh(x * 2.0 + 1.0)
            z = jnp.exp(-y) * y
            return jnp.sum(z)

        fn = jax.jit(f)
        x = jnp.linspace(0, 1, 32)
        opt_fn, res = gopt.optimize_jitted(fn, (x,), name="outline")
        assert res.by_rule().get("outline", 0) >= 1
        assert res.regions_after < res.regions_before
        assert gopt.bit_exact(fn(x), opt_fn(x))

    def test_sharding_coalesce_burns_gi004_disagreement(self, mesh8):
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        mesh = Mesh(np.array(mesh8), ("dp",))

        def f(x):
            a = jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P("dp")))
            b = jax.lax.with_sharding_constraint(
                x * 1.0, NamedSharding(mesh, P(None)))
            return a + b

        x = jnp.arange(16, dtype=jnp.float32)
        fn = jax.jit(f)
        prog = gi.trace(fn, (x,), "coalesce")
        before = gi.analyze_program(prog, [gi.PASSES_BY_ID["GI004"]])
        assert any("disagreeing shardings" in f_.message for f_ in before)
        oprog, res = gopt.optimize_program(prog)
        assert res.by_rule().get("sharding-coalesce", 0) >= 1
        after = [f_ for f_ in gi.analyze_program(
            oprog, [gi.PASSES_BY_ID["GI004"]])
            if "disagreeing" in f_.message]
        assert after == []
        opt_fn, _ = gopt.optimize_jitted(fn, (x,), name="coalesce")
        assert gopt.bit_exact(fn(x), opt_fn(x))

    def test_collectives_survive_rewrites(self, mesh8):
        """A shard_map psum program must keep its collective (never
        CSE'd/outlined/DCE'd away) and stay GI001-clean optimized."""
        from jax.sharding import Mesh, PartitionSpec as P

        mesh = Mesh(np.array(mesh8), ("dp",))

        def body(x):
            return jax.lax.psum(x * 2.0, "dp") + 1.0

        fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P("dp"),),
                                   out_specs=P(), check_vma=False))
        x = jnp.arange(16, dtype=jnp.float32)
        prog = gi.trace(fn, (x,), "coll")
        oprog, _res = gopt.optimize_program(prog)
        from paddle_tpu.analysis.jaxpr import collectives as coll

        assert coll.census_jaxpr(oprog.jaxpr).get("all_reduce", 0) >= 1
        assert gi.analyze_program(
            oprog, [gi.PASSES_BY_ID["GI001"]]) == []
        opt_fn, _ = gopt.optimize_jitted(fn, (x,), name="coll")
        assert gopt.bit_exact(fn(x), opt_fn(x))


# --------------------------------------------------------------------------- #
# 2. flagship fusion parity
# --------------------------------------------------------------------------- #
class TestFlagshipFusion:
    @pytest.mark.parametrize("name", ["serving.mixed_step",
                                      "serving.decode_burst"])
    def test_serving_program_optimizes_bit_exact(self, name):
        prog, fn, args = gi.build_program(name, with_callable=True)
        opt_fn, res = gopt.optimize_jitted(fn, _copy(args), name=name)
        assert gopt.bit_exact(fn(*_copy(args)), opt_fn(*_copy(args)))
        assert res.regions_after < res.regions_before
        oprog, _ = gopt.optimize_program(prog)
        assert gi.analyze_program(oprog, list(gi.ALL_PASSES)) == []

    def test_mesh_train_step_optimizes_bit_exact(self, mesh8):
        prog, fn, args = gi.build_program("mesh.train_step",
                                          with_callable=True)
        opt_fn, res = gopt.optimize_jitted(fn, _copy(args),
                                           name="mesh.train_step")
        assert gopt.bit_exact(fn(*_copy(args)), opt_fn(*_copy(args)))
        assert res.regions_after < res.regions_before
        oprog, _ = gopt.optimize_program(prog)
        assert gi.analyze_program(oprog, list(gi.ALL_PASSES)) == []

    def test_gi004_findings_on_flagships_are_zero(self, mesh8):
        """The ISSUE 12 burn-to-zero bar: GI004 (with the literal-aware
        duplicate detector) finds NOTHING on any flagship program, and
        both analysis baselines stay empty."""
        new, base, programs, errors = gi.analyze_flagship(
            passes=[gi.PASSES_BY_ID["GI004"]])
        assert errors == {}
        assert new == [] and base == []
        assert len(gi.load_baseline()) == 0


# --------------------------------------------------------------------------- #
# 3. the budget-driven remat planner
# --------------------------------------------------------------------------- #
def _tiny_llama_pair(seed=0):
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    paddle.seed(seed)
    cfg = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=2, num_attention_heads=2,
                      num_key_value_heads=2, max_position_embeddings=32)
    m = LlamaForCausalLM(cfg)
    opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                parameters=m.parameters())
    return m, opt


def _llama_loss(model, ids, labels):
    loss, _ = model(ids, labels=labels)
    return loss


def _batch(seed=0):
    r = np.random.RandomState(seed)
    return (r.randint(0, 64, (8, 8)).astype("int64"),
            r.randint(0, 64, (8, 8, 1)).astype("int64"))


class TestRematPlanner:
    @pytest.fixture(scope="class")
    def drill(self, mesh8):
        """ONE planned DP=8 ZeRO-1 llama step under a forcing budget,
        shared by the drill assertions (each parallelize pays a real
        build)."""
        from paddle_tpu import mesh as pmesh

        ids, labels = _batch()
        peaks = {}
        for policy in ("none", "all"):
            m, o = _tiny_llama_pair()
            mp = pmesh.parallelize(
                m, o, _llama_loss, (ids, labels),
                config={"dp_degree": 8, "shard_optimizer": True,
                        "recompute_policy": policy})
            peaks[policy] = gi.estimate(gi.trace(
                mp._jitted, (mp._pv, mp._av, mp._mv, ids, labels),
                policy))["peak_bytes"]
        budget = (peaks["none"] + peaks["all"]) // 2
        m, o = _tiny_llama_pair()
        planned = pmesh.parallelize(
            m, o, _llama_loss, (ids, labels),
            config={"dp_degree": 8, "shard_optimizer": True,
                    "recompute_policy": "budget", "hbm_budget": budget})
        return {"peaks": peaks, "budget": budget, "planned": planned,
                "ids": ids, "labels": labels}

    def test_budget_below_peak_yields_fitting_plan(self, drill):
        plan = drill["planned"].remat_plan
        assert drill["budget"] < drill["peaks"]["none"]
        assert len(plan["sites"]) >= 1
        assert plan["planned_peak_bytes"] <= drill["budget"]
        # bytes-reduction: the planned program really shrinks the peak
        assert plan["planned_peak_bytes"] < plan["base_peak_bytes"]

    def test_measured_bytes_confirm_within_band(self, drill):
        mp = drill["planned"]
        meas = gi.measure_compiled(
            mp._jitted, (mp._pv, mp._av, mp._mv,
                         drill["ids"], drill["labels"]))
        ratio = mp.remat_plan["planned_peak_bytes"] / meas["peak_bytes"]
        assert abs(ratio - 1.0) <= 0.15, (mp.remat_plan, meas)

    def test_loss_parity_vs_unoptimized_step(self, drill):
        from paddle_tpu import mesh as pmesh

        ids, labels = drill["ids"], drill["labels"]
        m, o = _tiny_llama_pair()
        base = pmesh.parallelize(
            m, o, _llama_loss, (ids, labels),
            config={"dp_degree": 8, "shard_optimizer": True})
        got = [float(drill["planned"].step(ids, labels))
               for _ in range(3)]
        ref = [float(base.step(ids, labels)) for _ in range(3)]
        np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-5)

    def test_planner_is_deterministic(self, drill, mesh8):
        """Same model/batch/budget => same plan (fresh build)."""
        from paddle_tpu import mesh as pmesh

        ids, labels = drill["ids"], drill["labels"]
        m, o = _tiny_llama_pair()
        again = pmesh.parallelize(
            m, o, _llama_loss, (ids, labels),
            config={"dp_degree": 8, "shard_optimizer": True,
                    "recompute_policy": "budget",
                    "hbm_budget": drill["budget"]})
        assert again.remat_plan["sites"] == \
            drill["planned"].remat_plan["sites"]
        assert again.remat_plan["planned_peak_bytes"] == \
            drill["planned"].remat_plan["planned_peak_bytes"]

    def test_generous_budget_plans_zero_remat(self, mesh8):
        ids, labels = _batch()
        from paddle_tpu import mesh as pmesh

        m, o = _tiny_llama_pair()
        mp = pmesh.parallelize(
            m, o, _llama_loss, (ids, labels),
            config={"dp_degree": 8, "shard_optimizer": True,
                    "recompute_policy": "budget",
                    "hbm_budget": 1 << 30})
        assert mp.remat_plan["sites"] == []
        assert all(not layer._recompute
                   for _n, layer in gplanner.remat_candidates(m))

    def test_unsatisfiable_budget_raises_typed(self, mesh8):
        ids, labels = _batch()
        from paddle_tpu import mesh as pmesh

        m, o = _tiny_llama_pair()
        flags_before = [layer._recompute
                        for _n, layer in gplanner.remat_candidates(m)]
        with pytest.raises(gplanner.RematPlanError):
            pmesh.parallelize(
                m, o, _llama_loss, (ids, labels),
                config={"dp_degree": 8, "shard_optimizer": True,
                        "recompute_policy": "budget", "hbm_budget": 1})
        # a failed plan must not leave probe flags behind
        assert [layer._recompute
                for _n, layer in gplanner.remat_candidates(m)] \
            == flags_before

    def test_policy_all_and_none_endpoints(self, mesh8):
        ids, labels = _batch()
        from paddle_tpu import mesh as pmesh

        m, o = _tiny_llama_pair()
        mp = pmesh.parallelize(
            m, o, _llama_loss, (ids, labels),
            config={"dp_degree": 8, "shard_optimizer": True,
                    "recompute_policy": "all"})
        assert len(mp.remat_plan["sites"]) == 2
        assert all(layer._recompute
                   for _n, layer in gplanner.remat_candidates(m))
        m2, o2 = _tiny_llama_pair()
        mp2 = pmesh.parallelize(
            m2, o2, _llama_loss, (ids, labels),
            config={"dp_degree": 8, "shard_optimizer": True,
                    "recompute_policy": "none"})
        assert mp2.remat_plan["sites"] == []

    def test_model_config_declares_the_policy(self, mesh8):
        """LlamaConfig(recompute_policy=..., hbm_budget=...) is the
        declarative path — parallelize() picks it up with no config."""
        from paddle_tpu import mesh as pmesh
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

        ids, labels = _batch()
        paddle.seed(0)
        cfg = LlamaConfig(vocab_size=64, hidden_size=32,
                          intermediate_size=64, num_hidden_layers=2,
                          num_attention_heads=2, num_key_value_heads=2,
                          max_position_embeddings=32,
                          recompute_policy="all")
        m = LlamaForCausalLM(cfg)
        o = paddle.optimizer.Adam(learning_rate=1e-3,
                                  parameters=m.parameters())
        mp = pmesh.parallelize(m, o, _llama_loss, (ids, labels),
                               config={"dp_degree": 8})
        assert len(mp.remat_plan["sites"]) == 2


class TestModelPlanRemat:
    """The single-device (hapi Model / eager fit) planner path."""

    def _gpt_model(self, budget):
        from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

        paddle.seed(0)
        cfg = GPTConfig(vocab_size=64, hidden_size=32,
                        num_hidden_layers=2, num_attention_heads=4,
                        max_position_embeddings=32,
                        hidden_dropout_prob=0.0,
                        attention_probs_dropout_prob=0.0,
                        recompute_policy="budget", hbm_budget=budget)
        lm = GPTForCausalLM(cfg)

        class LossOnly(paddle.nn.Layer):
            def __init__(self, inner):
                super().__init__()
                self.inner = inner
                self.config = inner.config

            def forward(self, ids, labels):
                loss, _ = self.inner(ids, labels=labels)
                return loss

        return lm, LossOnly(lm)

    def test_fit_path_plans_once_and_trains(self):
        lm, net = self._gpt_model(budget=None)
        model = paddle.Model(net)
        optim = paddle.optimizer.Adam(learning_rate=1e-3,
                                      parameters=net.parameters())
        model.prepare(optimizer=optim, loss=None)
        r = np.random.RandomState(0)
        ids = r.randint(0, 64, (4, 8)).astype("int64")
        labels = r.randint(0, 64, (4, 8, 1)).astype("int64")
        # bracket the reachable range: generous budget reads the
        # no-remat base, an impossible one reports the full-remat floor
        plan0 = model.plan_remat([ids, labels], budget=1 << 30)
        assert plan0["sites"] == []
        with pytest.raises(gplanner.RematPlanError) as ei:
            model.plan_remat([ids, labels], budget=1)
        full_peak = ei.value.estimate
        assert full_peak < plan0["base_peak_bytes"]
        # ...then force a real plan at the midpoint
        budget = (plan0["base_peak_bytes"] + full_peak) // 2
        plan = model.plan_remat([ids, labels], budget=budget)
        assert plan["planned_peak_bytes"] <= budget
        assert len(plan["sites"]) >= 1
        flagged = [layer._recompute for layer in lm.gpt.h]
        assert any(flagged)
        # training proceeds with the plan applied
        out = model.train_batch([ids, labels])
        assert np.isfinite(out[0])

    def test_config_budget_auto_plans_on_first_batch(self):
        lm, net = self._gpt_model(budget=1 << 30)
        model = paddle.Model(net)
        optim = paddle.optimizer.Adam(learning_rate=1e-3,
                                      parameters=net.parameters())
        model.prepare(optimizer=optim, loss=None)
        r = np.random.RandomState(0)
        ids = r.randint(0, 64, (4, 8)).astype("int64")
        labels = r.randint(0, 64, (4, 8, 1)).astype("int64")
        assert model._remat_plan is None
        model.train_batch([ids, labels])
        assert model._remat_plan is not None
        n_traces = model._remat_plan["n_traces"]
        model.train_batch([ids, labels])  # plans exactly once
        assert model._remat_plan["n_traces"] == n_traces


# --------------------------------------------------------------------------- #
# 4. sanitize steady state on the optimized program
# --------------------------------------------------------------------------- #
class TestSanitizedSteadyState:
    def test_optimized_mesh_step_zero_postwarmup_recompiles(self, mesh8):
        """PADDLE_TPU_SANITIZE discipline on the OPTIMIZED program: the
        rebuilt (graftopt-rewritten, re-jitted) DP=8 ZeRO-1 train step
        with the Adam optimizer inside compiles ONCE and never again
        across steady-state steps — recompile sentinel armed, zero
        trips, state threaded through the donated outputs."""
        from paddle_tpu.analysis import sanitizers as san

        _prog, fn, args = gi.build_program("mesh.train_step",
                                           with_callable=True)
        opt_fn, _res = gopt.optimize_jitted(fn, _copy(args),
                                            name="mesh.train_step")
        pv, av, mv, ids, labels = _copy(args)
        loss, pv, av, mv = opt_fn(pv, av, mv, ids, labels)  # warm
        san.reset()
        san.enable("recompile", "hostsync")
        try:
            cache_before = opt_fn._raw._cache_size()
            losses = []
            for _ in range(3):
                loss, pv, av, mv = opt_fn(pv, av, mv, ids, labels)
                losses.append(float(jnp.asarray(loss)))
            assert opt_fn._raw._cache_size() == cache_before == 1, \
                "optimized step recompiled post-warmup"
            assert san.trips() == []
            assert all(np.isfinite(l) for l in losses)  # noqa: E741
        finally:
            san.reset()
            san.disable("recompile", "hostsync")


# --------------------------------------------------------------------------- #
# 5. CLI + byte census satellites
# --------------------------------------------------------------------------- #
class TestCollectiveBytes:
    def test_byte_census_prices_psum_payload(self, mesh8):
        from jax.sharding import Mesh, PartitionSpec as P

        from paddle_tpu.analysis.jaxpr import collectives as coll

        mesh = Mesh(np.array(mesh8), ("dp",))

        def body(x):
            return jax.lax.psum(x, "dp")

        fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P("dp"),),
                                   out_specs=P(), check_vma=False))
        x = jnp.zeros((8, 4), jnp.float32)
        prog = gi.trace(fn, (x,), "psum")
        census = coll.byte_census_jaxpr(prog.jaxpr)
        # per-device payload: the LOCAL (1, 4) f32 shard = 16 bytes
        assert census == {"all_reduce": {"count": 1, "bytes": 16}}

    def test_mesh_step_bytes_on_wire_surface(self, mesh8):
        from paddle_tpu import mesh as pmesh

        ids, labels = _batch()
        m, o = _tiny_llama_pair()
        mp = pmesh.parallelize(m, o, _llama_loss, (ids, labels),
                               config={"dp_degree": 8,
                                       "shard_optimizer": True})
        bts = mp.collective_bytes(ids, labels)
        assert bts["reduce_scatter"]["count"] >= 1
        assert bts["reduce_scatter"]["bytes"] > 0
        assert bts["all_gather"]["bytes"] > 0
        # the span surface: a traced step stamps <coll>_bytes attrs
        from paddle_tpu.monitor import trace as mtrace

        was = mtrace.enabled()
        mtrace.enable()
        try:
            mp.step(ids, labels)
            spans = [s for s in mtrace.spans()
                     if s.name == "comm.mesh_step"]
            assert spans
            attrs = spans[-1].attrs
            assert attrs.get("reduce_scatter_bytes", 0) > 0
            assert attrs.get("all_gather_bytes", 0) > 0
        finally:
            if not was:
                mtrace.disable()


class TestCLI:
    def _env(self):
        env = dict(os.environ)
        flags = env.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            env["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8")
        env["JAX_PLATFORMS"] = "cpu"
        return env

    @pytest.mark.slow
    def test_module_cli_optimize_json(self):
        p = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.analysis.jaxpr",
             "--optimize", "--json", "--programs",
             "serving.decode_burst"],
            capture_output=True, text=True, timeout=420,
            env=self._env(), cwd=ROOT)
        assert p.returncode == 0, p.stdout + p.stderr
        doc = json.loads(p.stdout)
        assert doc["ok"] is True
        (row,) = doc["optimize"]
        assert row["program"] == "serving.decode_burst"
        assert sum(row["rewrites"].values()) >= 1
        assert row["regions"][1] < row["regions"][0]
        assert row["findings"] == []

    @pytest.mark.slow
    def test_ir_report_optimize_table(self):
        p = subprocess.run(
            [sys.executable, os.path.join(ROOT, "tools", "ir_report.py"),
             "--optimize", "--programs", "serving.decode_burst"],
            capture_output=True, text=True, timeout=420,
            env=self._env(), cwd=ROOT)
        assert p.returncode == 0, p.stdout + p.stderr
        assert "graftopt:" in p.stdout
        assert "serving.decode_burst" in p.stdout
        assert "[outline]" in p.stdout

    def test_checks_rows_include_opt_parity(self, mesh8):
        rows = gi.static_check_rows()
        names = [r["check"] for r in rows]
        assert names == ["check_collective_consistency", "check_donation",
                         "check_hbm_budgets", "check_precision_flow",
                         "check_numeric_hazards", "check_opt_parity"]
        parity = rows[-1]
        assert parity["ok"], parity["detail"]
        assert set(parity["rewrites"]) == set(gi.FLAGSHIP)


class TestOptimizerHoist:
    def test_adam_bias_correction_hoisted_and_bit_identical(self):
        """The in-tree GI004 burn: ONE pow pair per fused apply, and the
        update numerically identical to the per-param form (same ops,
        same order)."""
        paddle.seed(0)
        import paddle_tpu.nn as nn

        lin = nn.Linear(8, 8)
        opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                    parameters=lin.parameters())
        x = paddle.to_tensor(np.ones((4, 8), np.float32))
        y = lin(x).sum()
        y.backward()
        opt.step()
        # the fused apply's jaxpr carries exactly one pow per beta
        (fn,) = list(opt._jit_cache.values())
        state = {"moment1": jnp.zeros((8, 8), jnp.float32),
                 "moment2": jnp.zeros((8, 8), jnp.float32)}
        closed = jax.make_jaxpr(fn.__wrapped__)(
            [jnp.ones((8, 8))] * 2, [jnp.ones((8, 8))] * 2,
            [state, state], [None, None], jnp.float32(0.01),
            jnp.float32(1.0))

        def count_pows(jaxpr):
            from paddle_tpu.analysis.jaxpr import collectives as coll

            n = sum(1 for e in jaxpr.eqns if e.primitive.name == "pow")
            for e in jaxpr.eqns:
                for _s, sub in coll.iter_subjaxprs(e):
                    n += count_pows(sub)
            return n

        assert count_pows(closed.jaxpr) == 2  # one per beta, not per param
