"""graftir (paddle_tpu/analysis/jaxpr): the jaxpr-level static-analysis
gate, tier-1.

Five contracts under test:

1. the FLAGSHIP gate — the three live programs (serving mixed step,
   decode burst, DP=8 ZeRO-1 mesh train step) analyze clean under
   GI001–GI007 with an EMPTY baseline, and every flagship program has a
   budget row in the manifest;
2. every pass fires on its dirty traced fixture and stays silent on its
   clean one — branch-divergent psum (GI001), donated-unaliased /
   donated-read-after-alias / large-un-donated (GI002), budget
   over/under (GI003), convert churn / duplicate subexpression /
   disagreeing shardings (GI004), fp16 accumulation / downcast-sum-widen
   (GI005), raw-vs-stabilized softmax / eps-less rsqrt / fp16 dot
   overflow via the abstract value-range walk (GI006), unscaled fp16
   collective crossings and masterless committed state (GI007);
3. the GI003 estimator is held to the LIVE program: its per-device peak
   for the DP=8 ZeRO-1 llama step lands within 15% of the compiled
   executable's own memory analysis (the ISSUE 11 acceptance bar);
4. the machinery — baseline round-trip with multiset absorption, typed
   AnalysisError isolation (a crashing pass, and the ``ir.analyze``
   fault-point drill, must name program + pass, never fail opaquely);
5. the CLI surfaces behave as subprocesses (module CLI ``--json``
   contract, ``tools/ir_report.py`` without eager jax import).
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.analysis import faultinject as fi
from paddle_tpu.analysis import jaxpr as gi

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _pass(pid):
    return [gi.PASSES_BY_ID[pid]]


def _analyze(fn, args, pid, donate_argnums=None):
    new, _base, prog = gi.analyze_fn(fn, args, name=f"fixture.{pid}",
                                     passes=_pass(pid),
                                     donate_argnums=donate_argnums)
    return new, prog


class TestFlagshipGate:
    """The acceptance invariant: GI001-GI004 over all three flagship
    live programs with an empty finding set."""

    def test_flagship_programs_analyze_clean(self, mesh8):
        new, base, programs, errors = gi.analyze_flagship()
        assert errors == {}, errors
        assert sorted(programs) == sorted(gi.FLAGSHIP)
        assert base == []  # baseline is empty AND unused
        assert not new, "new graftir findings:\n" + "\n".join(
            repr(f) for f in new)

    def test_baseline_is_empty(self):
        assert len(gi.load_baseline()) == 0

    def test_budget_manifest_covers_flagship(self):
        budgets = gi.load_budgets()
        missing = set(gi.FLAGSHIP) - set(budgets)
        assert not missing, f"flagship programs without a budget: {missing}"
        assert all(b > 0 for b in budgets.values())


class TestGI001CollectiveConsistency:
    def _traced(self, fn, x, mesh8):
        from jax.sharding import Mesh, PartitionSpec as P

        mesh = Mesh(np.array(mesh8), ("dp",))
        sm = jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=(P("dp"),),
                                   out_specs=P("dp"), check_vma=False))
        return gi.trace(sm, (x,), "fixture.gi001")

    def test_branch_divergent_psum_fires(self, mesh8):
        from jax import lax

        def body(x):
            return lax.cond(x.sum() > 0,
                            lambda v: lax.psum(v, "dp"),
                            lambda v: v * 2.0, x)

        prog = self._traced(body, jnp.ones((8, 4)), mesh8)
        new = gi.analyze_program(prog, _pass("GI001"))
        assert len(new) == 1
        assert new[0].rule == "GI001"
        assert "diverges across cond branches" in new[0].message
        assert "all_reduce@dp" in new[0].message

    def test_matching_branches_are_silent(self, mesh8):
        from jax import lax

        def body(x):
            return lax.cond(x.sum() > 0,
                            lambda v: lax.psum(v * 2.0, "dp"),
                            lambda v: lax.psum(v + 1.0, "dp"), x)

        prog = self._traced(body, jnp.ones((8, 4)), mesh8)
        assert gi.analyze_program(prog, _pass("GI001")) == []

    def test_axis_mismatch_across_branches_fires(self, mesh8):
        from jax import lax
        from jax.sharding import Mesh, PartitionSpec as P

        mesh = Mesh(np.array(mesh8).reshape(4, 2), ("dp", "mp"))

        def body(x):
            return lax.cond(x.sum() > 0,
                            lambda v: lax.psum(v, "dp"),
                            lambda v: lax.psum(v, "mp"), x)

        sm = jax.jit(jax.shard_map(body, mesh=mesh,
                                   in_specs=(P("dp", "mp"),),
                                   out_specs=P("dp", "mp"),
                                   check_vma=False))
        prog = gi.trace(sm, (jnp.ones((8, 4)),), "fixture.gi001.axes")
        new = gi.analyze_program(prog, _pass("GI001"))
        assert len(new) == 1 and "diverges" in new[0].message

    def test_census_vocabulary_is_shared_with_trainer_spans(self):
        """Satellite 1: the HLO census the comm.mesh_step spans attach
        and GI001's jaxpr walk speak ONE vocabulary, from one module."""
        import importlib

        from paddle_tpu.analysis.jaxpr import collectives as coll

        par = importlib.import_module("paddle_tpu.mesh.parallelize")
        assert par._collectives is coll
        assert coll.census_hlo("all-reduce stablehlo.all_gather") == {
            "all_reduce": 1, "all_gather": 1}
        assert set(coll.COLLECTIVE_PRIMITIVES.values()) <= {
            "all_reduce", "all_gather", "reduce_scatter", "all_to_all",
            "collective_permute"}


class TestGI002DonationSafety:
    def test_donated_unaliased_fires(self):
        def f(a, b):
            return (a * b).sum()        # no output matches a's aval

        import warnings

        with warnings.catch_warnings():
            # jax itself warns about the unusable donation at lowering;
            # the POINT of this fixture is catching it statically
            warnings.simplefilter("ignore")
            jf = jax.jit(f, donate_argnums=(0,))
            new, _ = _analyze(jf, (jnp.ones((16, 16)), jnp.ones((16, 16))),
                              "GI002")
        assert len(new) == 1
        assert "aliases no output" in new[0].message

    def test_donated_read_after_alias_fires(self):
        def f(a, b):
            out = a * 2.0               # the aliasable successor of a
            late = (a + b).sum()        # a read AFTER out materializes
            return out, late

        jf = jax.jit(f, donate_argnums=(0,))
        new, _ = _analyze(jf, (jnp.ones((16, 16)), jnp.ones((16, 16))),
                          "GI002")
        assert len(new) == 1
        assert "read after every output it could alias" in new[0].message

    def test_large_undonated_state_fires(self):
        def f(small, big):
            return small + 1.0, big * 1.0   # big flows through un-donated

        jf = jax.jit(f, donate_argnums=(0,))
        new, _ = _analyze(jf, (jnp.ones((4,)), jnp.ones((512, 1024))),
                          "GI002")
        assert len(new) == 1
        assert "un-donated invar" in new[0].message

    def test_proper_donation_is_silent(self):
        def f(state, batch):
            new_state = state + batch.sum()
            return new_state, new_state.mean()

        jf = jax.jit(f, donate_argnums=(0,))
        new, _ = _analyze(jf, (jnp.ones((512, 1024)),
                               jnp.ones((1024,))), "GI002")
        assert new == []


class TestGI003HBM:
    def test_estimator_prices_simple_program(self):
        def f(x):
            return x + 1.0

        jf = jax.jit(f, donate_argnums=(0,))
        est = gi.estimate_fn(jf, (jnp.ones((1024, 1024), jnp.float32),),
                             name="simple")
        mb4 = 4 * 1024 * 1024
        # donated in-place add: between one buffer (greedy reuses the
        # donated operand) and two (program order holds both)
        assert mb4 <= est["peak_bytes"] <= 2 * mb4 + 4096
        assert est["args_bytes"] == mb4
        assert est["donated_bytes"] == mb4
        assert est["peak_sched_bytes"] <= est["peak_bytes"] \
            <= est["peak_order_bytes"]

    def test_budget_over_under(self):
        def f(x):
            return (x * 2.0).sum()

        jf = jax.jit(f)
        x = jnp.ones((256, 256))
        est = gi.assert_hbm_budget(jf, (x,), 10 << 20, name="under")
        assert est["peak_bytes"] > 0
        with pytest.raises(gi.HBMBudgetExceeded) as ei:
            gi.assert_hbm_budget(jf, (x,), 1024, name="over")
        assert ei.value.program == "over"
        assert ei.value.estimate > ei.value.budget == 1024

    def test_manifest_gate_fires_on_shrunk_budget(self, mesh8):
        prog = gi.build_program("serving.decode_burst")
        tight = gi.HBMBudget(budgets={"serving.decode_burst": 1})
        new = tight.check(prog)
        assert len(new) == 1 and "exceeds the declared budget" in \
            new[0].message
        roomy = gi.HBMBudget(budgets={"serving.decode_burst": 1 << 30})
        assert roomy.check(prog) == []

    def test_mesh_step_estimate_within_15pct_of_measured(self, mesh8):
        """THE acceptance bar: GI003's per-device peak for the DP=8
        ZeRO-1 llama step vs the compiled executable's own memory
        analysis (arguments + temps + outputs − donation-aliased)."""
        prog, fn, args = gi.build_program("mesh.train_step",
                                          with_callable=True)
        est = gi.estimate(prog)
        meas = gi.measure_compiled(fn, args)
        assert meas["peak_bytes"] > 0
        rel = abs(est["peak_bytes"] - meas["peak_bytes"]) \
            / meas["peak_bytes"]
        assert rel <= 0.15, (
            f"estimate {est['peak_bytes']} vs measured "
            f"{meas['peak_bytes']} ({rel:.1%} off)\n{est}\n{meas}")
        # the schedule bracket must actually bracket the measurement
        assert est["peak_sched_bytes"] <= meas["peak_bytes"] \
            <= est["peak_order_bytes"] * 1.05

    def test_args_bytes_match_live_state_bytes(self, mesh8):
        """The estimator's per-device argument pricing vs the REAL
        jax.Array shards: ZeRO rows at 1/dp, replicated params whole."""
        prog, _fn, args = gi.build_program("mesh.train_step",
                                           with_callable=True)
        est = gi.estimate(prog)
        state_leaves = [v for v in jax.tree_util.tree_leaves(args[:3])]
        per_device = 0
        for v in state_leaves:
            sh = v.sharding.shard_shape(v.shape)
            per_device += int(np.prod(sh)) * v.dtype.itemsize
        # batch args are host numpy (priced global) — tolerate their
        # small contribution in the comparison
        batch_bytes = sum(int(np.prod(b.shape)) * b.dtype.itemsize
                          for b in args[3:])
        assert abs(est["args_bytes"] - per_device - batch_bytes) \
            <= batch_bytes + 1024


class TestGI004Fusion:
    def test_convert_churn_fires(self):
        def f(x):
            return x.astype(jnp.bfloat16).astype(jnp.float32) * x

        new, _ = _analyze(jax.jit(f), (jnp.ones((8, 8), jnp.float32),),
                          "GI004")
        assert len(new) == 1
        assert "convert round-trip" in new[0].message

    def test_duplicate_subexpression_fires(self):
        def f(a):
            return jnp.exp(a) + jnp.exp(a)

        new, _ = _analyze(jax.jit(f), (jnp.ones((8, 8)),), "GI004")
        assert len(new) == 1
        assert "duplicated subexpression: exp" in new[0].message

    def test_disagreeing_shardings_fire(self, mesh8):
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        mesh = Mesh(np.array(mesh8), ("dp",))

        def f(a, b):
            a = jax.lax.with_sharding_constraint(
                a, NamedSharding(mesh, P("dp", None)))
            b = jax.lax.with_sharding_constraint(
                b, NamedSharding(mesh, P(None, "dp")))
            return a + b

        new, _ = _analyze(jax.jit(f), (jnp.ones((8, 8)),
                                       jnp.ones((8, 8))), "GI004")
        assert len(new) == 1
        assert "disagreeing shardings" in new[0].message
        assert "mesh_reshards_total" in new[0].message

    def test_straight_line_compute_is_silent(self):
        def f(a, b):
            h = jnp.tanh(a @ b)
            return (h * a).sum()

        new, _ = _analyze(jax.jit(f), (jnp.ones((8, 8)),
                                       jnp.ones((8, 8))), "GI004")
        assert new == []


class TestGI005PrecisionFlow:
    def _dot(self, acc):
        return lambda a, b: jax.lax.dot_general(
            a, b, (((1,), (0,)), ((), ())), preferred_element_type=acc)

    def test_fp16_dot_accumulation_fires(self):
        a = jnp.ones((64, 2048), jnp.float16)
        b = jnp.ones((2048, 64), jnp.float16)
        prog = gi.trace(self._dot(jnp.float16), (a, b), "fixture.GI005")
        new = gi.analyze_program(prog, _pass("GI005"))
        assert len(new) == 1
        assert "dot_general accumulates in float16" in new[0].message
        assert "2048 contracted elements" in new[0].message

    def test_fp32_accumulating_dot_is_silent(self):
        a = jnp.ones((64, 2048), jnp.float16)
        b = jnp.ones((2048, 64), jnp.float16)
        prog = gi.trace(self._dot(jnp.float32), (a, b), "fixture.GI005")
        assert gi.analyze_program(prog, _pass("GI005")) == []

    def test_fp16_reduce_sum_over_large_axis_fires(self):
        # jnp.sum upcasts fp16 internally; bind the primitive directly
        # for a true reduced-precision accumulation
        def f(x):
            return jax.lax.reduce_sum_p.bind(x, axes=(1,))

        prog = gi.trace(f, (jnp.ones((8, 2048), jnp.float16),),
                        "fixture.GI005")
        new = gi.analyze_program(prog, _pass("GI005"))
        assert len(new) == 1
        assert "reduce_sum accumulates in float16" in new[0].message

    def test_small_axis_fp16_sum_is_silent(self):
        def f(x):
            return jax.lax.reduce_sum_p.bind(x, axes=(1,))

        prog = gi.trace(f, (jnp.ones((8, 16), jnp.float16),),
                        "fixture.GI005")
        assert gi.analyze_program(prog, _pass("GI005")) == []

    def test_downcast_sum_widen_fires(self):
        """f32 -> f16 -> sum whose result flows wide again: the downcast
        bought nothing but the accumulation error."""
        def f(x):
            return jnp.sum(x.astype(jnp.float16), axis=1)

        prog = gi.trace(f, (jnp.ones((8, 2048), jnp.float32),),
                        "fixture.GI005")
        new = gi.analyze_program(prog, _pass("GI005"))
        assert len(new) == 1
        assert "downcast float32 -> float16 feeds a reduce_sum" \
            in new[0].message

    def test_upcast_before_sum_is_silent(self):
        def f(x):
            return jnp.sum(x.astype(jnp.float32), axis=1)

        prog = gi.trace(f, (jnp.ones((8, 2048), jnp.float16),),
                        "fixture.GI005")
        assert gi.analyze_program(prog, _pass("GI005")) == []


class TestGI006NumericHazard:
    def _count(self, fn, args):
        prog = gi.trace(fn, args, "fixture.GI006")
        return gi.analyze_program(prog, _pass("GI006"))

    def test_raw_softmax_fires_exp_and_div(self):
        def raw_softmax(x):
            e = jnp.exp(x)
            return e / jnp.sum(e, axis=-1, keepdims=True)

        new = self._count(raw_softmax, (jnp.ones((4, 128), jnp.float16),))
        assert len(new) == 2
        msgs = " | ".join(f.message for f in new)
        assert "exp over values that may reach" in msgs
        assert "div by a reduced-precision-derived denominator" in msgs
        # f32 input: the div denominator is full-precision, only the
        # unshifted exp remains hazardous
        new32 = self._count(raw_softmax, (jnp.ones((4, 128), jnp.float32),))
        assert len(new32) == 1
        assert "exp over values that may reach" in new32[0].message

    def test_stabilized_softmax_is_silent(self):
        """jax.nn.softmax max-shifts: the range walk must see exp fed
        values in [-inf, 0] and a denominator with a sum floor."""
        for dt in (jnp.float32, jnp.float16):
            assert self._count(lambda x: jax.nn.softmax(x, axis=-1),
                               (jnp.ones((4, 128), dt),)) == []

    def test_logsumexp_guard_is_silent(self):
        assert self._count(lambda x: jax.nn.logsumexp(x, axis=-1),
                           (jnp.ones((4, 128), jnp.float32),)) == []

    def test_rsqrt_without_eps_fires_with_eps_silent(self):
        def rms_noeps(x):
            return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1,
                                              keepdims=True))

        def rms_eps(x):
            return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1,
                                              keepdims=True) + 1e-5)

        x = jnp.ones((4, 64), jnp.float16)
        new = self._count(rms_noeps, (x,))
        assert len(new) == 1
        assert "rsqrt over reduced-precision-derived values" \
            in new[0].message
        assert self._count(rms_eps, (x,)) == []

    def test_log_without_eps_fires_with_eps_silent(self):
        x = jnp.ones((4, 8), jnp.float16)
        new = self._count(lambda v: jnp.log(jnp.sum(v * v, axis=-1)),
                          (x,))
        assert len(new) == 1
        assert "log over reduced-precision-derived values" \
            in new[0].message
        assert self._count(
            lambda v: jnp.log(jnp.sum(v * v, axis=-1) + 1e-6), (x,)) == []

    def test_fp16_dot_output_bound_fires_only_when_it_can_overflow(self):
        def dot16(a, b):
            return jax.lax.dot_general(
                a, b, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float16)

        # unbounded f16 operands over K=4096: bound 65504*4096 >> 65504
        new = self._count(dot16, (jnp.ones((8, 4096), jnp.float16),
                                  jnp.ones((4096, 8), jnp.float16)))
        assert len(new) == 1
        assert "static output bound" in new[0].message
        # softmax @ tanh: both operands in [-1, 1], bound K=64 — clean
        def bounded(a, b):
            return dot16(jax.nn.softmax(a, axis=-1), jnp.tanh(b))

        assert self._count(bounded, (jnp.ones((8, 64), jnp.float16),
                                     jnp.ones((64, 8), jnp.float16))) == []


class TestGI007LossScaleCoverage:
    def _psum(self, mesh8, fn, args, in_specs):
        from jax.sharding import Mesh, PartitionSpec as P

        mesh = Mesh(np.array(mesh8), ("dp",))
        sm = jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                           out_specs=P("dp"), check_vma=False)
        return gi.trace(sm, args, "fixture.GI007")

    def test_unscaled_fp16_psum_fires(self, mesh8):
        from jax.sharding import PartitionSpec as P

        prog = self._psum(mesh8, lambda t: jax.lax.psum(t, "dp"),
                          (jnp.ones((8, 16), jnp.float16),), (P("dp"),))
        new = gi.analyze_program(prog, _pass("GI007"))
        assert len(new) == 1
        assert "float16 value crosses collective all_reduce" \
            in new[0].message

    def test_scaled_fp16_psum_is_silent(self, mesh8):
        from jax.sharding import PartitionSpec as P

        def scaled(t, s):
            return jax.lax.psum(t * s.astype(jnp.float16), "dp")

        prog = self._psum(mesh8, scaled,
                          (jnp.ones((8, 16), jnp.float16),
                           jnp.float32(1024.0)), (P("dp"), P()))
        assert gi.analyze_program(prog, _pass("GI007")) == []

    def test_bf16_psum_is_exempt(self, mesh8):
        from jax.sharding import PartitionSpec as P

        prog = self._psum(mesh8, lambda t: jax.lax.psum(t, "dp"),
                          (jnp.ones((8, 16), jnp.bfloat16),), (P("dp"),))
        assert gi.analyze_program(prog, _pass("GI007")) == []

    def test_fp16_state_without_master_copy_fires(self):
        def step(p, g):
            return p - jnp.float16(0.01) * g

        prog = gi.trace(step, (jnp.ones((16,), jnp.float16),
                               jnp.ones((16,), jnp.float16)),
                        "fixture.GI007", donate_argnums=(0,))
        new = gi.analyze_program(prog, _pass("GI007"))
        assert len(new) == 1
        assert "no fp32 master copy" in new[0].message

    def test_fp16_state_from_fp32_master_is_silent(self):
        def step(p, g):
            return (p.astype(jnp.float32)
                    - 0.01 * g.astype(jnp.float32)).astype(jnp.float16)

        prog = gi.trace(step, (jnp.ones((16,), jnp.float16),
                               jnp.ones((16,), jnp.float16)),
                        "fixture.GI007", donate_argnums=(0,))
        assert gi.analyze_program(prog, _pass("GI007")) == []


class TestBaselineAndIsolation:
    def test_baseline_round_trip(self, tmp_path):
        def f(a):
            return jnp.exp(a) + jnp.exp(a)

        new, _ = _analyze(jax.jit(f), (jnp.ones((4,)),), "GI004")
        assert len(new) == 1
        path = tmp_path / "ir_baseline.json"
        gi.write_baseline(str(path), new)
        again = gi.analyze_program(
            gi.trace(jax.jit(f), (jnp.ones((4,)),), "fixture.GI004"),
            _pass("GI004"))
        now_new, now_base = gi.partition_findings(
            again, gi.load_baseline(str(path)))
        assert now_new == [] and len(now_base) == 1

    def test_baseline_is_a_multiset(self, tmp_path):
        """A second identical violation next to a baselined one still
        reports as new — same semantics as the lint baseline."""
        def one(a):
            return jnp.exp(a) + jnp.exp(a)

        def two(a):
            return jnp.exp(a) + jnp.exp(a) + jnp.exp(a)

        new1, _ = _analyze(jax.jit(one), (jnp.ones((4,)),), "GI004")
        path = tmp_path / "ir_baseline.json"
        gi.write_baseline(str(path), new1)
        # `two` produces TWO duplicate findings with the same
        # fingerprint; the single grandfathered entry absorbs only one
        prog = gi.trace(jax.jit(two), (jnp.ones((4,)),), "fixture.GI004")
        found = gi.analyze_program(prog, _pass("GI004"))
        assert len(found) == 2
        now_new, now_base = gi.partition_findings(
            found, gi.load_baseline(str(path)))
        assert len(now_base) == 1 and len(now_new) == 1

    def test_fingerprint_is_location_free(self):
        f = gi.IRFinding("GI004", "p", "scan[3].jaxpr[0]", "msg")
        g = gi.IRFinding("GI004", "p", "scan[9].jaxpr[0]", "msg")
        assert f.fingerprint == g.fingerprint
        assert "scan[3]" not in f.fingerprint

    def test_crashing_pass_raises_typed_analysis_error(self):
        class Bomb(gi.IRPass):
            id = "GI999"
            name = "bomb"

            def check(self, program):
                raise ValueError("boom")

        prog = gi.trace(jax.jit(lambda x: x + 1), (jnp.ones((4,)),),
                        "victim")
        with pytest.raises(gi.AnalysisError) as ei:
            gi.analyze_program(prog, [Bomb()])
        assert ei.value.program == "victim"
        assert ei.value.pass_id == "GI999"
        assert "boom" in str(ei.value)

    def test_ir_analyze_fault_point_drills_isolation(self):
        """The ir.analyze drill: an injected fault mid-analysis must
        surface as the SAME typed AnalysisError naming the program —
        never an opaque build failure."""
        fi.reset()
        fi.arm("ir.analyze", action="raise")
        try:
            prog = gi.trace(jax.jit(lambda x: x * 2), (jnp.ones((4,)),),
                            "drilled")
            with pytest.raises(gi.AnalysisError) as ei:
                gi.analyze_program(prog, list(gi.ALL_PASSES))
            assert ei.value.program == "drilled"
            assert "injected fault" in str(ei.value)
            assert fi.trips() == [("ir.analyze", "raise")]
        finally:
            fi.reset()

    def test_trace_failure_is_typed(self):
        def broken(x):
            raise RuntimeError("cannot even trace")

        with pytest.raises(gi.AnalysisError) as ei:
            gi.trace(broken, (jnp.ones((4,)),), "untraceable")
        assert ei.value.program == "untraceable"


class TestCLISurfaces:
    def _env(self):
        env = dict(os.environ)
        flags = env.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            env["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8")
        env["JAX_PLATFORMS"] = "cpu"
        return env

    def _run(self, *cmd, timeout=420):
        return subprocess.run([sys.executable, *cmd], cwd=ROOT,
                              capture_output=True, text=True,
                              timeout=timeout, env=self._env())

    def test_module_cli_json_contract(self):
        """`python -m paddle_tpu.analysis.jaxpr --json`: exit 0 on the
        shipped tree with a clean report and the HBM row under budget.
        (One program keeps the subprocess inside the tier-1 budget; the
        all-programs sweep runs in-process in TestFlagshipGate and as a
        subprocess via the run_static_checks aggregator test.)"""
        p = self._run("-m", "paddle_tpu.analysis.jaxpr", "--json",
                      "--programs", "serving.mixed_step")
        assert p.returncode == 0, p.stderr[-800:]
        report = json.loads(p.stdout)
        assert report["ok"] is True
        assert report["findings"] == []
        assert report["errors"] == {}
        assert report["programs"] == ["serving.mixed_step"]
        (row,) = report["hbm"]
        assert row["program"] == "serving.mixed_step"
        assert 0 < row["peak_bytes"] <= row["budget_bytes"]

    def test_module_cli_rejects_unknown_names(self):
        p = self._run("-m", "paddle_tpu.analysis.jaxpr", "--programs",
                      "nope", timeout=120)
        assert p.returncode == 2
        assert "unknown program" in p.stderr
        p = self._run("-m", "paddle_tpu.analysis.jaxpr", "--passes",
                      "GI999", timeout=120)
        assert p.returncode == 2
        assert "unknown pass" in p.stderr

    def test_ir_report_shim(self):
        """tools/ir_report.py: no eager jax import (instant --help), and
        the default report prints the HBM table for a program subset."""
        p = self._run("tools/ir_report.py", "--help", timeout=30)
        assert p.returncode == 0
        assert "does NOT import jax eagerly" in p.stdout
        p = self._run("tools/ir_report.py", "--programs",
                      "serving.decode_burst")
        assert p.returncode == 0, p.stderr[-800:]
        assert "serving.decode_burst" in p.stdout
        assert "graftir: 0 finding(s)" in p.stdout
