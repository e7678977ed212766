"""graftpilot: the closed-loop control plane (paddle_tpu/control/, ISSUE 18).

The acceptance bars:

- KNOBS: every actuated knob has a declared KNOB_BOUNDS row; ``set()``
  clamps to [min, max], limits one decision's step to ``slew`` and
  quantizes integer knobs; an undeclared name is a constructor-time
  ValueError; a raising setter HOLDS the tracked value so controller
  state never diverges from the live system;
- RULES: deterministic functions of (telemetry, knobs) — autoscale from
  queue depth + SLO burn with scale-down hysteresis, hedge threshold
  from the live TTFT tail behind a deadband, chunk_size from the /perfz
  queue-wait component, decode_burst K from the arrival rate, and the
  HBM guard's one-shot re-plan + admission shrink/recover;
- REPLAY: a recorded telemetry stream fed through FRESH rules and
  shadow knobs reproduces the bit-identical decision sequence —
  including failure ticks — and a tampered rule set visibly diverges;
- FAIL-STATIC (the control.tick / control.actuate drills): a failing
  tick is an ``error`` decision, ``max_failures`` consecutive failures
  degrade the controller to the static configuration with every knob
  held, ``enable()`` re-arms; a failed actuation never moves the knob;
- OBSERVABILITY: /controlz carries the decision record, /statusz the
  controller section, flight dumps the compact section, and
  tools/obs_probe.py surfaces the controller summary;
- SERVING WIRING: burn-aware routing stays least-inflight with the
  flag OFF (the regression pin) and deprioritizes — never excludes —
  an alerting replica with it on; engine knobs stage at step
  boundaries; ``build_serving_controller`` actuates a live fleet.
"""
import json
import os
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.analysis import faultinject as fi
from paddle_tpu.analysis import sanitizers as san
from paddle_tpu.analysis.jaxpr.planner import make_replan_hook
from paddle_tpu.control import (KNOB_BOUNDS, AutoscaleRule, BurstRule,
                                ChunkRule, Controller, HbmGuardRule,
                                HedgeRule, Knob, build_serving_controller,
                                decision_sequence, fleet_telemetry, replay,
                                serving_rules)
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.serving import ContinuousBatchingEngine
from paddle_tpu.monitor import server as obs
from paddle_tpu.monitor import trace
from paddle_tpu.monitor.slo import SLOTracker, serving_objectives
from paddle_tpu.serving import FleetRouter


@pytest.fixture(autouse=True)
def _clean():
    fi.reset()
    yield
    obs.shutdown()
    fi.reset()
    san.disable()
    san.reset()
    monitor.disable()
    monitor.reset()
    trace.disable()
    trace.reset()


_MODEL = None


def _model():
    global _MODEL
    if _MODEL is None:
        paddle.seed(0)
        cfg = LlamaConfig(vocab_size=96, hidden_size=64,
                          intermediate_size=176, num_hidden_layers=2,
                          num_attention_heads=4, num_key_value_heads=2,
                          max_position_embeddings=128)
        _MODEL = LlamaForCausalLM(cfg)
    return _MODEL


def _fleet(model, replicas=2, start=True, **kw):
    ekw = dict(max_batch=2, block_size=8, chunk_size=16, decode_burst=1)
    ekw.update(kw.pop("engine_kwargs", {}))
    kw.setdefault("max_new_tokens", 6)
    return FleetRouter(model, replicas=replicas, engine_kwargs=ekw,
                       start=start, **kw)


# --------------------------------------------------------------------------- #
# knobs: declared bounds, clamping, slew limiting
# --------------------------------------------------------------------------- #

class TestKnobs:
    def test_undeclared_name_is_a_constructor_error(self):
        with pytest.raises(ValueError, match="undeclared knob"):
            Knob("fleet.bogus", 1)

    def test_bounds_table_is_sane(self):
        """The in-process mirror of the check_control_bounds CI row."""
        for name, spec in KNOB_BOUNDS.items():
            assert spec["min"] < spec["max"], name
            assert spec["slew"] > 0, name

    def test_set_clamps_then_slew_limits(self):
        k = Knob("engine.chunk_size", 16)
        # target far above max: clamp to 4096, then one slew step up
        assert k.set(10_000) == (16, 272)
        assert k.value == 272
        # target far below min: clamp to 8, then one slew step down
        assert k.set(0) == (272, 16)

    def test_integer_knob_quantizes_and_floats_stay_floats(self):
        r = Knob("fleet.replicas", 2)
        assert r.set(2.6) == (2, 3)
        assert isinstance(r.value, int)
        h = Knob("fleet.hedge_after_s", 0.5)
        old, new = h.set(0.6)
        assert new == pytest.approx(0.6)
        assert isinstance(h.value, float)

    def test_noop_decision_does_not_call_the_setter(self):
        calls = []
        k = Knob("engine.max_queue", 64, setter=calls.append)
        assert k.set(64) == (64, 64)
        # a sub-quantum integer move is also a no-op
        assert Knob("fleet.replicas", 2).propose(2.4) == 2
        assert calls == []

    def test_raising_setter_holds_the_tracked_value(self):
        def boom(v):
            raise RuntimeError("actuator offline")
        k = Knob("engine.max_queue", 64, setter=boom)
        with pytest.raises(RuntimeError):
            k.set(32)
        assert k.value == 64      # never diverges from the live system

    def test_propose_predicts_set(self):
        k = Knob("engine.decode_burst", 2)
        for target in (0, 1, 3, 5, 9, 100):
            want = k.propose(target)
            assert k.set(target)[1] == want


# --------------------------------------------------------------------------- #
# rules: deterministic telemetry -> proposal functions
# --------------------------------------------------------------------------- #

def _shadow(**values):
    return {n: Knob(n, v) for n, v in values.items()}


class TestRules:
    def test_autoscale_up_on_queue_depth(self):
        r = AutoscaleRule()
        out = r.evaluate({"replicas_active": 2, "replicas_total": 4,
                          "queue_depth": 20}, _shadow())
        assert out == [{"knob": "fleet.replicas", "target": 3,
                        "reason": out[0]["reason"]}]
        assert "queue depth" in out[0]["reason"]

    def test_autoscale_up_on_slo_burn(self):
        r = AutoscaleRule()
        out = r.evaluate({"replicas_active": 1, "replicas_total": 3,
                          "queue_depth": 0, "slo_alerting": ["ttft"]},
                         _shadow())
        assert out[0]["target"] == 2
        assert "slo burn" in out[0]["reason"]

    def test_autoscale_capped_at_fleet_size(self):
        r = AutoscaleRule()
        assert r.evaluate({"replicas_active": 3, "replicas_total": 3,
                           "queue_depth": 99}, _shadow()) == []

    def test_autoscale_down_needs_consecutive_quiet_ticks(self):
        r = AutoscaleRule(low_for=3)
        quiet = {"replicas_active": 2, "replicas_total": 2,
                 "queue_depth": 0}
        assert r.evaluate(quiet, _shadow()) == []
        assert r.evaluate(quiet, _shadow()) == []
        out = r.evaluate(quiet, _shadow())
        assert out[0]["target"] == 1
        # a busy tick in between resets the hysteresis counter
        r2 = AutoscaleRule(low_for=2)
        assert r2.evaluate(quiet, _shadow()) == []
        r2.evaluate({"replicas_active": 2, "replicas_total": 2,
                     "queue_depth": 4}, _shadow())
        assert r2.evaluate(quiet, _shadow()) == []

    def test_autoscale_never_below_one(self):
        r = AutoscaleRule(low_for=1)
        assert r.evaluate({"replicas_active": 1, "replicas_total": 2,
                           "queue_depth": 0}, _shadow()) == []

    def test_hedge_tracks_ttft_tail_behind_a_deadband(self):
        r = HedgeRule(factor=3.0, deadband=0.2)
        knobs = _shadow(**{"fleet.hedge_after_s": 1.0})
        # 3 x 350ms = 1.05s: within 20% of 1.0 -> jitter suppressed
        assert r.evaluate({"ttft_p95_ms": 350.0}, knobs) == []
        out = r.evaluate({"ttft_p95_ms": 2000.0}, knobs)
        assert out[0]["target"] == pytest.approx(6.0)

    def test_chunk_follows_queue_wait(self):
        r = ChunkRule(wait_high_ms=50.0, wait_low_ms=5.0)
        knobs = _shadow(**{"engine.chunk_size": 64})
        assert r.evaluate({"queue_wait_ms": 100.0}, knobs)[0]["target"] == 128
        assert r.evaluate({"queue_wait_ms": 1.0}, knobs)[0]["target"] == 32
        assert r.evaluate({"queue_wait_ms": 20.0}, knobs) == []
        assert r.evaluate({}, knobs) == []       # missing signal holds

    def test_burst_follows_arrival_rate(self):
        r = BurstRule(rate_high=50.0, rate_low=5.0, k_idle=8)
        knobs = _shadow(**{"engine.decode_burst": 4})
        assert r.evaluate({"arrival_rate_rps": 100.0}, knobs)[0]["target"] == 1
        assert r.evaluate({"arrival_rate_rps": 1.0}, knobs)[0]["target"] == 8
        assert r.evaluate({"arrival_rate_rps": 20.0}, knobs) == []

    def test_hbm_guard_replans_once_then_shrinks_then_recovers(self):
        r = HbmGuardRule(watermark=0.9, clear=0.6)
        knobs = _shadow(**{"engine.max_queue": 64})
        hot = {"hbm_live_bytes": 95, "hbm_budget_bytes": 100}
        cool = {"hbm_live_bytes": 10, "hbm_budget_bytes": 100}

        out = r.evaluate(hot, knobs)
        assert [p.get("action") for p in out] == ["replan", None]
        assert out[1]["target"] == 32
        knobs["engine.max_queue"].set(out[1]["target"])

        out = r.evaluate(hot, knobs)             # still hot: NO 2nd replan
        assert [p.get("action") for p in out] == [None]
        knobs["engine.max_queue"].set(out[0]["target"])
        assert knobs["engine.max_queue"].value == 16

        # pressure cleared: admission doubles back toward the baseline
        assert r.evaluate(cool, knobs)[0]["target"] == 32
        knobs["engine.max_queue"].set(32)
        assert r.evaluate(cool, knobs)[0]["target"] == 64
        knobs["engine.max_queue"].set(64)
        assert r.evaluate(cool, knobs) == []     # at baseline: hold


# --------------------------------------------------------------------------- #
# the controller + decision replay (the ISSUE acceptance bar)
# --------------------------------------------------------------------------- #

# a scripted diurnal-ish telemetry trace exercising every serving rule,
# including one failed tick (None) in the middle
_TRACE = [
    {"replicas_active": 1, "replicas_total": 3, "queue_depth": 0,
     "arrival_rate_rps": 1.0, "ttft_p95_ms": 100.0, "queue_wait_ms": 2.0,
     "slo_alerting": []},
    {"replicas_active": 1, "replicas_total": 3, "queue_depth": 12,
     "arrival_rate_rps": 80.0, "ttft_p95_ms": 400.0, "queue_wait_ms": 60.0,
     "slo_alerting": ["ttft"]},
    None,
    {"replicas_active": 2, "replicas_total": 3, "queue_depth": 12,
     "arrival_rate_rps": 80.0, "ttft_p95_ms": 400.0, "queue_wait_ms": 60.0,
     "slo_alerting": ["ttft"], "hbm_live_bytes": 95,
     "hbm_budget_bytes": 100},
    {"replicas_active": 3, "replicas_total": 3, "queue_depth": 0,
     "arrival_rate_rps": 2.0, "ttft_p95_ms": 120.0, "queue_wait_ms": 1.0,
     "slo_alerting": [], "hbm_live_bytes": 10, "hbm_budget_bytes": 100},
    {"replicas_active": 3, "replicas_total": 3, "queue_depth": 0,
     "arrival_rate_rps": 2.0, "ttft_p95_ms": 120.0, "queue_wait_ms": 1.0,
     "slo_alerting": []},
    {"replicas_active": 3, "replicas_total": 3, "queue_depth": 0,
     "arrival_rate_rps": 2.0, "ttft_p95_ms": 120.0, "queue_wait_ms": 1.0,
     "slo_alerting": []},
]


def _shadow_serving_knobs():
    return _shadow(**{"fleet.replicas": 1, "fleet.hedge_after_s": 0.5,
                      "engine.chunk_size": 16, "engine.decode_burst": 2,
                      "engine.max_queue": 64})


def _record_trace(rules):
    ctl = Controller(rules, _shadow_serving_knobs(), register=False,
                     now_fn=lambda: 0.0)
    for i, snap in enumerate(_TRACE):
        ctl.tick(now=i * 0.25, telemetry=snap)
    return ctl.recorder.export()


class TestControllerReplay:
    def test_scripted_trace_records_bounded_decisions(self):
        record = _record_trace(serving_rules())
        assert len(record["ticks"]) == len(_TRACE)
        sets = [d for t in record["ticks"] for d in t["decisions"]
                if d["action"] == "set"]
        assert len(sets) >= 6
        for d in sets:
            spec = KNOB_BOUNDS[d["knob"]]
            assert spec["min"] <= d["new"] <= spec["max"]
            assert abs(d["new"] - d["old"]) <= spec["slew"] + 1e-9
        # the failed tick is an error decision, not a raise
        err = _TRACE.index(None)
        tick = record["ticks"][err]
        assert tick["telemetry"] is None
        assert tick["decisions"][0]["action"] == "error"
        # the scale-down hysteresis fired on the last quiet tick
        assert any(d["knob"] == "fleet.replicas" and d["new"] == 2
                   for d in record["ticks"][-1]["decisions"])

    def test_replay_reproduces_the_identical_decision_sequence(self):
        record = _record_trace(serving_rules())
        shadow = replay(record, serving_rules())
        assert decision_sequence(record) != []
        assert decision_sequence(shadow) == decision_sequence(record)

    def test_replay_with_tampered_rules_diverges(self):
        """The purity contract is falsifiable: replaying through a rule
        set with different parameters must NOT reproduce the record."""
        record = _record_trace(serving_rules())
        shadow = replay(record, serving_rules(hedge={"factor": 10.0}))
        assert decision_sequence(shadow) != decision_sequence(record)

    def test_replay_is_idempotent(self):
        record = _record_trace(serving_rules())
        a = replay(record, serving_rules())
        b = replay(a, serving_rules())
        assert decision_sequence(b) == decision_sequence(record)


# --------------------------------------------------------------------------- #
# fail-static: the control.tick / control.actuate drills
# --------------------------------------------------------------------------- #

class TestFailStatic:
    def test_consecutive_failures_degrade_to_static(self):
        def boom():
            raise RuntimeError("telemetry plane down")
        ctl = Controller([AutoscaleRule()], _shadow_serving_knobs(),
                         telemetry_fn=boom, register=False,
                         now_fn=lambda: 0.0, max_failures=3)
        for i in range(3):
            out = ctl.tick(now=float(i))
            assert not ctl.enabled or i < 2
        assert ctl.degraded and not ctl.enabled
        assert ctl.tick(now=9.0) == []           # disabled: a skip
        # every knob held at its last good value — the static config
        assert ctl.knobs["fleet.replicas"].value == 1
        # the degrade decision is on the record
        seq = decision_sequence(ctl.recorder.export())
        assert any(row[5] == "degrade" for row in seq)
        ctl.enable()
        assert ctl.tick(now=10.0, telemetry=_TRACE[1]) != []

    def test_tick_fault_drill_never_raises_and_degrades(self):
        """fi.arm('control.tick'): the drill lands as error decisions;
        tick() never raises, and max_failures of them degrade."""
        fi.arm("control.tick", action="raise", nth=1, times=3)
        ctl = Controller([AutoscaleRule()], _shadow_serving_knobs(),
                         telemetry_fn=lambda: _TRACE[1], register=False,
                         now_fn=lambda: 0.0, max_failures=3)
        for i in range(3):
            ctl.tick(now=float(i))               # must not raise
        assert ctl.degraded
        fi.reset()
        ctl.enable()
        out = ctl.tick(now=5.0)
        assert any(d["action"] == "set" for d in out)

    def test_actuate_fault_drill_holds_the_knob(self):
        fi.arm("control.actuate", action="raise", nth=1)
        ctl = Controller([HedgeRule()], _shadow_serving_knobs(),
                         register=False, now_fn=lambda: 0.0)
        ctl.tick(now=0.0, telemetry={"ttft_p95_ms": 2000.0})
        assert ctl.knobs["fleet.hedge_after_s"].value == 0.5
        seq = ctl.recorder.export()["ticks"][0]["decisions"]
        assert seq[0]["outcome"].startswith("error")
        assert seq[0]["old"] == seq[0]["new"] == 0.5

    def test_raising_setter_is_an_error_decision_value_held(self):
        def boom(v):
            raise RuntimeError("scale_to failed")
        knobs = _shadow_serving_knobs()
        knobs["fleet.replicas"] = Knob("fleet.replicas", 1, setter=boom)
        ctl = Controller([AutoscaleRule()], knobs, register=False,
                         now_fn=lambda: 0.0)
        ctl.tick(now=0.0, telemetry=_TRACE[1])
        assert ctl.knobs["fleet.replicas"].value == 1
        d = ctl.recorder.export()["ticks"][0]["decisions"][0]
        assert d["outcome"].startswith("error") and d["new"] == 1


# --------------------------------------------------------------------------- #
# observability: /controlz, /statusz, flight dumps, obs_probe
# --------------------------------------------------------------------------- #

def _get(port, path, timeout=10.0):
    import urllib.error
    import urllib.request
    url = f"http://127.0.0.1:{port}{path}"
    try:
        with urllib.request.urlopen(url, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read().decode())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read().decode())


def _load_obs_probe():
    import importlib.util
    import sys
    spec = importlib.util.spec_from_file_location(
        "_obs_probe", os.path.join(os.path.dirname(__file__), os.pardir,
                                   "tools", "obs_probe.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules["_obs_probe"] = mod
    spec.loader.exec_module(mod)
    return mod


class TestObservability:
    def test_controlz_statusz_and_probe(self):
        port = obs.serve(port=0)
        ctl = Controller([HedgeRule()], _shadow_serving_knobs(),
                         now_fn=lambda: 0.0)
        try:
            ctl.tick(now=0.0, telemetry={"ttft_p95_ms": 2000.0})
            code, doc = _get(port, "/controlz")
            assert code == 200
            sec = doc["controllers"]["control"]
            assert sec["enabled"] and not sec["degraded"]
            assert sec["ticks"] == 1 and sec["decisions"] == 1
            assert len(sec["record"]["ticks"]) == 1
            d = sec["record"]["ticks"][0]["decisions"][0]
            assert d["knob"] == "fleet.hedge_after_s"
            assert sec["knobs"]["fleet.replicas"]["min"] == 1

            code, st = _get(port, "/statusz")
            assert st["providers"]["control"]["rules"] == ["hedge"]

            probe = _load_obs_probe()
            rc, pd = probe.probe(f"http://127.0.0.1:{port}")
            assert rc == 0
            assert "control" in pd["controlz"]
            summary = "\n".join(probe._summary(pd))
            assert "controller control:" in summary
            assert "1 ticks, 1 decisions" in summary
        finally:
            ctl.close()
        # closed: providers unregistered, the endpoint stays up
        code, doc = _get(port, "/controlz")
        assert code == 200 and doc["controllers"] == {}

    def test_flight_dump_carries_the_controller_section(self, tmp_path):
        ctl = Controller(serving_rules(), _shadow_serving_knobs(),
                         now_fn=lambda: 0.0)
        try:
            ctl.tick(now=0.0, telemetry=_TRACE[1])
            path = str(tmp_path / "flight.json")
            assert trace.flight_dump(path=path, reason="test",
                                     coalesce_s=0) == path
            with open(path) as f:
                doc = json.load(f)
            sec = doc["sections"]["control"]
            assert sec["enabled"] and sec["ticks"] == 1
            assert sec["decisions"]                  # compact seq rows
            assert sec["knobs"]["fleet.replicas"] == 2
        finally:
            ctl.close()

    def test_controller_exports_cataloged_metrics(self):
        monitor.enable()
        ctl = Controller([HedgeRule()], _shadow_serving_knobs(),
                         now_fn=lambda: 0.0)
        try:
            ctl.tick(now=0.0, telemetry={"ttft_p95_ms": 2000.0})
            text = monitor.prometheus_text()
            assert "paddle_tpu_control_ticks_total 1" in text
            assert 'paddle_tpu_control_decisions_total{rule="hedge"} 1' \
                in text
            assert 'paddle_tpu_control_knob_value{knob="fleet.hedge_after_s"}' \
                in text
        finally:
            ctl.close()


# --------------------------------------------------------------------------- #
# serving wiring: burn-aware routing, engine knob staging, the fleet loop
# --------------------------------------------------------------------------- #

def _alerting_tracker(clock):
    return SLOTracker(serving_objectives(), fast_window_s=5.0,
                      slow_window_s=60.0, min_events=1,
                      now_fn=lambda: clock[0])


def _make_alerting(trk, tag):
    for _ in range(5):
        trk.record("completion", good=False, tenant=f"replica:{tag}")
    trk.scan()
    assert trk.is_alerting("completion", f"replica:{tag}")


class TestBurnAwareRouting:
    def test_flag_off_routing_stays_least_inflight(self):
        """The regression pin: with burn_aware_routing OFF (default),
        an alerting replica changes NOTHING about placement."""
        clock = [1000.0]
        trk = _alerting_tracker(clock)
        fl = _fleet(_model(), replicas=2, start=False, slo=trk)
        assert fl.burn_aware_routing is False
        p = np.arange(6, dtype=np.int32)
        fl.submit(p, max_new_tokens=4)           # -> replica 0 (idx order)
        assert fl.replicas[0].inflight == 1
        _make_alerting(trk, fl.replicas[1].tag)
        fl.submit(p, max_new_tokens=4)
        assert fl.replicas[1].inflight == 1      # least-inflight, period

    def test_flag_on_deprioritizes_but_never_excludes(self):
        clock = [1000.0]
        trk = _alerting_tracker(clock)
        fl = _fleet(_model(), replicas=2, start=False, slo=trk,
                    burn_aware_routing=True)
        p = np.arange(6, dtype=np.int32)
        fl.submit(p, max_new_tokens=4)
        assert fl.replicas[0].inflight == 1
        _make_alerting(trk, fl.replicas[1].tag)
        fl.submit(p, max_new_tokens=4)
        # the quiet replica wins despite its deeper queue
        assert fl.replicas[0].inflight == 2
        assert fl.replicas[1].inflight == 0
        # every replica alerting: the fleet still serves (least-inflight
        # among the alerting set), deprioritized is not excluded
        _make_alerting(trk, fl.replicas[0].tag)
        fl.submit(p, max_new_tokens=4)
        assert fl.replicas[1].inflight == 1


class TestEngineKnobStaging:
    def test_unknown_knob_fails_at_the_actuation_site(self):
        eng = ContinuousBatchingEngine(_model(), max_batch=2, block_size=8,
                                       chunk_size=16, decode_burst=1)
        with pytest.raises(ValueError, match="unknown serving knob"):
            eng.request_knobs(bogus=1)

    def test_staged_knobs_apply_at_the_step_boundary(self):
        eng = ContinuousBatchingEngine(_model(), max_batch=2, block_size=8,
                                       chunk_size=16, decode_burst=1)
        eng.submit(np.arange(6, dtype=np.int32), max_new_tokens=3)
        eng.request_knobs(chunk_size=32, decode_burst=2, max_queue=7)
        # staged, NOT applied — a knob never changes mid-step
        assert eng.chunk_size == 16 and eng.decode_burst == 1
        out = {}
        while eng.num_active or eng.num_pending:
            for rid, toks in eng.step():
                out[rid] = list(toks)
        assert eng.chunk_size == 32
        assert eng.decode_burst == 2
        assert eng.max_queue == 7
        assert len(out) == 1


class TestServingControllerWiring:
    def test_build_binds_real_setters_threadless(self):
        fl = _fleet(_model(), replicas=2, start=False, hedge_after_s=0.5)
        ctl = build_serving_controller(
            fl, rules=[HedgeRule(), ChunkRule()], register=False)
        try:
            assert ctl.knobs["fleet.replicas"].value == 2
            assert ctl.knobs["engine.chunk_size"].value == 16
            out = ctl.tick(now=0.0, telemetry={"ttft_p95_ms": 2000.0,
                                               "queue_wait_ms": 100.0})
            assert len(out) == 2
            # hedge: 3 x 2s = 6s target, slew-limited to 0.5 + 0.25
            assert fl.hedge_after_s == pytest.approx(0.75)
            # chunk: staged on EVERY replica engine, applied at step time
            for rep in fl.replicas:
                assert rep.engine.chunk_size == 16
                assert rep.engine._pending_knobs == {"chunk_size": 32}
        finally:
            ctl.close()

    def test_autoscale_resumes_a_drained_replica_and_moves_no_token(self):
        """The closed loop on a live fleet, no clock in it: a backlog
        queued on the one active replica makes the first tick resume the
        drained one; the record replays to the identical decisions
        inside the declared bounds; and the tokens equal those of the
        same fleet with no controller and with a controller that is
        built and never ticked (knobs move latency, never tokens)."""
        model = _model()
        r = np.random.RandomState(5)
        prompts = [r.randint(0, 96, (10,)).astype("int32")
                   for _ in range(6)]

        def serve(controller):
            fl = _fleet(model, replicas=2, start=False)
            ctl = None
            try:
                fl.drain(1)                      # the overnight shape
                if controller:
                    ctl = build_serving_controller(
                        fl, rules=[AutoscaleRule()], register=False)
                frids = [fl.submit(p, max_new_tokens=6) for p in prompts]
                if controller == "ticked":
                    ctl.tick(now=0.0)
                fl.start()
                got, t0 = {}, time.time()
                while len(got) < len(frids) and time.time() - t0 < 60:
                    got.update((f, list(t)) for f, t in fl.pop_results())
                    time.sleep(0.001)
                return [got.get(f) for f in frids], fl.active_replicas(), \
                    ctl and ctl.recorder.export(), ctl and ctl.degraded
            finally:
                if ctl is not None:
                    ctl.close()
                fl.stop()

        static, n_static, _, _ = serve(None)
        off, n_off, _, _ = serve("built")
        on, n_on, record, degraded = serve("ticked")
        assert all(t is not None for t in static)
        assert off == static and on == static
        assert (n_static, n_off, n_on) == (1, 1, 2)
        sets = [d for t in record["ticks"] for d in t["decisions"]
                if d["action"] == "set"]
        assert [(d["knob"], d["old"], d["new"]) for d in sets] \
            == [("fleet.replicas", 1, 2)]
        spec = KNOB_BOUNDS["fleet.replicas"]
        assert spec["min"] <= 2 <= spec["max"] and 1 <= spec["slew"]
        assert decision_sequence(replay(record, [AutoscaleRule()])) \
            == decision_sequence(record) != []
        assert degraded is False

    def test_fleet_telemetry_snapshot_is_jsonable(self):
        fl = _fleet(_model(), replicas=2, start=False)
        snap = fleet_telemetry(fl)()
        assert snap["replicas_total"] == 2
        assert snap["replicas_active"] == 2
        assert snap["queue_depth"] == 0
        fl.submit(np.arange(6, dtype=np.int32), max_new_tokens=4)
        snap = fleet_telemetry(fl)()
        assert snap["queue_depth"] == 1
        assert snap["arrival_rate_rps"] > 0
        json.dumps(snap)                         # the record is JSON-able

    def test_replan_hook_fires_once_and_is_inspectable(self):
        hook = make_replan_hook(lambda b: {"budget": b})
        ctl = Controller([HbmGuardRule()],
                         _shadow(**{"engine.max_queue": 64}),
                         hooks={"replan": hook}, register=False,
                         now_fn=lambda: 0.0)
        hot = {"hbm_live_bytes": 95, "hbm_budget_bytes": 100}
        ctl.tick(now=0.0, telemetry=hot)
        ctl.tick(now=1.0, telemetry=hot)
        assert hook.plans == [{"budget": 100}]   # re-planned ONCE
        assert ctl.knobs["engine.max_queue"].value == 16
        seq = decision_sequence(ctl.recorder.export())
        assert [row[5] for row in seq].count("replan") == 1

    def test_raising_replan_still_shrinks_admission(self):
        def bad_plan(b):
            raise RuntimeError("unsatisfiable budget")
        hook = make_replan_hook(bad_plan)
        ctl = Controller([HbmGuardRule()],
                         _shadow(**{"engine.max_queue": 64}),
                         hooks={"replan": hook}, register=False,
                         now_fn=lambda: 0.0)
        ctl.tick(now=0.0, telemetry={"hbm_live_bytes": 95,
                                     "hbm_budget_bytes": 100})
        d = ctl.recorder.export()["ticks"][0]["decisions"]
        assert d[0]["action"] == "replan"
        assert d[0]["outcome"].startswith("error")
        # the guard falls through to admission control regardless
        assert ctl.knobs["engine.max_queue"].value == 32
