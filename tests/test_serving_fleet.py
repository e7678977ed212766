"""The resilient serving fleet (paddle_tpu/serving/fleet.py, ISSUE 14).

The acceptance bars:
- ROUTING: least queue depth among admissible replicas, typed
  FleetUnavailable when nothing admits, half-open suspects carry at most
  one probe (the circuit breaker's admission contract);
- FAILOVER: killing 1 of 3 replicas mid-workload loses nothing — every
  request completes with outputs BIT-IDENTICAL to an undisturbed fleet
  (re-seeded from RequestAborted.tokens: prompt + partial output), the
  dead replica circuit-breaks, backs off, probes half-open and heals;
- HEDGING: a request past the latency SLO runs a bounded duplicate on a
  second replica; the first finisher wins and the loser is cancelled;
- DRAIN: a graceful drain migrates queued work, finishes active work,
  parks the replica, and loses ZERO requests;
- the engine-level satellites: cancel(), RequestAborted.stats, and the
  submit()-racing-recover() regression.
"""
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.analysis import faultinject as fi
from paddle_tpu.analysis import sanitizers as san
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.serving import (AdmissionTimeout,
                                       ContinuousBatchingEngine)
from paddle_tpu.monitor import trace
from paddle_tpu.serving import (DOWN, HEALTHY, PARKED, SUSPECT,
                                FleetRouter, FleetUnavailable)


@pytest.fixture(autouse=True)
def _clean():
    fi.reset()
    yield
    fi.reset()
    san.disable()
    san.reset()
    monitor.disable()
    monitor.reset()
    trace.disable()
    trace.reset()


def _model():
    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=96, hidden_size=64,
                      intermediate_size=176, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=2,
                      max_position_embeddings=128)
    return LlamaForCausalLM(cfg)


_MODEL = None


def _shared_model():
    global _MODEL
    if _MODEL is None:
        _MODEL = _model()
    return _MODEL


def _fleet(model, replicas=2, start=True, **kw):
    ekw = dict(max_batch=2, block_size=8, chunk_size=16, decode_burst=1)
    ekw.update(kw.pop("engine_kwargs", {}))
    kw.setdefault("max_new_tokens", 6)
    return FleetRouter(model, replicas=replicas, engine_kwargs=ekw,
                       start=start, **kw)


def _collect(fl, frids, deadline_s=60.0):
    got = {}
    t0 = time.time()
    while len(got) < len(frids) and time.time() - t0 < deadline_s:
        for frid, toks in fl.pop_results():
            got[frid] = list(toks)
        time.sleep(0.001)
    return [got.get(f) for f in frids]


# --------------------------------------------------------------------------- #
# routing (no threads: start=False routes + enqueues, nothing steps)
# --------------------------------------------------------------------------- #

class TestRouting:
    def test_least_depth_round_robins_an_idle_fleet(self):
        fl = _fleet(_shared_model(), replicas=3, start=False)
        r = np.random.RandomState(0)
        for _ in range(6):
            fl.submit(r.randint(0, 96, (8,)).astype("int32"),
                      max_new_tokens=4)
        assert [rep.inflight for rep in fl.replicas] == [2, 2, 2]

    def test_unavailable_when_nothing_admits_is_typed(self):
        fl = _fleet(_shared_model(), replicas=2, start=False)
        for rep in fl.replicas:
            rep.state = DOWN
        with pytest.raises(FleetUnavailable):
            fl.submit(np.arange(6, dtype=np.int32), max_new_tokens=4)

    def test_half_open_suspect_admits_exactly_one_probe(self):
        fl = _fleet(_shared_model(), replicas=2, start=False)
        fl.replicas[0].state = DOWN
        fl.replicas[1].state = SUSPECT
        p = np.arange(6, dtype=np.int32)
        fl.submit(p, max_new_tokens=4)        # the probe
        assert fl.replicas[1].inflight == 1
        with pytest.raises(FleetUnavailable):
            fl.submit(p, max_new_tokens=4)    # no second until it proves

    def test_route_fault_drill_surfaces_typed_error(self):
        fl = _fleet(_shared_model(), replicas=2, start=False)
        fi.arm("fleet.route", action="raise", nth=1)
        with pytest.raises(fi.InjectedFault):
            fl.submit(np.arange(6, dtype=np.int32), max_new_tokens=4)
        fi.reset()
        assert isinstance(
            fl.submit(np.arange(6, dtype=np.int32), max_new_tokens=4),
            int)

    def test_affinity_hook_is_a_stub(self):
        fl = _fleet(_shared_model(), replicas=2, start=False)
        assert fl._affinity_hint(np.arange(4), fl.replicas) is None


# --------------------------------------------------------------------------- #
# health-state machine (start=False: scans invoked by hand)
# --------------------------------------------------------------------------- #

class TestHealthStateMachine:
    def test_stale_heartbeat_suspects_then_heals(self):
        fl = _fleet(_shared_model(), replicas=2, start=False,
                    suspect_after_s=0.5)
        rep = fl.replicas[0]
        rep.heartbeat = time.monotonic() - 10.0
        fl._health_scan()
        assert rep.state == SUSPECT and rep.suspect_reason == "stale"
        rep.heartbeat = time.monotonic()
        fl._health_scan()
        assert rep.state == HEALTHY
        log = [(old, new) for tag, old, new, _r in fl.state_log
               if tag == rep.tag]
        assert log == [(HEALTHY, SUSPECT), (SUSPECT, HEALTHY)]

    def test_backoff_elapse_opens_half_open_window(self):
        fl = _fleet(_shared_model(), replicas=2, start=False)
        rep = fl.replicas[1]
        rep.state = DOWN
        rep.failures = 1
        rep.backoff_until = time.monotonic() - 0.01
        fl._health_scan()
        assert rep.state == SUSPECT and rep.suspect_reason == "probe"

    def test_health_fault_drill_trips(self):
        fl = _fleet(_shared_model(), replicas=2, start=False)
        fi.arm("fleet.health", action="raise", nth=1)
        with pytest.raises(fi.InjectedFault):
            fl._health_scan()
        assert fi.trips() == [("fleet.health", "raise")]
        fl._health_scan()       # scanning continues after the trip

    def test_state_transitions_export_metrics_and_span(self):
        monitor.enable()
        trace.enable()
        fl = _fleet(_shared_model(), replicas=2, start=False)
        rep = fl.replicas[0]
        rep.heartbeat = time.monotonic() - 10.0
        fl._health_scan()
        snap = monitor.snapshot()["metrics"]
        states = snap["paddle_tpu_fleet_replica_state"]["values"]
        assert states[f"replica={rep.tag}"] == 1          # suspect
        assert snap["paddle_tpu_fleet_healthy_replicas"]["values"][""] == 1
        assert any(sp.name == "fleet.health" for sp in trace.spans())


# --------------------------------------------------------------------------- #
# THE failover drill (ISSUE 14 acceptance, tier-1 shape)
# --------------------------------------------------------------------------- #

class TestFailoverDrill:
    def test_killed_replica_fails_over_bit_identical_then_heals(self):
        """Kill 1 of 3 replicas mid-workload: every request completes
        with outputs bit-identical to an undisturbed fleet (partial
        tokens re-seeded onto survivors), the merged stats carry the
        failover provenance with an honest TTFT, and the dead replica
        walks the breaker back to healthy via a half-open probe."""
        model = _model()
        r = np.random.RandomState(0)
        prompts = [r.randint(0, 96, (12,)).astype("int32")
                   for _ in range(9)]

        def run(arm):
            fi.reset()
            fl = _fleet(model, replicas=3, max_new_tokens=8,
                        backoff_base_s=0.05)
            fl.warmup(prompts[0][:6])
            if arm:
                fi.arm("fleet.replica_step", action="raise", nth=6)
            frids = [fl.submit(p, max_new_tokens=8) for p in prompts]
            out = _collect(fl, frids)
            stats = [fl.pop_stats(f) for f in frids]
            return fl, out, stats

        fl_ref, ref, _ = run(False)
        fl_ref.stop()
        fl, out, stats = run(True)
        try:
            assert fi.trips() == [("fleet.replica_step", "raise")]
            assert all(t is not None for t in out)
            assert out == ref                      # bit-identical failover
            assert fl.failovers >= 1
            failed_over = [s for s in stats
                           if s and s["failovers"] >= 1]
            assert failed_over
            # the merged stats stay honest across the re-route: TTFT is
            # present and measured from the ORIGINAL fleet submit
            assert all(s.get("ttft_ns", 0) > 0 for s in failed_over)
            # the dead replica circuit-broke...
            dead = [rep for rep in fl.replicas
                    if rep.engine.recovery_stats]
            assert len(dead) == 1
            tags = [(old, new) for tag, old, new, _r in fl.state_log
                    if tag == dead[0].tag]
            assert (HEALTHY, DOWN) in tags
            # ... and heals: backoff elapses -> half-open probe -> a
            # second wave completes on the whole fleet
            t0 = time.time()
            while dead[0].state == DOWN and time.time() - t0 < 10:
                time.sleep(0.01)
            assert dead[0].state in (SUSPECT, HEALTHY)
            frids2 = [fl.submit(p, max_new_tokens=8) for p in prompts]
            out2 = _collect(fl, frids2)
            assert out2 == ref
            t0 = time.time()
            while dead[0].state != HEALTHY and time.time() - t0 < 10:
                frid = fl.submit(prompts[0], max_new_tokens=4)
                _collect(fl, [frid], deadline_s=20)
                time.sleep(0.01)
            assert dead[0].state == HEALTHY
            assert (DOWN, SUSPECT) in [(o, n) for _t, o, n, _r
                                       in fl.state_log]
        finally:
            fl.stop()

    def test_failover_leaves_survivors_warm_and_dumps_the_dead_replica(
            self, monkeypatch, tmp_path):
        """The warm half of the kill drill: with the recompile sentinel
        at threshold 1 after warm-up (any compile would raise at its
        site), killing 1 of 3 replicas adds no program to any replica
        and trips nothing; the recovery's flight dump is the dead
        replica's own file."""
        monkeypatch.setenv("PADDLE_TPU_FLIGHT_DIR", str(tmp_path))
        monitor.enable()
        trace.enable()          # the flight dump needs the recorder on
        model = _model()
        r = np.random.RandomState(0)
        prompts = [r.randint(0, 96, (12,)).astype("int32")
                   for _ in range(9)]
        fl = _fleet(model, replicas=3, max_new_tokens=8)
        thr0 = san.recompile_threshold()
        try:
            fl.warmup(prompts[0][:6])
            programs0 = [len(rep.engine._jit_cache) for rep in fl.replicas]
            san.reset()
            san.set_recompile_threshold(1)
            san.enable("recompile")
            fi.arm("fleet.replica_step", action="raise", nth=6)
            frids = [fl.submit(p, max_new_tokens=8) for p in prompts]
            out = _collect(fl, frids)
            san.disable("recompile")
            assert all(t is not None for t in out)
            assert fl.failovers >= 1
            assert san.trips() == []
            assert sum(san.compile_counts().values()) == 0
            assert [len(rep.engine._jit_cache)
                    for rep in fl.replicas] == programs0
            (dead,) = [rep for rep in fl.replicas
                       if rep.engine.recovery_stats]
            dump = dead.engine.recovery_stats[0]["dump"]
            assert dump and dead.tag in dump
        finally:
            san.set_recompile_threshold(thr0)
            fl.stop()

    def test_fleet_counters_and_metrics_export(self):
        monitor.enable()
        model = _model()
        r = np.random.RandomState(3)
        fl = _fleet(model, replicas=2)
        try:
            fl.warmup(r.randint(0, 96, (6,)).astype("int32"))
            frids = [fl.submit(r.randint(0, 96, (10,)).astype("int32"),
                               max_new_tokens=4) for _ in range(4)]
            out = _collect(fl, frids)
            assert all(t is not None for t in out)
            snap = monitor.snapshot()["metrics"]
            assert snap["paddle_tpu_fleet_requests_total"]["values"][""] \
                == 4
            routed = snap["paddle_tpu_fleet_routed_total"]["values"]
            assert sum(routed.values()) >= 4 + len(fl.replicas)
        finally:
            fl.stop()


# --------------------------------------------------------------------------- #
# tail hedging
# --------------------------------------------------------------------------- #

class TestHedging:
    def test_slow_primary_hedges_first_finisher_wins_loser_cancelled(self):
        model = _model()
        r = np.random.RandomState(5)
        prompt = r.randint(0, 96, (10,)).astype("int32")
        fl = _fleet(model, replicas=2, max_new_tokens=6,
                    health_poll_s=0.01)
        try:
            fl.warmup(prompt[:6])
            # reference tokens from the undisturbed fleet (greedy ->
            # deterministic, so the hedge winner must reproduce them)
            ref = _collect(fl, [fl.submit(prompt, max_new_tokens=6)])[0]
            # SLO armed only now: compile-time warmup latency must not
            # count as a tail
            fl.hedge_after_s = 0.05
            fi.arm("serving.step", action="delay", delay_s=0.4, nth=2,
                   times=2)
            frid = fl.submit(prompt, max_new_tokens=6)
            out = _collect(fl, [frid])[0]
            st = fl.pop_stats(frid)
            assert out == ref                  # either winner is exact
            assert fl.hedges >= 1
            assert st["hedged"] is True
            # the loser is cancelled (engine-side), not left running
            t0 = time.time()
            while sum(rep.engine.cancelled for rep in fl.replicas) < 1 \
                    and time.time() - t0 < 10:
                time.sleep(0.01)
            assert sum(rep.engine.cancelled for rep in fl.replicas) >= 1
            with fl._lock:
                assert not fl._requests       # ledger fully resolved
        finally:
            fl.stop()

    def test_hedge_budget_bounds_concurrent_duplicates(self):
        from paddle_tpu.serving import fleet as fleet_mod

        model = _model()
        fl = _fleet(model, replicas=2, start=False, max_hedges=1)
        fl.hedge_after_s = 0.0
        r = np.random.RandomState(6)
        for _ in range(3):
            fl.submit(r.randint(0, 96, (8,)).astype("int32"),
                      max_new_tokens=4)
        fl._maybe_hedge(fleet_mod._mon(), time.monotonic())
        assert fl.hedges == 1                  # bounded, not per-request

    def test_cancel_bookkeeping_is_bounded_and_idempotent(self):
        fl = _fleet(_shared_model(), replicas=1, start=False)
        rep = fl.replicas[0]
        # a successfully cancelled request never completes, so nothing
        # else would ever discard its entry — the record is bounded
        for i in range(2000):
            rep.mark_cancelled(i)
        assert len(rep.cancelled_rids) <= 1024
        assert 1999 in rep.cancelled_rids and 0 not in rep.cancelled_rids
        # cancelling an attempt twice (a completion raced in) must not
        # double-decrement inflight — a negative count would skew
        # routing and wedge drain()
        frid = fl.submit(np.arange(6, dtype=np.int32), max_new_tokens=2)
        att = fl._requests[frid].primary
        with fl._lock:
            assert fl._cancel_attempt_locked(rep, att.rid) is True
            assert fl._cancel_attempt_locked(rep, att.rid) is False
        assert rep.inflight == 0


# --------------------------------------------------------------------------- #
# graceful drain + rolling restart
# --------------------------------------------------------------------------- #

class TestDrainAndResume:
    def test_drain_migrates_queued_finishes_active_zero_lost(self):
        model = _model()
        r = np.random.RandomState(7)
        prompts = [r.randint(0, 96, (10,)).astype("int32")
                   for _ in range(6)]
        fl = _fleet(model, replicas=2, start=False,
                    engine_kwargs=dict(max_batch=1), max_new_tokens=6)
        try:
            frids = [fl.submit(p, max_new_tokens=6) for p in prompts]
            assert fl.replicas[0].inflight == 3
            res = fl.drain(0)                  # nothing active yet:
            assert res["parked"] is True       # all three queued migrate
            assert res["migrated"] == 3
            assert fl.replicas[0].inflight == 0
            assert fl.replicas[1].inflight == 6
            assert fl.states()[fl.replicas[0].tag] == PARKED
            fl.start()
            out = _collect(fl, frids)
            assert all(t is not None for t in out)          # zero lost
            assert fl.replicas[0].engine.num_active == 0
            # rolling restart completes: resume re-admits the replica
            fl.resume(0)
            assert fl.states()[fl.replicas[0].tag] == HEALTHY
            frid = fl.submit(prompts[0], max_new_tokens=4)
            assert _collect(fl, [frid])[0] is not None
        finally:
            fl.stop()

    def test_drain_mid_decode_finishes_in_flight_work(self):
        model = _model()
        r = np.random.RandomState(8)
        prompts = [r.randint(0, 96, (10,)).astype("int32")
                   for _ in range(4)]
        fl = _fleet(model, replicas=2, max_new_tokens=10)
        try:
            fl.warmup(prompts[0][:6])
            ref = _collect(fl, [fl.submit(p, max_new_tokens=10)
                                for p in prompts])
            frids = [fl.submit(p, max_new_tokens=10) for p in prompts]
            res = fl.drain(1, timeout=30.0)
            assert res["parked"] is True
            out = _collect(fl, frids)
            assert all(t is not None for t in out)          # zero lost
            assert out == ref       # a drain moves work, never tokens
            assert fl.states()[fl.replicas[1].tag] == PARKED
            assert fl.drains == 1
        finally:
            fl.stop()


# --------------------------------------------------------------------------- #
# engine-level satellites
# --------------------------------------------------------------------------- #

class TestEngineCancel:
    def test_cancel_queued_request_leaves_its_lane(self):
        eng = ContinuousBatchingEngine(_shared_model(), max_batch=1,
                                       block_size=8, chunk_size=16,
                                       decode_burst=1)
        p = np.arange(9, dtype=np.int32)
        rid1 = eng.submit(p, max_new_tokens=3)
        rid2 = eng.submit(p, max_new_tokens=3)
        eng.cancel(rid2)
        done = {}
        for _ in range(40):
            for rid, toks in eng.step():
                done[rid] = toks
            if not (eng.num_active or eng.num_pending):
                break
        assert rid1 in done and rid2 not in done
        assert eng.num_pending == 0
        assert eng.cancelled == 1

    def test_cancel_active_request_frees_slot_without_result(self):
        monitor.enable()
        # prefix_cache off: cached blocks legitimately outlive eviction
        # and would offset the exact free-pool accounting below
        eng = ContinuousBatchingEngine(_shared_model(), max_batch=2,
                                       block_size=8, chunk_size=16,
                                       decode_burst=1, prefix_cache=False)
        free0 = len(eng._pager._free)
        p = np.arange(9, dtype=np.int32)
        rid = eng.add_request(p, max_new_tokens=50)
        for _ in range(3):
            eng.step()
        assert eng.num_active == 1
        eng.cancel(rid)
        out = eng.step()
        assert out == [] and eng.num_active == 0
        assert len(eng._pager._free) == free0        # blocks all freed
        snap = monitor.snapshot()["metrics"]
        assert snap["paddle_tpu_serving_cancelled_total"]["values"][""] \
            == 1

    def test_cancel_unknown_or_finished_rid_is_a_noop(self):
        eng = ContinuousBatchingEngine(_shared_model(), max_batch=1,
                                       block_size=8, chunk_size=16)
        rid = eng.add_request(np.arange(6, dtype=np.int32),
                              max_new_tokens=2)
        done = {}
        for _ in range(20):
            for r2, toks in eng.step():
                done[r2] = toks
            if not eng.num_active:
                break
        eng.cancel(rid)
        eng.cancel(12345)
        assert eng.step() == []
        assert eng.cancelled == 0
        assert done[rid]                     # the finished result stands


class TestAbortStatsCarried:
    def test_request_aborted_carries_partial_stats(self):
        """The abort-path satellite: recover() pops the rid's stats
        record into RequestAborted.stats (nobody would ever pop the
        dead rid again) so a router can merge ttft/chunks/shared into
        the replacement's final stats."""
        eng = ContinuousBatchingEngine(_shared_model(), max_batch=2,
                                       block_size=8, chunk_size=16,
                                       decode_burst=1)
        p = np.arange(10, dtype=np.int32)
        rid = eng.add_request(p, max_new_tokens=20)
        for _ in range(4):
            eng.step()                       # prefill + a few tokens
        eng.recover("drill")
        (err,) = eng.pop_aborted()
        assert err.rid == rid
        assert err.stats is not None
        assert err.stats["aborted"] is True
        assert err.stats["tokens"] == len(err.tokens) >= 1
        assert err.stats["ttft_ns"] > 0      # first token had landed
        assert err.stats["prefill_chunks"] >= 1
        # ... and the record is GONE from the engine (not orphaned)
        assert eng.pop_stats(rid) is None

    def test_abort_before_first_token_has_no_ttft(self):
        eng = ContinuousBatchingEngine(_shared_model(), max_batch=1,
                                       block_size=8, chunk_size=4,
                                       decode_burst=1)
        rid = eng.add_request(np.arange(20, dtype=np.int32),
                              max_new_tokens=4)
        eng.step()                           # one 4-token prefill chunk
        eng.recover("drill")
        (err,) = eng.pop_aborted()
        assert err.rid == rid and err.tokens == []
        assert err.stats is not None and "ttft_ns" not in err.stats


class TestSubmitRecoverRace:
    def test_blocked_submitter_survives_recovery(self):
        """The satellite regression: a caller blocked in submit()'s
        bounded queue while the driving thread dies and recovers must
        get clean admission on the warm restart (or a typed error) —
        never a leaked slot or a hung caller."""
        # prefix_cache off so the no-leaked-blocks check is exact (the
        # cache would legitimately pin prompt blocks past eviction)
        eng = ContinuousBatchingEngine(_shared_model(), max_batch=1,
                                       block_size=8, chunk_size=16,
                                       decode_burst=1, max_queue=1,
                                       prefix_cache=False)
        free0 = len(eng._pager._free)
        p = np.arange(9, dtype=np.int32)
        eng.start_driver()
        try:
            rid1 = eng.submit(p, max_new_tokens=6, timeout=10.0)
            t0 = time.time()
            while eng.num_pending and time.time() - t0 < 10:
                time.sleep(0.001)            # rid1 admitted -> room
            rid2 = eng.submit(p, max_new_tokens=6, timeout=10.0)
            out = {}

            def blocked():
                try:
                    out["rid"] = eng.submit(p, max_new_tokens=6,
                                            timeout=20.0)
                except AdmissionTimeout as e:
                    out["err"] = e

            th = threading.Thread(target=blocked)
            th.start()
            fi.arm("serving.drive", action="raise", nth=3)
            tracked = {rid1: None, rid2: None}
            t0 = time.time()
            while time.time() - t0 < 30:
                for rid, toks in eng.pop_results():
                    if rid in tracked:
                        tracked[rid] = toks
                for err in eng.pop_aborted():
                    if err.rid in tracked and tracked[err.rid] is None:
                        del tracked[err.rid]
                        tracked[eng.submit(p, max_new_tokens=6,
                                           timeout=10.0)] = None
                if "rid" in out and out["rid"] not in tracked:
                    tracked[out["rid"]] = None
                if all(v is not None for v in tracked.values()) \
                        and ("rid" in out or "err" in out):
                    break
                time.sleep(0.001)
            th.join(timeout=30)
            assert not th.is_alive()                 # never a hung caller
            assert "rid" in out or "err" in out      # admitted or typed
            assert len(eng.recovery_stats) == 1
            assert all(v is not None for v in tracked.values())
        finally:
            eng.stop_driver()
        assert eng.num_active == 0 and eng.num_pending == 0
        t0 = time.time()
        while len(eng._pager._free) != free0 and time.time() - t0 < 5:
            time.sleep(0.01)
        assert len(eng._pager._free) == free0        # no leaked blocks


class TestSubmitRacingWithdraw:
    """ISSUE 15 review hardening: an abort/withdrawal landing in the
    instant between the engine accepting a request and the router
    recording its rid mapping must be CLAIMED and re-seeded (the
    abort-side twin of the unclaimed-result race), and a request the
    driver finished inside that same gap must not re-enter the ledger
    where nothing would ever remove it."""

    def test_unrecorded_abort_claimed_and_reseeded(self):
        from paddle_tpu.serving import fleet as fleet_mod

        fl = _fleet(_shared_model(), replicas=2, start=False)
        try:
            rep0 = fl.replicas[0]
            # the race, reproduced deterministically: the withdrawal
            # arrives while rid 7 has no rid2att mapping yet
            with fl._lock:
                out = fl._absorb_abort_locked(rep0, 7, [5, 6], None)
            assert out == []
            assert list(rep0.unclaimed_aborts) == [(7, [5, 6], None)]
            # ... then the submit path records the mapping for rid 7:
            # the parked abort must be claimed and the request re-seeded
            # with the partial tokens as its prefix
            fr = fleet_mod._FleetRequest(0, np.arange(4, dtype=np.int32),
                                         6, "", 0)
            att = fleet_mod._Attempt(fr, prefix=(), hedge=False)
            fr.primary = att
            orig = rep0.engine.submit
            rep0.engine.submit = lambda *a, **k: 7
            try:
                fl._submit_attempt(att, rep=rep0)
            finally:
                rep0.engine.submit = orig
            assert not rep0.unclaimed_aborts          # claimed
            new = fr.primary
            assert new is not att                     # re-seeded
            assert new.prefix == [5, 6]
            assert fr.failovers == 1 and fl.failovers == 1
            # the reservation is balanced: exactly the replacement's
            # inflight remains, mapped to the replacement attempt
            total = sum(r.inflight for r in fl.replicas)
            assert total == 1
            assert new.rep.rid2att[new.rid] is new
        finally:
            fl.stop()

    def test_unrecorded_abort_of_cancelled_rid_dropped(self):
        fl = _fleet(_shared_model(), replicas=1, start=False)
        try:
            rep = fl.replicas[0]
            rep.mark_cancelled(9)
            with fl._lock:
                assert fl._absorb_abort_locked(rep, 9, [1], None) == []
            # a cancelled hedge's abort re-seeds nothing and parks
            # nothing — its entry is simply consumed
            assert not rep.unclaimed_aborts
            assert 9 not in rep.cancelled_rids
        finally:
            fl.stop()

    def test_done_request_not_reinserted_into_ledger(self):
        fl = _fleet(_shared_model(), replicas=1, start=False)
        try:
            rep = fl.replicas[0]
            # the driver "finished" rid 3 before the mapping landed
            rep.unclaimed.append((3, [9, 9]))
            orig = rep.engine.submit
            rep.engine.submit = lambda *a, **k: 3
            try:
                frid = fl.submit(np.arange(4, dtype=np.int32))
            finally:
                rep.engine.submit = orig
            # the claimed result completed the request; the ledger must
            # stay EMPTY (nothing would ever remove a done entry)
            assert fl.pop_results() == [(frid, [9, 9])]
            assert fl.num_inflight == 0
        finally:
            fl.stop()
