"""graftscope analytics (ISSUE 15): span-timeline math on CONSTRUCTED
span sets with hand-computed answers — overlap/bubble/TTFT must match
exactly, not approximately — plus the SLO burn-rate window math + alert
drill on an injected clock.
"""
import pytest

import paddle_tpu  # noqa: F401 - initializes the package (monitor deps)
from paddle_tpu import monitor
from paddle_tpu.monitor import slo as slo_mod
from paddle_tpu.monitor import timeline as tl
from paddle_tpu.monitor import trace
from paddle_tpu.monitor.slo import Objective, SLOTracker


@pytest.fixture(autouse=True)
def _clean():
    yield
    monitor.disable()
    monitor.reset()
    trace.disable()
    trace.reset()


def _span(name, t0, t1, span_id=None, parent_id=None, trace_id=0,
          attrs=None):
    d = {"name": name, "t0_ns": t0, "t1_ns": t1,
         "span_id": span_id or (t0 * 1000 + (t1 or 0)),
         "trace_id": trace_id, "parent_id": parent_id}
    if attrs:
        d["attrs"] = attrs
    return d


class TestCommOverlap:
    def test_hand_computed_exact(self):
        spans = [
            _span("comm.all_reduce", 0, 100),
            _span("train.backward", 50, 150),
        ]
        rep = tl.comm_overlap(spans)
        assert rep == {"comm_ns": 100, "compute_ns": 100,
                       "overlapped_ns": 50, "overlap_fraction": 0.5}

    def test_unions_merge_before_intersecting(self):
        """Two overlapping comm spans count once; two compute spans
        bracketing them intersect exactly the union."""
        spans = [
            _span("comm.reduce_scatter", 0, 60),
            _span("comm.all_gather", 40, 100),      # merges -> [0, 100)
            _span("train.forward", 0, 30),
            _span("train.backward", 30, 50),        # union [0, 50)
            _span("train.optimizer", 90, 120),
        ]
        rep = tl.comm_overlap(spans)
        assert rep["comm_ns"] == 100
        assert rep["overlapped_ns"] == 50 + 10
        assert rep["overlap_fraction"] == 0.6

    def test_no_comm_is_zero(self):
        rep = tl.comm_overlap([_span("train.forward", 0, 10)])
        assert rep["comm_ns"] == 0 and rep["overlap_fraction"] == 0.0

    def test_open_spans_skipped(self):
        spans = [_span("comm.wait", 0, None), _span("comm.wait", 0, 10),
                 _span("train.forward", 0, 10)]
        assert tl.comm_overlap(spans)["comm_ns"] == 10


class TestBubbleAndPhases:
    def _step(self):
        root = _span("train.step", 0, 100, span_id=1)
        return [
            root,
            _span("train.forward", 10, 40, span_id=2, parent_id=1),
            _span("train.backward", 40, 70, span_id=3, parent_id=1),
        ]

    def test_bubble_hand_computed(self):
        rep = tl.bubble_fraction(self._step())
        assert rep["steps"] == 1
        assert rep["busy_ns"] == 60
        assert rep["bubble_ns"] == 40
        assert rep["bubble_fraction"] == 0.4

    def test_comm_in_window_counts_as_busy(self):
        spans = self._step() + [_span("comm.mesh_step", 70, 90,
                                      span_id=4)]
        rep = tl.bubble_fraction(spans)
        assert rep["busy_ns"] == 80 and rep["bubble_fraction"] == 0.2

    def test_comm_clipped_to_window(self):
        # comm span hanging past the step only counts its in-window part
        spans = self._step() + [_span("comm.mesh_step", 90, 130,
                                      span_id=4)]
        assert tl.bubble_fraction(spans)["busy_ns"] == 70

    def test_multi_step_aggregates(self):
        spans = self._step() + [
            _span("train.step", 200, 260, span_id=10),
            _span("train.forward", 200, 260, span_id=11, parent_id=10),
        ]
        rep = tl.bubble_fraction(spans)
        assert rep["steps"] == 2
        assert rep["step_ns"] == 160 and rep["busy_ns"] == 120
        assert rep["bubble_fraction"] == 0.25

    def test_step_phases(self):
        spans = self._step() + [_span("comm.collective", 75, 95,
                                      span_id=5)]
        rep = tl.step_phases(spans)
        assert rep["steps"] == 1
        assert rep["rows"][0]["phases"] == {"forward": 30,
                                            "backward": 30, "comm": 20}
        assert rep["mean_ns"]["forward"] == 30


class TestTTFTDecomposition:
    def _tree(self, trace_id, t0, qw, pf, gap, rid=0):
        """serving.request at t0; queue_wait [t0, t0+qw); prefill
        [t0+qw+gap, ...+pf) -> ttft = qw + gap + pf."""
        root_id = trace_id * 100
        admit = t0 + qw
        return [
            _span("serving.request", t0, t0 + qw + gap + pf + 50,
                  span_id=root_id, trace_id=trace_id,
                  attrs={"rid": rid}),
            _span("serving.queue_wait", t0, admit, span_id=root_id + 1,
                  parent_id=root_id, trace_id=trace_id),
            _span("serving.prefill", admit + gap, t0 + qw + gap + pf,
                  span_id=root_id + 2, parent_id=root_id,
                  trace_id=trace_id),
            _span("serving.decode_step", t0 + qw + gap + pf,
                  t0 + qw + gap + pf + 40, span_id=root_id + 3,
                  parent_id=root_id, trace_id=trace_id),
        ]

    def test_components_sum_exactly(self):
        spans = self._tree(1, 1000, qw=300, pf=600, gap=7, rid=42)
        rep = tl.ttft_decomposition(spans)
        assert rep["requests"] == 1
        row = rep["rows"][0]
        assert row["rid"] == 42
        assert row["ttft_ns"] == 907
        assert row["queue_wait_ns"] == 300
        assert row["prefill_ns"] == 600
        assert row["gap_ns"] == 7
        assert row["decode_ns"] == 40
        assert row["ttft_ns"] == row["queue_wait_ns"] \
            + row["prefill_ns"] + row["gap_ns"]

    def test_medians_over_requests(self):
        spans = (self._tree(1, 0, qw=100, pf=200, gap=0)
                 + self._tree(2, 5000, qw=300, pf=400, gap=0)
                 + self._tree(3, 9000, qw=500, pf=600, gap=0))
        rep = tl.ttft_decomposition(spans)
        assert rep["requests"] == 3
        assert rep["p50_ms"]["queue_wait_ms"] == 300 / 1e6
        assert rep["p50_ms"]["prefill_ms"] == 400 / 1e6
        assert rep["p50_ms"]["ttft_ms"] == 700 / 1e6

    def test_no_prefill_no_row(self):
        spans = [_span("serving.request", 0, 100, span_id=1,
                       trace_id=1)]
        assert tl.ttft_decomposition(spans)["requests"] == 0


class TestPerfReport:
    def test_assembles_from_live_ring(self):
        trace.enable()
        with trace.training_step(step=0) as ts:
            with ts.stage("forward"):
                pass
            with ts.stage("backward"):
                pass
        rep = tl.perf_report()
        assert rep["span_count"] >= 3
        assert rep["train"]["phases"]["steps"] == 1
        assert 0.0 <= rep["train"]["bubble"]["bubble_fraction"] <= 1.0
        assert "serving" not in rep
        assert "provenance" in rep


# -- SLO burn-rate window math + alert drill --------------------------------

class _Clock:
    def __init__(self, t=0.0):
        self.t = float(t)

    def __call__(self):
        return self.t


class TestObjective:
    def test_latency_classify(self):
        o = Objective("ttft", target=0.99, threshold_ns=1000)
        assert o.classify(value=1000) is True
        assert o.classify(value=1001) is False
        assert o.budget == pytest.approx(0.01)

    def test_validation(self):
        with pytest.raises(ValueError):
            Objective("x", target=1.0)
        o = Objective("x", target=0.9)
        with pytest.raises(ValueError):
            o.classify(value=5)          # no threshold, no good=
        assert len(slo_mod.serving_objectives()) == 3


class TestBurnRateMath:
    def _tracker(self, clock, **kw):
        kw.setdefault("fast_window_s", 10.0)
        kw.setdefault("slow_window_s", 100.0)
        kw.setdefault("burn_threshold", 2.0)
        kw.setdefault("min_events", 5)
        return SLOTracker([Objective("avail", target=0.99)],
                          now_fn=clock, **kw)

    def test_burn_rate_hand_computed(self):
        clock = _Clock(1000.0)
        t = self._tracker(clock)
        for _ in range(90):
            t.record("avail", good=True)
        for _ in range(10):
            t.record("avail", good=False)
        # bad fraction 0.1 over budget 0.01 = burn 10, both windows
        assert t.burn_rate("avail", 10.0) == pytest.approx(10.0)
        assert t.burn_rate("avail", 100.0) == pytest.approx(10.0)

    def test_windows_see_different_history(self):
        clock = _Clock(0.0)
        t = self._tracker(clock)
        for _ in range(99):              # old GOOD traffic at t=0
            t.record("avail", good=True)
        clock.t = 95.0                   # fast window [85, 95): bads only
        for _ in range(10):
            t.record("avail", good=False)
        fast = t.burn_rate("avail", 10.0)
        slow = t.burn_rate("avail", 100.0)
        assert fast == pytest.approx(100.0)   # 10/10 bad / 0.01
        assert slow == pytest.approx((10 / 109) / 0.01)
        assert fast > slow

    def test_unknown_objective_raises(self):
        t = self._tracker(_Clock())
        with pytest.raises(ValueError):
            t.record("nope", good=True)

    def test_buckets_pruned_past_slow_window(self):
        clock = _Clock(0.0)
        t = self._tracker(clock)
        for sec in range(300):
            clock.t = float(sec)
            t.record("avail", good=True)
        dq = t._buckets[("avail", "")]
        assert len(dq) <= 101            # bounded by the slow window
        assert t.burn_rate("avail", 100.0) == 0.0

    def test_per_tenant_series_isolated(self):
        clock = _Clock(10.0)
        t = self._tracker(clock)
        for _ in range(10):
            t.record("avail", good=False, tenant="bronze")
            t.record("avail", good=True, tenant="gold")
        assert t.burn_rate("avail", 10.0, tenant="bronze") \
            == pytest.approx(100.0)
        assert t.burn_rate("avail", 10.0, tenant="gold") == 0.0


class TestAlertDrill:
    def _burning_tracker(self, clock):
        t = SLOTracker([Objective("avail", target=0.99)],
                       fast_window_s=10.0, slow_window_s=100.0,
                       burn_threshold=2.0, min_events=5, now_fn=clock)
        return t

    def test_edge_triggered_alert_and_recovery(self):
        clock = _Clock(1000.0)
        t = self._burning_tracker(clock)
        for _ in range(10):
            t.record("avail", good=False)
        rows = t.scan()
        assert rows[0]["alerting"] is True
        assert len(t.alerts) == 1                 # the EDGE
        assert t.scan()[0]["alerting"] is True
        assert len(t.alerts) == 1                 # still firing: no new edge
        clock.t += 200.0                          # both windows drain
        # a fully-drained series is DROPPED (bounded key space), which
        # also resolves its alert
        assert t.scan() == []
        for _ in range(10):                       # second breach
            t.record("avail", good=False)
        assert t.scan()[0]["alerting"] is True
        assert len(t.alerts) == 2

    def test_stale_tenant_series_dropped(self):
        """Caller-supplied tenant ids must not grow the tracker forever:
        a series whose traffic drained past the slow window disappears
        from the bucket map on the next scan — and its burn-rate gauge
        children leave the registry too (a drained tenant must neither
        freeze at its last burn value on /metricsz nor accumulate
        label-value history)."""
        monitor.enable()
        clock = _Clock(0.0)
        t = self._burning_tracker(clock)
        for i in range(20):
            t.record("avail", good=True, tenant=f"t{i}")
        assert len(t._buckets) == 20
        t.scan()                                  # gauges materialize
        g = monitor.registry.get("paddle_tpu_monitor_slo_burn_rate")
        assert len(g.children()) == 40            # 20 series x 2 windows
        clock.t = 500.0                           # all past the slow window
        assert t.scan() == []
        assert t._buckets == {}
        assert g.children() == []

    def test_min_events_guards_fast_window(self):
        clock = _Clock(0.0)
        t = self._burning_tracker(clock)
        for _ in range(4):                        # < min_events
            t.record("avail", good=False)
        assert t.scan()[0]["alerting"] is False

    def test_both_windows_must_burn(self):
        clock = _Clock(0.0)
        t = self._burning_tracker(clock)
        for _ in range(990):                      # slow window: healthy
            t.record("avail", good=True)
        clock.t = 95.0
        for _ in range(10):                       # fast window: on fire
            t.record("avail", good=False)
        row = t.scan()[0]
        assert row["fast_burn"] >= 2.0
        assert row["slow_burn"] < 2.0
        assert row["alerting"] is False           # classic rule: need both

    def test_alert_telemetry_exported(self):
        monitor.enable()
        trace.enable()
        clock = _Clock(0.0)
        t = self._burning_tracker(clock)
        for _ in range(10):
            t.record("avail", good=False, tenant="gold")
        t.scan()
        snap = monitor.snapshot()["metrics"]
        alerts = snap["paddle_tpu_monitor_slo_alerts_total"]["values"]
        assert alerts["objective=avail/gold"] == 1
        burn = snap["paddle_tpu_monitor_slo_burn_rate"]["values"]
        assert burn["objective=avail/gold,window=fast"] >= 2.0
        names = [sp.name for sp in trace.spans()]
        assert "monitor.slo_alert" in names
        st = t.statusz()
        assert st["alerting"] == ["avail/gold"]
        assert st["recent_alerts"][0]["tenant"] == "gold"
