"""The serving engine's step phases, per-token times and the trace.phase
helper (ISSUE 29).

Contracts under test:

1. ``trace.phase`` — the shared no-op with both switches off (nothing
   constructed), a profiler annotation under either switch, a ring span
   with the same edges under span tracing, ``then`` handing over at one
   shared instant;
2. the engine's four phases (``serving.pack_tokens`` -> ``dispatch`` ->
   ``wait`` -> ``route``) are consecutive children of ``serving.step`` and
   sum to it, for a mixed step and for a burst; since ISSUE 37 a call
   prepares one step (schedule, dispatch) and fetches the one before it
   (wait, route): tests/test_step_in_flight.py holds the order of events;
3. ``paddle_tpu_serving_steps_total`` / ``..._step_phase_ns_total`` move by
   exactly one step's worth per ``step()`` and not at all for a step that
   returns early;
4. ``pop_stats(rid)["token_times_ns"]`` holds one time per token, always;
   ``paddle_tpu_serving_token_gap_ns`` observes the gaps under the monitor;
5. a profiled slice shows the step and its phases on a ``/host:`` plane;
6. a traced mesh step never lowers the program to fill its span's attrs;
7. ``paddle_tpu_serving_attn_blocks_total{extent}`` (ISSUE 30): read +
   skipped = lanes x table width per step, a burst counts every iteration,
   the plain path reads everything, the monitor off counts nothing.
"""
import glob
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.monitor import catalog, trace

PHASES = ["serving.pack_tokens", "serving.dispatch", "serving.wait",
          "serving.route"]
LABELS = ["schedule", "dispatch", "wait", "route"]


@pytest.fixture(autouse=True)
def _clean():
    monitor.disable()
    trace.disable()
    monitor.reset()
    yield
    monitor.disable()
    trace.disable()
    monitor.reset()


def _engine(decode_burst=4, max_batch=2):
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.serving import ContinuousBatchingEngine

    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=1, num_attention_heads=4,
                      num_key_value_heads=4, max_position_embeddings=64)
    return ContinuousBatchingEngine(LlamaForCausalLM(cfg),
                                    max_batch=max_batch, max_len=48,
                                    block_size=8, chunk_size=8,
                                    decode_burst=decode_burst)


def _warm(eng):
    """Compile both programs on a throw-away request, so that no step
    under test holds a compile."""
    eng.submit(np.array([7, 8, 9], np.int32), max_new_tokens=6)
    while eng.num_active or eng.num_pending:
        eng.step()


def _counter(name):
    return dict(monitor.snapshot()["metrics"][name]["values"])


def _moved(before, after):
    return {k: after[k] - before.get(k, 0.0) for k in after
            if after[k] != before.get(k, 0.0)}


# --------------------------------------------------------------------------- #
# trace.phase
# --------------------------------------------------------------------------- #

class _CountingAnnotation:
    made = 0

    def __init__(self, name):
        type(self).made += 1
        self.name = name

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.fixture
def counting_annotation(monkeypatch):
    _CountingAnnotation.made = 0
    monkeypatch.setattr(trace, "_annotation", _CountingAnnotation)
    return _CountingAnnotation


class TestPhaseHelper:
    def test_both_switches_off_is_the_shared_noop(self, counting_annotation):
        ctx = trace.phase("serving.step", attrs={"engine": "e"})
        assert ctx is trace._NOOP
        with ctx as sp:
            assert sp is None
        assert ctx.then("serving.wait") is ctx and ctx.span is None
        ctx.close()
        assert counting_annotation.made == 0
        assert trace.spans() == [] and trace.open_spans() == []

    @pytest.mark.parametrize("switch", ["monitor", "trace"])
    def test_either_switch_annotates_and_only_tracing_writes_the_ring(
            self, switch, counting_annotation):
        (monitor if switch == "monitor" else trace).enable()
        with trace.phase("serving.step") as sp:
            assert (sp is None) == (switch == "monitor")
        assert counting_annotation.made == 1
        assert [s.name for s in trace.spans()] == \
            ([] if switch == "monitor" else ["serving.step"])
        # switching off again gives the no-op back
        (monitor if switch == "monitor" else trace).disable()
        assert trace.phase("serving.step") is trace._NOOP

    def test_monitor_keeps_annotating_when_tracing_goes_off(self):
        monitor.enable()
        trace.enable()
        trace.disable()
        assert trace.phase("serving.step") is not trace._NOOP
        monitor.disable()
        assert trace.phase("serving.step") is trace._NOOP

    def test_then_shares_the_edge_and_the_parent(self, counting_annotation):
        trace.enable()
        root = trace.phase("serving.step")
        sp = root.__enter__()
        a = trace.phase("serving.pack_tokens", parent=sp, t0_ns=root.t0_ns)
        a.__enter__()
        a.attrs = {"n_decode": 3}
        b = a.then("serving.dispatch")
        b.close()
        b.close()                                   # a second close: no-op
        root.close(b.t1_ns)
        assert a.t0_ns == root.t0_ns and a.t1_ns == b.t0_ns
        assert root.t1_ns == b.t1_ns
        by = {s.name: s for s in trace.spans()}
        assert by["serving.pack_tokens"].attrs == {"n_decode": 3}
        assert by["serving.pack_tokens"].parent_id == sp.span_id
        assert by["serving.dispatch"].parent_id == sp.span_id
        assert (by["serving.pack_tokens"].t0_ns, by["serving.dispatch"].t1_ns) \
            == (sp.t0_ns, sp.t1_ns)
        assert counting_annotation.made == 3 and not trace.open_spans()

    def test_the_new_names_are_cataloged(self):
        for name in PHASES + ["serving.step", "mesh.step"]:
            assert catalog.span_spec(name), name
        assert catalog.spec("paddle_tpu_serving_step_phase_ns_total")[1] == \
            ("phase", "kind")
        assert catalog.spec("paddle_tpu_serving_steps_total")[1] == ("kind",)
        grid = catalog.TOKEN_GAP_NS_BUCKETS
        assert grid[0] == 0 and list(grid) == sorted(set(grid))
        fine = [b for b in grid if 10_000_000 <= b <= 2_000_000_000]
        assert fine[0] == 10_000_000 and fine[-1] == 2_000_000_000
        assert max(b / a for a, b in zip(fine, fine[1:])) <= 1.10


# --------------------------------------------------------------------------- #
# the engine's phases and counters
# --------------------------------------------------------------------------- #

def _one_step_of(kind):
    """An engine about to make one step of ``kind``, warmed up."""
    eng = _engine()
    _warm(eng)
    eng.submit(np.array([1, 2, 3, 4, 5], np.int32), max_new_tokens=12)
    if kind == "burst":
        eng.step()                      # the prefill: a mixed step
    return eng


def _in_flight(kind):
    """An engine with one step of ``kind`` in flight whose next call
    dispatches another of that kind (ISSUE 37: a call prepares one step
    and fetches the one before it), warmed up."""
    eng = _engine()
    _warm(eng)
    if kind == "mixed":
        # 13 prompt tokens: a chunk of 8 (in flight), then one of 5
        eng.submit(np.arange(1, 14, dtype=np.int32), max_new_tokens=12)
        eng.step()
    else:
        eng.submit(np.array([1, 2, 3, 4, 5], np.int32), max_new_tokens=12)
        eng.step()                      # the prefill: a mixed step
        eng.step()                      # the first burst; fetches the prefill
    assert eng._flight.kind == kind
    return eng


class TestEnginePhases:
    @pytest.mark.parametrize("kind", ["mixed", "burst"])
    def test_four_consecutive_phases_sum_to_the_step(self, kind):
        eng = _in_flight(kind)
        trace.enable()
        eng.step()
        trace.disable()
        spans = trace.spans()
        step = [s for s in spans if s.name == "serving.step"]
        assert len(step) == 1
        step = step[0]
        kids = [s for s in spans if s.parent_id == step.span_id]
        assert [s.name for s in kids] == PHASES
        for a, b in zip(kids, kids[1:]):
            assert a.t1_ns == b.t0_ns               # consecutive
        assert kids[0].t0_ns == step.t0_ns and kids[-1].t1_ns == step.t1_ns
        total = sum(s.duration_ns for s in kids)
        assert abs(total - step.duration_ns) <= 0.01 * step.duration_ns
        attrs = kids[0].attrs
        if kind == "mixed":
            assert attrs["n_prefill"] == 5 and attrs["n_decode"] == 0
        else:
            assert attrs == {"n_decode": 1, "burst": 4}
        assert not [s for s in trace.open_spans()
                    if s.name != "serving.request"]

    @pytest.mark.parametrize("kind", ["mixed", "burst"])
    def test_counters_move_by_exactly_one_steps_worth(self, kind):
        eng = _in_flight(kind)
        monitor.enable()
        trace.enable()
        steps0 = _counter("paddle_tpu_serving_steps_total")
        ns0 = _counter("paddle_tpu_serving_step_phase_ns_total")
        eng.step()
        trace.disable()
        monitor.disable()
        assert _moved(steps0, _counter("paddle_tpu_serving_steps_total")) \
            == {f"kind={kind}": 1.0}
        moved = _moved(ns0, _counter("paddle_tpu_serving_step_phase_ns_total"))
        by = {s.name: s for s in trace.spans()}
        assert moved == {f"phase={label},kind={kind}":
                         float(by[name].duration_ns)
                         for label, name in zip(LABELS, PHASES)}
        assert sum(moved.values()) == by["serving.step"].duration_ns

    def test_monitor_alone_counts_without_writing_a_span(self):
        eng = _in_flight("burst")
        monitor.enable()
        eng.step()
        eng.step()
        monitor.disable()
        assert _counter("paddle_tpu_serving_steps_total") == \
            {"kind=burst": 2.0}
        ns = _counter("paddle_tpu_serving_step_phase_ns_total")
        assert set(ns) == {f"phase={p},kind=burst" for p in LABELS}
        assert all(v > 0 for v in ns.values())
        assert trace.spans() == []

    def test_a_step_that_returns_early_counts_nothing(self):
        eng = _engine()
        _warm(eng)
        monitor.enable()
        trace.enable()
        assert eng.step() == []                     # no active lane
        assert _counter("paddle_tpu_serving_steps_total") == {}
        assert _counter("paddle_tpu_serving_step_phase_ns_total") == {}
        # the step and the one phase it reached are still on record
        assert [s.name for s in trace.spans()] == \
            ["serving.pack_tokens", "serving.step"]

    def test_a_step_that_raises_counts_nothing_and_leaves_no_open_phase(self):
        from paddle_tpu.analysis import faultinject as fi

        eng = _one_step_of("burst")
        monitor.enable()
        trace.enable()
        fi.arm("serving.step", action="raise", nth=1)
        try:
            with pytest.raises(Exception):
                eng.step()
        finally:
            fi.disarm()
        assert _counter("paddle_tpu_serving_steps_total") == {}
        assert not [s for s in trace.open_spans()
                    if s.name != "serving.request"]

    def test_both_switches_off_nothing_is_constructed_counted_or_written(
            self, counting_annotation):
        eng = _one_step_of("mixed")
        eng.step()
        eng.step()
        assert counting_annotation.made == 0
        assert eng._phase is trace._NOOP and eng._phases == ()
        assert trace.spans() == [] and trace.open_spans() == []
        assert _counter("paddle_tpu_serving_steps_total") == {}
        assert _counter("paddle_tpu_serving_step_phase_ns_total") == {}
        assert monitor.snapshot()["metrics"][
            "paddle_tpu_serving_token_gap_ns"]["values"][""]["count"] == 0


class TestAttnBlocks:
    """The engine of ``_engine()``: 2 slots, table 6 wide (max_len 48 /
    block 8), 10 lanes a mixed step (2 + chunk 8), bursts of 4."""
    NAME = "paddle_tpu_serving_attn_blocks_total"

    def _step(self, kind, monkeypatch, ragged):
        from paddle_tpu.models import paged_kv

        eng = _one_step_of(kind)
        if ragged:
            # what the engine counts where the kernel runs (a TPU): the
            # count is host arithmetic over the positions, so turning the
            # dispatch's predicate is enough (both programs are compiled)
            monkeypatch.setattr(paged_kv, "_kernel_applies",
                                lambda q, pool: True)
        monitor.enable()
        before = _counter(self.NAME)
        eng.step()
        monitor.disable()
        return _moved(before, _counter(self.NAME))

    def test_it_is_cataloged(self):
        assert catalog.spec(self.NAME)[:2] == ("counter", ("extent",))

    @pytest.mark.parametrize("kind,lanes,read", [
        # the 5-token prompt's chunk at positions 0..4 is ONE query tile: it
        # walks block 0 once (ISSUE 33; each lane on its own: 5)
        ("mixed", 10, 1),
        # one decode lane at positions 5, 6, 7 (block 0) and 8 (block 1)
        ("burst", 2 * 4, 3 * 1 + 2),
    ])
    def test_read_and_skipped_add_up_to_lanes_times_table_width(
            self, monkeypatch, kind, lanes, read):
        moved = self._step(kind, monkeypatch, ragged=True)
        assert moved == {"extent=read": float(read),
                         "extent=skipped": float(lanes * 6 - read)}

    @pytest.mark.parametrize("kind,lanes", [("mixed", 10), ("burst", 8)])
    def test_the_plain_path_reads_the_whole_table(self, monkeypatch, kind,
                                                  lanes):
        moved = self._step(kind, monkeypatch, ragged=False)
        assert moved == {"extent=read": float(lanes * 6)}

    def test_the_monitor_off_counts_nothing(self):
        eng = _one_step_of("mixed")
        eng.step()
        eng.step()
        assert _counter(self.NAME) == {}
        assert _counter(self.LANES) == {}

    # -- the lanes by the path that served them (ISSUE 33) -------------------
    LANES = "paddle_tpu_serving_attn_lanes_total"

    def test_the_lanes_counter_is_cataloged(self):
        assert catalog.spec(self.LANES)[:2] == ("counter", ("path",))
        assert catalog.spec(
            "paddle_tpu_serving_attn_kind_blocks_total")[:2] == (
                "counter", ("kind",))

    @pytest.mark.parametrize("kind,ragged,moved", [
        # the chunk's 5 lanes are a tile's; a burst's lanes walk alone
        ("mixed", True, {"path=tiled": 5.0}),
        ("burst", True, {"path=lane": 4.0}),
        # where no kernel runs there is no tile: the gather path reads all
        ("mixed", False, {"path=lane": 5.0}),
    ])
    def test_tiled_and_lane_add_up_to_the_valid_lanes(self, monkeypatch, kind,
                                                      ragged, moved):
        self.NAME, name = self.LANES, self.NAME
        try:
            assert self._step(kind, monkeypatch, ragged) == moved
        finally:
            self.NAME = name

    def test_a_longer_prompt_is_tiled_a_chunk_and_counted_a_block_once(
            self, monkeypatch):
        """A 13-token prompt behind a decoding request: its chunk of 8 at
        positions 0..7 is one tile over block 0; the next step carries the
        other 5 (positions 8..12, block 1: the tile walks blocks 0 and 1)
        beside the first request's decode lane."""
        from paddle_tpu.models import paged_kv

        eng = _one_step_of("burst")           # slot 0 decodes at position 5
        eng.submit(np.arange(1, 14, dtype=np.int32), max_new_tokens=4)
        monkeypatch.setattr(paged_kv, "_kernel_applies", lambda q, pool: True)
        monitor.enable()
        seen = []
        for _ in range(2):
            blocks, lanes = _counter(self.NAME), _counter(self.LANES)
            eng.step()
            seen.append((_moved(blocks, _counter(self.NAME)),
                         _moved(lanes, _counter(self.LANES))))
        monitor.disable()
        assert seen[0] == ({"extent=read": 1.0 + 1.0,
                            "extent=skipped": 10 * 6 - 2.0},
                           {"path=tiled": 8.0, "path=lane": 1.0})
        assert seen[1] == ({"extent=read": 1.0 + 2.0,
                            "extent=skipped": 10 * 6 - 3.0},
                           {"path=tiled": 5.0, "path=lane": 1.0})

    def test_the_benchmarks_metric_file_reads_the_lanes_counter(
            self, monkeypatch):
        """``attn_tiled_lane_share.sat`` is data: the accepted reader
        ``counter_share`` over this PR's counter. A mixed step with a tile
        of 5 lanes, then a burst of 4 lane-iterations: 5 of 9."""
        import importlib.util
        import json
        import os

        from paddle_tpu.models import paged_kv

        bench = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmarks")
        with open(os.path.join(bench, "metrics",
                               "attn_tiled_lane_share.sat.json")) as f:
            spec = json.load(f)
        assert spec["reader"] == "counter_share"
        mod = importlib.util.spec_from_file_location(
            "bench_counter_share",
            os.path.join(bench, "readers", "counter_share.py"))
        reader = importlib.util.module_from_spec(mod)
        mod.loader.exec_module(reader)
        monitor.reset()
        # (the parent of this PR has no such counter: nothing, and no raise)
        assert reader.read({}, spec["params"], {}) is None
        eng = _one_step_of("mixed")
        monkeypatch.setattr(paged_kv, "_kernel_applies",
                            lambda q, pool: True)
        monitor.enable()
        eng.step()
        eng.step()
        monitor.disable()
        assert eng._step_kind == "burst"
        value, note = reader.read({}, spec["params"], {})
        assert value == pytest.approx(100.0 * 5 / 9)
        assert note[self.LANES] == {"path=tiled": 5.0, "path=lane": 4.0}

    def test_the_benchmarks_metric_file_reads_the_dispatch_counter(self):
        """``steps_dispatched_ahead_share.sat`` (ISSUE 37) is data: the
        accepted reader ``counter_share`` over this PR's counter. A request
        served from an empty engine: its first step follows nothing, every
        other one is dispatched while the step before it is unfetched."""
        import importlib.util
        import json
        import os

        bench = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmarks")
        with open(os.path.join(
                bench, "metrics",
                "steps_dispatched_ahead_share.sat.json")) as f:
            spec = json.load(f)
        assert spec["reader"] == "counter_share"
        assert catalog.spec(spec["params"]["counter"])[:2] == (
            "counter", ("ahead",))
        with open(os.path.join(os.path.dirname(bench),
                               "BENCHMARK.json")) as f:
            (entry,) = [m for m in json.load(f)["per_layer"]
                        if m["name"] == "steps_dispatched_ahead_share.sat"]
        assert entry["layer"] == "serving scheduler" and \
            entry["moves"] == "serve_tokens_per_s" and len(
                entry["workloads"]) == 3
        mod = importlib.util.spec_from_file_location(
            "bench_counter_share",
            os.path.join(bench, "readers", "counter_share.py"))
        reader = importlib.util.module_from_spec(mod)
        mod.loader.exec_module(reader)
        monitor.reset()
        # (the parent of this PR has no such counter: nothing, and no raise)
        assert reader.read({}, spec["params"], {}) is None
        eng = _engine()
        _warm(eng)
        monitor.enable()
        eng.submit(np.array([1, 2, 3, 4, 5], np.int32), max_new_tokens=12)
        calls = 0
        while eng.num_active or eng.num_pending:
            eng.step()
            calls += 1
        monitor.disable()
        # the last call dispatches nothing: calls - 1 steps, the first of
        # them behind an empty engine
        value, note = reader.read({}, spec["params"], {})
        assert calls == 5
        assert value == pytest.approx(100.0 * 3 / 4)
        assert note["paddle_tpu_serving_dispatch_total"] == {
            "ahead=no": 1.0, "ahead=yes": 3.0}

    def test_kind_blocks_under_a_window_kind(self):
        """``attn_kind_blocks_total`` of a window layer: a lane of its own
        from its window's first block, a tile from its FIRST lane's window's
        first block to its last lane's last; counted by the code that plans
        the tiles on the device."""
        from paddle_tpu.ops.pallas import paged_attention as pa

        rows = np.array([0] + [1] * 12, np.int32)
        pos = np.array([40] + list(range(20, 32)), np.int32)
        plan = pa.plan_tiles(rows, pos, 32, np)
        assert list(plan["n"][:2]) == [12, 0] and plan["tiles"] == 1
        # block 8, window 16: the lane at 40 reads blocks 3..5; the tile's
        # first lane (20) sees from 5 = block 0, its last (31) ends block 3
        assert pa.blocks_walked(pos, 8, plan=plan, window=16) == (3 + 4, 12)
        assert pa.blocks_walked(pos, 8, plan=plan) == (6 + 4, 12)
        # per lane, as before the tiles: 3 blocks each (a window of 16
        # straddles three blocks of 8 unless it ends one)
        assert pa.blocks_walked(pos, 8, window=16) == (
            int(sum(p // 8 - max(p - 15, 0) // 8 + 1 for p in pos)), 0)


# --------------------------------------------------------------------------- #
# per-token times
# --------------------------------------------------------------------------- #

def _run_to_end(eng, prompts, max_new):
    rids = [eng.submit(np.asarray(p, np.int32), max_new_tokens=max_new)
            for p in prompts]
    out = {}
    while eng.num_active or eng.num_pending:
        for rid, tokens in eng.step():
            out[rid] = (tokens, eng.pop_stats(rid))
    return [out[r] for r in rids]


class TestTokenTimes:
    @pytest.mark.parametrize("monitored", [False, True])
    def test_one_nondecreasing_time_per_token(self, monitored):
        eng = _engine()
        _warm(eng)
        if monitored:
            monitor.enable()
        got = _run_to_end(eng, [[1, 2, 3], list(range(1, 14))], 10)
        for tokens, st in got:
            times = st["token_times_ns"]
            assert len(times) == len(tokens) == st["tokens"] == 10
            assert times[0] == st["submit_ns"] + st["ttft_ns"]
            assert all(b >= a for a, b in zip(times, times[1:]))
            assert all(isinstance(t, int) for t in times)
        # the tokens of one burst share its time: among ten tokens of a
        # request at burst 4 some neighbours are equal, and no more than
        # four in a row
        times = got[0][1]["token_times_ns"]
        runs = [sum(1 for t in times if t == u) for u in sorted(set(times))]
        assert max(runs) == 4
        hist = monitor.snapshot()["metrics"][
            "paddle_tpu_serving_token_gap_ns"]["values"][""]
        if not monitored:
            assert hist["count"] == 0
            return
        # every token after a request's first observed its gap; the zero
        # bucket holds the gaps inside bursts
        gaps = [b - a for _, st in got
                for a, b in zip(st["token_times_ns"],
                                st["token_times_ns"][1:])]
        assert hist["count"] == len(gaps) == 18
        assert hist["buckets"][0] == [0, sum(1 for g in gaps if g == 0)]
        assert hist["sum"] == float(sum(gaps))

    def test_an_aborted_request_carries_its_times(self):
        eng = _engine()
        _warm(eng)
        rid = eng.submit(np.array([1, 2, 3], np.int32), max_new_tokens=20)
        eng.step()
        eng.step()                      # routes the prefill's token
        eng.step()                      # ... and the first burst's four
        eng.recover(reason="test")
        (rec,) = eng.pop_aborted()
        assert rec.rid == rid and len(rec.tokens) >= 2
        assert len(rec.stats["token_times_ns"]) == len(rec.tokens)


# --------------------------------------------------------------------------- #
# on the profiler's own trace
# --------------------------------------------------------------------------- #

def _host_events(directory):
    import jax

    (path,) = glob.glob(os.path.join(directory, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((e.name, e.start_ns, e.duration_ns)
                           for e in line.events)
    return out


def test_a_profiled_slice_holds_the_step_and_its_phases_on_a_host_plane(
        tmp_path):
    import jax

    eng = _in_flight("mixed")
    monitor.enable()                 # the monitor alone, as a traced bench run
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.eng_step"):
            eng.step()
        eng.step()
        eng.step()
    finally:
        jax.profiler.stop_trace()
    events = _host_events(str(tmp_path))
    names = [n for n, _, _ in events]
    for name in ["serving.step"] + PHASES:
        assert names.count(name) == 3, (name, names.count(name))
    # on one clock: the first step and its phases lie inside bench.eng_step
    (outer,) = [e for e in events if e[0] == "bench.eng_step"]
    first = sorted(e for e in events if e[0] == "serving.step")[0]
    assert outer[1] <= first[1] and \
        first[1] + first[2] <= outer[1] + outer[2] + 1
    assert trace.spans() == []       # span tracing was never on


# --------------------------------------------------------------------------- #
# the mesh step
# --------------------------------------------------------------------------- #

def test_a_traced_mesh_step_leaves_the_collective_census_as_it_found_it(
        mesh8, counting_annotation):
    from paddle_tpu import mesh as pmesh

    paddle.seed(0)
    model = paddle.nn.Linear(4, 4)
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=model.parameters())
    x = np.ones((8, 4), "float32")
    y = np.zeros((8, 4), "float32")

    def loss(m, a, b):
        return ((m(a) - b) ** 2).mean()

    mp = pmesh.parallelize(model, opt, loss, (x, y),
                           config={"dp_degree": 8})
    mp.step(x, y)                                   # the one compile
    trace.enable()
    mp.step(x, y)
    assert mp._collectives is None and mp._collective_bytes is None
    assert mp._closed_jaxpr is None and mp._hlo_text is None
    span = [s for s in trace.spans() if s.name == "comm.mesh_step"][-1]
    assert span.attrs == {"dp": 8, "step": 2, "zero": False}
    enqueue = [s for s in trace.spans() if s.name == "mesh.step"]
    assert len(enqueue) == 1 and counting_annotation.made == 1
    assert span.t0_ns <= enqueue[0].t0_ns <= enqueue[0].t1_ns <= span.t1_ns
    # asked for outside a step, the census rides the next step's span
    counts = mp.collective_counts(x, y)
    mp.collective_bytes(x, y)
    mp.step(x, y)
    span = [s for s in trace.spans() if s.name == "comm.mesh_step"][-1]
    assert counts.get("all_reduce", 0) >= 1
    assert span.attrs["all_reduce"] == counts["all_reduce"]
    assert span.attrs["all_reduce_bytes"] > 0
