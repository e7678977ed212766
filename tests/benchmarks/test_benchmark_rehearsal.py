"""One rehearsal of each cell end to end, as the driver runs it, and the rest
of a run with the timed path broken underneath: ``correct`` has to come out
false for each fault the cell can have."""
import functools
import json

import numpy as np
import pytest

import _bench_util as U

BENCH = U.benchmark_json()
CELLS = [w["name"] for w in BENCH["workloads"]]


def _metric_names(group, cell):
    return {m["name"] for m in BENCH[group]
            if "workloads" not in m or cell in m["workloads"]}


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_prints_the_contracts_line(cell):
    rc, res, out, err = U.run_cell(cell, 4294967311, 3)
    assert rc == 0, err[-3000:]
    assert U.RESULT_KEYS <= set(res) and list(res)[-1] == "compared"
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == _metric_names("end_to_end", cell)
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["device"]["platform"] == "cpu"            # never a tpu
    assert {"kind", "count", "memory_peak_bytes"} <= set(res["device"])
    # every number compared stands beside its limit, last on standard error
    tail = [line for line in err.strip().splitlines()][-len(res["compared"]):]
    assert all(line.startswith("compared ") and " limit " in line for line in tail)
    assert res["compared"]["compiled_in_window"]["value"] == 0


@functools.lru_cache(maxsize=None)
def _traced_rehearsal(cell):
    """One traced rehearsal a cell, read by every test that needs one."""
    return U.run_cell(cell, 12345, 4, trace=1)


@pytest.mark.parametrize("cell", CELLS)
def test_traced_rehearsal_reads_the_per_layer_metrics_and_the_breakdown(cell):
    rc, res, out, err = _traced_rehearsal(cell)
    assert rc == 0, err[-3000:]
    assert res["correct"] is True
    # what needs a published peak (mfu, roofline) has nothing to read on the CPU
    expected = {n for n in _metric_names("per_layer", cell)
                if "mfu" not in n and "roofline" not in n}
    assert set(res["metrics"]) == expected
    assert res["device"]["busy_s"] > 0 and res["device"]["window_s"] > 0
    assert 0 < len(res["breakdown"]["device_ops"]) <= 10
    assert len(res["breakdown"]["idle_gaps"]) <= 10
    # busy and window are one clock's, the operations': never busier than
    # the window, whatever the host's clock read at the profiler's edges
    assert res["device"]["busy_s"] <= res["device"]["window_s"]
    said = next(json.loads(line)["traced_slice"] for line in out.splitlines()
                if line.startswith('{"traced_slice"'))
    assert said["busy_s"] == res["device"]["busy_s"]
    assert said["device_window_s"] == res["device"]["window_s"]
    assert said["host_slice_s"] > 0 and isinstance(said["programs"], dict)


@pytest.mark.parametrize("cell", CELLS)
def test_every_share_of_a_traced_rehearsal_lies_between_0_and_100(cell):
    """The contract of a unit ``%``, over every per-layer metric the traced
    rehearsal reads: none under 0 (an idle share of a device busier than its
    window) and none over 100."""
    rc, res, out, err = _traced_rehearsal(cell)
    assert rc == 0, err[-3000:]
    shares = {name: m["value"] for name, m in res["metrics"].items()
              if m["unit"] == "%"}
    assert shares and any(n.startswith("device_idle_share") for n in shares)
    for name, value in shares.items():
        assert 0.0 <= value <= 100.0, (name, value)


def _train_cell():
    return next(w["name"] for w in BENCH["workloads"] if "train" in w["name"])


def _serve_cell():
    return next(w["name"] for w in BENCH["workloads"] if "serve" in w["name"])


def _state_unchanged(handle):
    import jax.numpy as jnp

    real = handle.step

    def step(*batch):
        saved = ([jnp.copy(v) for v in handle._pv],
                 [[jnp.copy(v) for v in row] for row in handle._av],
                 [jnp.copy(v) for v in handle._mv])
        loss = real(*batch)
        handle.set_state(*saved)
        return loss
    handle.step = step


def _half_batch(handle):
    real = handle.step

    def step(ids, labels):
        half = len(ids) // 2
        # the mean over the first half alone: the rest is left out
        return real(np.concatenate([ids[:half]] * 2),
                    np.concatenate([labels[:half]] * 2))
    handle.step = step


def _altered_token(eng):
    real = eng.step

    def step(*a, **k):
        out = []
        for rid, tokens in real(*a, **k):
            tokens = list(tokens)
            tokens[len(tokens) // 2] = (tokens[len(tokens) // 2] + 1) % 256
            out.append((rid, tokens))
        return out
    eng.step = step


@pytest.mark.parametrize("fault,failing", [
    (_state_unchanged, "change_norm_gap"),
    (_half_batch, "grad_norm_gap"),
])
def test_a_broken_train_step_reads_not_correct(fault, failing):
    res, err = U.run_cell_with_fault(_train_cell(), 99, 1, fault)
    assert res["correct"] is False
    c = res["compared"][failing]
    assert c["value"] > c["limit"]
    assert "NOT OK" in err


def test_an_altered_served_token_reads_not_correct():
    res, err = U.run_cell_with_fault(_serve_cell(), 99, 2, _altered_token)
    assert res["correct"] is False
    c = res["compared"]["token_logit_gap"]
    assert np.isfinite(c["value"]) and c["value"] > c["limit"]


def test_the_unbroken_path_reads_correct_in_process():
    res, _ = U.run_cell_with_fault(_train_cell(), 99, 1, lambda handle: None)
    assert res["correct"] is True


def test_slice_counters_are_the_increase_between_the_profilers_start_and_stop(
        tmp_path):
    """The serve driver in this process, traced, with the engine's steps
    counted from outside: what it hands the readers as ``slice_counters`` is
    what the program counted for the steps made between the profiler's start
    and its stop, not for the window and not for the whole run."""
    run = U.load("", "run")
    _, _, cfg, traffic, _ = run.load_cell(_serve_cell(), True)
    run.set_environment(True)
    import common
    from paddle_tpu import monitor

    calls = []

    def count_steps(eng):
        real = eng.step

        def step(*a, **k):
            out = real(*a, **k)
            calls.append(1)
            return out
        eng.step = step

    class Tracer(common.SliceTracer):
        def start(self):
            self.calls_at_start = len(calls)
            super().start()

        def stop(self):
            super().stop()
            self.calls_at_stop = len(calls)

    tracer = Tracer(str(tmp_path))
    ctx = run.context(cfg, traffic, 2 ** 31 + 41, 4.0, True, tracer, count_steps)
    try:
        raw = run._module("drivers", cfg["driver"]).run(ctx)
        whole = monitor.snapshot()["metrics"]
    finally:
        monitor.disable()
        monitor.reset()
    sliced = raw["slice_counters"]
    steps = "paddle_tpu_serving_steps_total"
    in_slice = tracer.calls_at_stop - tracer.calls_at_start
    assert 0 < in_slice < len(calls)
    assert sum(sliced[steps].values()) == in_slice
    assert sum(whole[steps]["values"].values()) > in_slice      # window + drain
    # the window's own counter (read at its edges) has run for longer
    tokens = "paddle_tpu_serving_generated_tokens_total"
    assert 0 < sliced[tokens][""] < raw["counters"][tokens]
    assert 0 < sliced["paddle_tpu_serving_attn_blocks_total"]["extent=read"] \
        < whole["paddle_tpu_serving_attn_blocks_total"]["values"]["extent=read"]
    assert raw["slice_seconds"] == pytest.approx(tracer.t_stop - tracer.t_start)
    assert raw["slice_seconds"] < raw["window_s"]
    # the driver gave the tracer a wait of its own for both edges, and what the
    # program counted at DISPATCH is the slice's steps too: every call between
    # the edges dispatched one
    assert tracer.rest is not None
    assert raw["notes"]["slice_dispatches"] == in_slice


def test_the_tracer_waits_for_the_device_before_it_reads_each_edge(
        tmp_path, monkeypatch):
    """The order of calls at the two edges, recorded on the CPU: the wait for
    the device, then the counters, then the profiler's start; the wait, then
    the counters, then its stop. Without a wait or a reader it is the
    profiler alone."""
    import jax

    run = U.load("", "run")
    run.set_environment(True)
    import common

    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda directory: calls.append("start_trace"))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append("stop_trace"))
    tracer = common.SliceTracer(str(tmp_path))
    tracer.rest = lambda: calls.append("rest")
    tracer.read_at_edges = lambda: calls.append("read") or len(calls)
    assert not tracer.running
    tracer.start()
    assert tracer.running and tracer.t_stop is None
    tracer.stop()
    assert calls == ["rest", "read", "start_trace",
                     "rest", "read", "stop_trace"]
    assert tracer.edges == [2, 5] and not tracer.running
    assert tracer.t_stop >= tracer.t_start
    calls.clear()
    bare = common.SliceTracer(str(tmp_path))
    bare.start()
    bare.stop()
    assert calls == ["start_trace", "stop_trace"] and bare.edges == []


def test_the_wait_for_the_device_compiles_in_set_up_and_never_again():
    run = U.load("", "run")
    run.set_environment(True)
    import jax

    import common

    compiles = common.Compiles()
    rest = common.device_rest(jax.devices()[0])
    made = compiles.n
    assert made >= 1                     # its one program, warmed up at once
    for _ in range(3):
        assert rest() is None
    assert compiles.n == made            # nothing compiles at an edge


def test_an_untraced_run_reads_no_slice_counters_and_never_turns_the_monitor_on():
    from paddle_tpu import monitor

    run = U.load("", "run")
    _, _, cfg, traffic, _ = run.load_cell(_serve_cell(), True)
    run.set_environment(True)
    ctx = run.context(cfg, traffic, 77, 1.0, True)
    raw = run._module("drivers", cfg["driver"]).run(ctx)
    assert "slice_counters" not in raw and not monitor.enabled()
