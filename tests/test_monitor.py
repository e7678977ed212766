"""Telemetry subsystem tests (ISSUE 1): registry semantics, thread safety,
disabled-mode no-op + overhead budget, instrumented dispatch/JIT/KV/
dataloader, Prometheus exposition validity, provenance, chrome-trace
counter merge, metric-name lint, and the serving-loop integration
acceptance run."""
import json
import math
import os
import re
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.monitor.registry import (Counter, Gauge, Histogram, Registry,
                                         _RESERVOIR_SIZE)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_monitor():
    """Every test starts disabled/zeroed and cannot leak enabled-mode
    overhead into the rest of the suite."""
    monitor.disable()
    monitor.reset()
    yield
    monitor.disable()
    monitor.reset()


# --------------------------------------------------------------------------- #
# registry primitives
# --------------------------------------------------------------------------- #

class TestRegistryPrimitives:
    def test_counter_inc_and_negative_rejected(self):
        r = Registry()
        c = r.counter("test_counter_total")
        c.inc()
        c.inc(4)
        assert c.value == 5
        with pytest.raises(ValueError, match="only go up"):
            c.inc(-1)

    def test_labeled_counter_children(self):
        r = Registry()
        c = r.counter("test_ops_total", labelnames=("op",))
        c.labels("add").inc(2)
        c.labels(op="mul").inc()
        assert c.labels("add").value == 2
        assert c.labels("mul").value == 1
        assert dict((lv, ch.value) for lv, ch in c.children()) == {
            ("add",): 2, ("mul",): 1}
        with pytest.raises(ValueError, match="labeled"):
            c.inc()  # parent of a labeled family is not a series

    def test_gauge_set_inc_dec(self):
        r = Registry()
        g = r.gauge("test_gauge")
        g.set(10)
        g.inc(5)
        g.dec(3)
        assert g.value == 12.0

    def test_histogram_bucket_boundaries(self):
        """Observations land in the FIRST bucket whose bound is >= value
        (le semantics, boundary inclusive); cumulative counts terminate in
        +Inf == count."""
        r = Registry()
        h = r.histogram("test_hist", buckets=(10, 100, 1000))
        for v in (5, 10, 11, 100, 500, 5000):
            h.observe(v)
        cum = dict(h.cumulative_buckets())
        assert cum[10] == 2        # 5, 10 (boundary is inclusive)
        assert cum[100] == 4       # + 11, 100
        assert cum[1000] == 5      # + 500
        assert cum[float("inf")] == 6 == h.count
        assert h.sum == 5 + 10 + 11 + 100 + 500 + 5000

    def test_histogram_fixed_buckets_sorted(self):
        r = Registry()
        h = r.histogram("test_hist_sorted", buckets=(100, 1, 10))
        assert h.buckets == (1, 10, 100)

    def test_histogram_reservoir_bounded_and_percentiles(self):
        r = Registry()
        h = r.histogram("test_res", buckets=(1e9,))
        n = _RESERVOIR_SIZE * 4
        for v in range(n):
            h.observe(v)
        assert h.count == n
        assert len(h._reservoir) == _RESERVOIR_SIZE  # bounded memory
        p50, p99 = h.percentile(50), h.percentile(99)
        assert p50 is not None and p99 is not None and p50 <= p99

    def test_histogram_time_context_manager(self):
        r = Registry()
        h = r.histogram("test_span")
        with h.time():
            time.sleep(0.01)
        assert h.count == 1
        assert h.sum >= 5e6  # at least ~5ms in ns

    def test_reregistration_type_conflict_rejected(self):
        r = Registry()
        r.counter("test_conflict_total")
        with pytest.raises(ValueError, match="already registered"):
            r.gauge("test_conflict_total")
        with pytest.raises(ValueError, match="already registered"):
            r.counter("test_conflict_total", labelnames=("x",))

    def test_catalog_mismatch_rejected(self):
        with pytest.raises(ValueError, match="cataloged"):
            monitor.gauge("paddle_tpu_dispatch_op_calls_total")

    def test_labels_on_unlabeled_metric_rejected(self):
        r = Registry()
        c = r.counter("test_unlabeled_total")
        with pytest.raises(ValueError, match="not a labeled metric"):
            c.labels()  # would otherwise create a hidden dead series

    def test_labels_positional_and_keyword_rejected(self):
        r = Registry()
        h = r.histogram("test_label_conflict", labelnames=("op",))
        with pytest.raises(ValueError, match="not both"):
            h.labels("add", op="mul")

    def test_rereg_bucket_mismatch_rejected(self):
        r = Registry()
        r.histogram("test_grid", buckets=(1, 2, 3))
        r.histogram("test_grid")                    # no buckets: accepts
        r.histogram("test_grid", buckets=(3, 2, 1))  # same grid, any order
        with pytest.raises(ValueError, match="buckets"):
            r.histogram("test_grid", buckets=(10, 20))

    def test_invalid_names_rejected(self):
        r = Registry()
        with pytest.raises(ValueError, match="invalid metric name"):
            r.counter("9starts_with_digit")
        with pytest.raises(ValueError, match="invalid label name"):
            r.counter("test_total", labelnames=("bad-label",))


class TestConcurrency:
    def test_concurrent_counter_increments_exact(self):
        r = Registry()
        c = r.counter("test_mt_total", labelnames=("who",))
        h = r.histogram("test_mt_hist", buckets=(10, 1000))
        n_threads, per_thread = 8, 2000
        start = threading.Barrier(n_threads)

        def work(i):
            child = c.labels(f"t{i % 2}")
            start.wait()
            for k in range(per_thread):
                child.inc()
                h.observe(k % 20)

        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        total = sum(ch.value for _, ch in c.children())
        assert total == n_threads * per_thread  # locked: exact, not racy
        assert h.count == n_threads * per_thread
        assert dict(h.cumulative_buckets())[float("inf")] == h.count


# --------------------------------------------------------------------------- #
# disabled-mode behavior + overhead budget
# --------------------------------------------------------------------------- #

def _floor_us(f, n=60):
    import gc

    f()  # warm: fills the per-signature caches (jit trace on first backward)
    gc.collect()
    ts = []
    for _ in range(7):
        t0 = time.perf_counter()
        for _ in range(n):
            f()
        ts.append((time.perf_counter() - t0) / n * 1e6)
    return min(ts)


class TestDisabledMode:
    def test_disabled_dispatch_records_nothing(self):
        x = paddle.to_tensor(np.ones((2, 2), "float32"))
        y = paddle.to_tensor(np.ones((2, 2), "float32"))
        (x + y) @ y
        snap = monitor.snapshot()
        calls = snap["metrics"].get("paddle_tpu_dispatch_op_calls_total",
                                    {"values": {}})["values"]
        assert all(v == 0 for v in calls.values())
        hist = snap["metrics"].get("paddle_tpu_dispatch_latency_ns")
        if hist is not None:
            assert all(s["count"] == 0 for s in hist["values"].values())

    def test_disabled_sample_is_noop(self):
        monitor.sample()
        assert monitor.chrome_counter_events() == []

    def test_disabled_dispatch_overhead_within_forward_budget(self):
        """Tier-1 overhead budget: with the monitor disabled the
        instrumented dispatch path must stay inside the SAME 40us forward
        budget tests/test_dispatch_perf.py enforces — the telemetry layer
        may not tax the eager hot path when off.

        Retry-on-load pattern (PR 4): run standalone on a loaded 1-core
        box, one min-of-7 floor can still eat a scheduler storm and
        false-alarm; a real overhead regression raises the floor itself
        and fails EVERY attempt, so up to three attempts keep the budget
        meaningful without the flake."""
        y = paddle.to_tensor(np.random.randn(4, 4).astype("float32"))
        xg = paddle.to_tensor(np.random.randn(4, 4).astype("float32"),
                              stop_gradient=False)
        us = None
        for _attempt in range(3):
            us = _floor_us(lambda: xg + y)
            if us < 40:
                return
        assert us < 40, \
            f"monitor-off dispatch {us:.0f}us exceeds 40us budget (3 tries)"


# --------------------------------------------------------------------------- #
# instrumented subsystems
# --------------------------------------------------------------------------- #

class TestInstrumentedDispatch:
    def test_op_counts_and_latency(self):
        monitor.enable()
        x = paddle.to_tensor(np.ones((2, 2), "float32"))
        y = paddle.to_tensor(np.ones((2, 2), "float32"))
        x + y
        x + y
        x @ y
        snap = monitor.snapshot()
        calls = snap["metrics"]["paddle_tpu_dispatch_op_calls_total"]["values"]
        assert calls["op=add"] == 2
        assert calls["op=matmul"] == 1
        lat = snap["metrics"]["paddle_tpu_dispatch_latency_ns"]["values"][""]
        assert lat["count"] == 3
        assert lat["sum"] > 0

    def test_amp_cast_counter(self):
        monitor.enable()
        x = paddle.to_tensor(np.ones((2, 2), "float32"))
        y = paddle.to_tensor(np.ones((2, 2), "float32"))
        with paddle.amp.auto_cast(level="O1", dtype="bfloat16"):
            x @ y
        c = monitor.registry.get("paddle_tpu_dispatch_amp_casts_total")
        assert c.value == 2  # both matmul inputs cast f32 -> bf16


class TestInstrumentedJit:
    def test_compiles_hits_signatures(self):
        from paddle_tpu import jit

        monitor.enable()

        @jit.to_static
        def f(a):
            return a * 2 + 1

        x = paddle.to_tensor(np.ones((2, 2), "float32"))
        f(x)           # compile (signature 1)
        f(x)           # hit
        f(x)           # hit
        f(paddle.to_tensor(np.ones((3, 3), "float32")))  # compile (sig 2)
        snap = monitor.snapshot()["metrics"]
        assert snap["paddle_tpu_jit_compiles_total"]["values"][
            "function=f"] == 2
        assert snap["paddle_tpu_jit_cache_hits_total"]["values"][
            "function=f"] == 2
        assert snap["paddle_tpu_jit_cached_signatures"]["values"][
            "function=f"] == 2
        tc = snap["paddle_tpu_jit_trace_compile_seconds"]["values"][""]
        assert tc["count"] == 2 and tc["sum"] > 0


class TestInstrumentedKV:
    def _pool(self, num_blocks=9, batch=2):
        from paddle_tpu.models.paged_kv import PagedKVCache

        return PagedKVCache(num_layers=1, num_blocks=num_blocks, block_size=4,
                            kv_heads=1, head_dim=4, batch=batch,
                            max_blocks_per_seq=4)

    def test_free_block_gauge_tracks_allocator(self):
        monitor.enable()
        pk = self._pool()
        pk.ensure_capacity([8, 4])
        g = monitor.registry.get("paddle_tpu_kv_free_blocks")
        assert g.value == len(pk._free) == 5
        pk.free_sequence(0)
        assert g.value == len(pk._free) == 7
        # consistency with refcounts: free blocks = unreferenced - null block
        assert g.value == int((pk._refs == 0).sum()) - 1

    def test_pool_exhaustion_counter(self):
        monitor.enable()
        pk = self._pool(num_blocks=3)
        with pytest.raises(RuntimeError, match="exhausted"):
            pk.ensure_capacity([8, 8])
        c = monitor.registry.get("paddle_tpu_kv_pool_exhausted_total")
        assert c.value == 1

    def test_exhaustion_keeps_device_table_synced(self):
        """Partial grants made before a pool-exhaustion raise must still
        reach the device table — a caller that catches the error would
        otherwise decode against a stale device copy."""
        monitor.enable()
        pk = self._pool(num_blocks=3)   # 2 usable blocks
        with pytest.raises(RuntimeError, match="exhausted"):
            pk.ensure_capacity([8, 8])  # row 0 granted both, row 1 raises
        np.testing.assert_array_equal(np.asarray(pk.block_tables),
                                      pk._tables_np)
        assert (pk._tables_np[0] > 0).sum() == 2  # row 0's grant survived

    def test_cow_copy_counter(self):
        import jax.numpy as jnp

        monitor.enable()
        pk = self._pool()
        pk.ensure_capacity([4, 0])
        pk.fork_rows([0, 0])      # row 1 shares row 0's block
        pools = [(pk.k[0], pk.v[0])]
        pools = pk.make_tail_exclusive(0, pools)
        c = monitor.registry.get("paddle_tpu_kv_cow_copies_total")
        assert c.value == 1       # one shared tail block copied
        g = monitor.registry.get("paddle_tpu_kv_free_blocks")
        assert g.value == len(pk._free)


class TestInstrumentedDataloader:
    def test_batches_and_fetch_latency(self):
        from paddle_tpu.io import DataLoader, Dataset

        class DS(Dataset):
            def __len__(self):
                return 12

            def __getitem__(self, i):
                return np.full((3,), i, "float32")

        monitor.enable()
        loader = DataLoader(DS(), batch_size=4, num_workers=0)
        batches = list(loader)
        assert len(batches) == 3
        c = monitor.registry.get("paddle_tpu_dataloader_batches_total")
        h = monitor.registry.get("paddle_tpu_dataloader_fetch_latency_ns")
        assert c.value == 3
        assert h.count == 3


# --------------------------------------------------------------------------- #
# exporters
# --------------------------------------------------------------------------- #

_SAMPLE_RE = re.compile(
    r'^([a-z_][a-z0-9_]*)(\{[^}]*\})?\s'
    r'([-+]?(\d+(\.\d+)?([eE][-+]?\d+)?|\.\d+)|[-+]?Inf|NaN)$')


def _parse_prometheus(text):
    """Strict parser for the exposition format: returns {series: value} and
    raises AssertionError on any malformed line."""
    series = {}
    types = {}
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# HELP "):
            assert re.match(r"^# HELP [a-z_][a-z0-9_]* \S", line), line
            continue
        if line.startswith("# TYPE "):
            parts = line.split(" ")
            assert len(parts) == 4 and parts[3] in (
                "counter", "gauge", "histogram"), line
            types[parts[2]] = parts[3]
            continue
        m = _SAMPLE_RE.match(line)
        assert m, f"malformed sample line: {line!r}"
        series[m.group(1) + (m.group(2) or "")] = float(
            m.group(3).replace("Inf", "inf"))
    return series, types


class TestExporters:
    def _populate(self):
        monitor.enable()
        x = paddle.to_tensor(np.ones((2, 2), "float32"))
        x + x
        monitor.histogram("paddle_tpu_dispatch_latency_ns")  # ensure present

    def test_prometheus_text_parses(self):
        self._populate()
        text = monitor.prometheus_text()
        series, types = _parse_prometheus(text)
        assert types["paddle_tpu_dispatch_op_calls_total"] == "counter"
        assert types["paddle_tpu_dispatch_latency_ns"] == "histogram"
        assert series['paddle_tpu_dispatch_op_calls_total{op="add"}'] == 1.0

    def test_prometheus_histogram_invariants(self):
        self._populate()
        text = monitor.prometheus_text()
        series, _ = _parse_prometheus(text)
        buckets = sorted(
            ((float(re.search(r'le="([^"]+)"', k).group(1)
                    .replace("+Inf", "inf")), v)
             for k, v in series.items()
             if k.startswith("paddle_tpu_dispatch_latency_ns_bucket")),
        )
        counts = [v for _, v in buckets]
        assert counts == sorted(counts), "bucket counts must be cumulative"
        assert buckets[-1][0] == math.inf
        assert buckets[-1][1] == series["paddle_tpu_dispatch_latency_ns_count"]

    def test_snapshot_provenance_real_and_valid(self):
        snap = monitor.snapshot()
        prov = snap["provenance"]
        real = subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                              capture_output=True, text=True,
                              cwd=ROOT).stdout.strip()
        assert prov["git_rev"] == real
        assert re.match(r"^[0-9a-f]{7,40}$", prov["git_rev"])
        assert prov["hostname"]
        assert prov["platform"] in ("cpu", "tpu", "gpu")
        assert prov["monotonic_start_ns"] <= prov["monotonic_ns"]
        assert monitor.validate_provenance(prov) == []

    def test_validate_rejects_placeholder_and_future(self):
        bad = {"git_rev": "deadbee", "wall_time": "2030-01-01T00:00:00Z"}
        problems = monitor.validate_provenance(bad)
        assert len(problems) == 2
        assert any("placeholder" in p for p in problems)
        assert any("future" in p for p in problems)

    def test_validate_accepts_absent_rev(self):
        """An unversioned (non-git) deployment omits git_rev entirely —
        absence is not forgery, only a PRESENT placeholder is."""
        ok = {"wall_time": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                         time.gmtime())}
        assert monitor.validate_provenance(ok) == []

    def test_snapshot_is_json_serializable(self):
        self._populate()
        json.dumps(monitor.snapshot())

    def test_chrome_counter_events_merge_into_profiler_trace(self, tmp_path):
        from paddle_tpu import profiler

        monitor.enable()
        x = paddle.to_tensor(np.ones((2, 2), "float32"))
        with profiler.Profiler(
                targets=[profiler.ProfilerTarget.CPU]) as p:
            x + x
            p.step()    # samples the metric timeline
            x @ x
            p.step()
        path = tmp_path / "trace.json"
        p.export(str(path))
        doc = json.loads(path.read_text())
        counters = [e for e in doc["traceEvents"] if e.get("ph") == "C"]
        assert counters, "no counter events merged into the chrome trace"
        names = {e["name"].split("{")[0] for e in counters}
        assert "paddle_tpu_dispatch_op_calls_total" in names
        spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
        assert spans, "host spans missing from the merged trace"


# --------------------------------------------------------------------------- #
# tooling
# --------------------------------------------------------------------------- #

class TestMetricNameLint:
    def test_lint_passes_on_tree(self):
        p = subprocess.run(
            [sys.executable, os.path.join(ROOT, "tools",
                                          "check_metric_names.py")],
            capture_output=True, text=True, timeout=60)
        assert p.returncode == 0, p.stderr

    def test_lint_catches_bad_name(self, tmp_path):
        # simulate an undeclared registration in a scratch tree
        pkg = tmp_path / "paddle_tpu" / "monitor"
        pkg.mkdir(parents=True)
        src_cat = os.path.join(ROOT, "paddle_tpu", "monitor", "catalog.py")
        (pkg / "catalog.py").write_text(open(src_cat).read())
        (tmp_path / "paddle_tpu" / "rogue.py").write_text(
            'm.counter("paddle_tpu_dispatch_not_in_catalog_total")\n')
        sys.path.insert(0, ROOT)
        try:
            import tools.check_metric_names as lint

            problems = lint.check(root=str(tmp_path))
        finally:
            sys.path.remove(ROOT)
        assert any("not_in_catalog" in p for p in problems)


# --------------------------------------------------------------------------- #
# serving-loop integration (the acceptance run)
# --------------------------------------------------------------------------- #

class TestServingIntegration:
    # tiny 2-layer model: the whole scripted run compiles + decodes in a few
    # seconds on CPU, cheap enough for the fast tier
    def test_scripted_run_matches_ground_truth(self):
        """ISSUE 1 acceptance: after a scripted ContinuousBatchingEngine
        run under monitor.enable(), the snapshot reports non-zero serving
        tokens, dispatch counts, JIT compile/hit counts, and a KV
        free-block gauge consistent with the allocator's _refs/_free
        state; prometheus_text() parses; provenance carries the real
        rev."""
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
        from paddle_tpu.models.serving import ContinuousBatchingEngine

        monitor.enable()
        paddle.seed(0)
        cfg = LlamaConfig(vocab_size=96, hidden_size=64,
                          intermediate_size=176, num_hidden_layers=2,
                          num_attention_heads=4, num_key_value_heads=2,
                          max_position_embeddings=128)
        model = LlamaForCausalLM(cfg)
        # the scripted run includes eager pre/post-processing ops (the
        # realistic serving loop shape), so dispatch counters tick too
        probe = paddle.to_tensor(np.ones((4, 4), "float32"))
        (probe + probe) @ probe
        eng = ContinuousBatchingEngine(model, max_batch=2, max_len=32,
                                       block_size=8, chunk_size=8)
        rng = np.random.RandomState(0)
        rids = [eng.submit(rng.randint(0, 96, (n,)).astype("int32"))
                for n in (5, 7, 4)]
        # submit() is a pure enqueue; the driving thread admits at step()
        assert eng.num_pending == 3
        done = {}
        for rid, toks in eng.step(max_new_tokens=5):
            done[rid] = toks
        steps = 1
        assert eng.num_pending == 1     # third request queued, batch of 2
        while len(done) < 3 and steps < 40:
            for rid, toks in eng.step(max_new_tokens=5):
                done[rid] = toks
            steps += 1
        assert sorted(done) == sorted(rids)
        total_tokens = sum(len(v) for v in done.values())

        snap = monitor.snapshot()
        m = snap["metrics"]
        # serving counters match the scripted ground truth exactly
        assert m["paddle_tpu_serving_generated_tokens_total"]["values"][
            ""] == total_tokens
        assert m["paddle_tpu_serving_evictions_total"]["values"][""] == 3
        assert m["paddle_tpu_serving_admitted_total"]["values"][""] == 3
        assert m["paddle_tpu_serving_queue_depth"]["values"][""] == 0
        assert m["paddle_tpu_serving_ttft_ns"]["values"][""]["count"] == 3
        # chunked prefill: every prompt fits one chunk (<= chunk_size)
        assert m["paddle_tpu_serving_chunked_prefill_depth"]["values"][
            ""]["count"] == 3
        # one latency observation per step (mixed or burst alike); the
        # first call dispatched a step and fetched none (ISSUE 37)
        assert m["paddle_tpu_serving_decode_step_latency_ns"]["values"][
            ""]["count"] == steps - 1
        # prefix cache: 3 distinct prompts, all cold
        assert m["paddle_tpu_serving_prefix_cache_misses_total"]["values"][
            ""] == 3
        # dispatch + jit caches saw real traffic
        disp = m["paddle_tpu_dispatch_op_calls_total"]["values"]
        assert sum(disp.values()) > 0
        # the engine's whole program set: the mixed step and (if the run
        # reached steady decode) the burst — every step() call that
        # dispatches (all but the last, which fetches the step in flight)
        # is either a compile or a hit of label serving.step, never a new
        # signature
        jit_c = m["paddle_tpu_jit_compiles_total"]["values"]
        jit_h = m["paddle_tpu_jit_cache_hits_total"]["values"]
        assert 1 <= jit_c["function=serving.step"] <= 2
        assert jit_c["function=serving.step"] \
            + jit_h["function=serving.step"] == steps - 1
        # KV gauge consistent with the allocator's internal state
        pk = eng._pager
        gauge = m["paddle_tpu_kv_free_blocks"]["values"][""]
        assert gauge == len(pk._free)
        assert gauge == int((pk._refs == 0).sum()) - 1  # minus null block
        # exporters remain valid mid-flight
        series, types = _parse_prometheus(monitor.prometheus_text())
        assert series["paddle_tpu_serving_generated_tokens_total"] == \
            total_tokens
        assert monitor.validate_provenance(snap["provenance"]) == []
        assert re.match(r"^[0-9a-f]{7,40}$", snap["provenance"]["git_rev"])
        # timeline samples accumulated for the chrome-trace counter track
        assert monitor.chrome_counter_events()
