"""Continuous batching: chunked prefill + prefix-shared paged KV
(models/serving.py).

The acceptance bars:
- requests admitted at DIFFERENT times, packed into one mixed compiled
  step at ragged positions, must each reproduce the tokens the SAME
  engine produces for that prompt alone (batching never changes results);
- a warm prefix-cache run emits tokens bit-identical to the cold run
  (shared-block reuse is exact, not approximate);
- slots recycle blocks after eviction; the scheduler knobs and submit()
  backpressure behave as documented;
- a steady-state run under PADDLE_TPU_SANITIZE=all stays silent: the
  token-budget pack holds the engine at its two compiled programs and the
  decode loop never host-syncs a Tensor.
"""
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.analysis import sanitizers as san
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.analysis import faultinject as fi
from paddle_tpu.models.serving import (AdmissionTimeout,
                                       ContinuousBatchingEngine,
                                       RequestShed)


def _model(vocab=96, layers=2):
    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=vocab, hidden_size=64,
                      intermediate_size=176, num_hidden_layers=layers,
                      num_attention_heads=4, num_key_value_heads=2,
                      max_position_embeddings=128)
    return LlamaForCausalLM(cfg)


def _run_all(eng, max_steps=60, **step_kw):
    done = {}
    for _ in range(max_steps):
        for rid, toks in eng.step(**step_kw):
            done[rid] = np.asarray(toks)
        if not (eng.num_active or eng.num_pending):
            break
    return done


@pytest.mark.slow
class TestContinuousBatching:
    def test_staggered_requests_match_single_request(self):
        """Mid-flight admission at ragged positions reproduces each
        prompt's solo tokens — the mixed pack computes every lane
        independently of its neighbours."""
        model = _model()
        rng = np.random.RandomState(0)
        prompts = [rng.randint(0, 96, (n,)).astype("int32")
                   for n in (9, 5, 13)]
        want = {}
        for i, p in enumerate(prompts):
            solo = ContinuousBatchingEngine(model, max_batch=1, max_len=64,
                                            block_size=8, chunk_size=16,
                                            prefix_cache=False,
                                            decode_burst=1)
            solo.add_request(p)
            want[i] = list(_run_all(solo, max_new_tokens=10).values())[0]

        eng = ContinuousBatchingEngine(model, max_batch=4, max_len=64,
                                       block_size=8, chunk_size=16)
        rid0 = eng.add_request(prompts[0])
        eng.step(max_new_tokens=10)              # request 0 alone
        rid1 = eng.add_request(prompts[1])       # joins mid-flight
        eng.step(max_new_tokens=10)
        rid2 = eng.add_request(prompts[2])       # three at ragged positions
        done = _run_all(eng, max_new_tokens=10)
        assert set(done) == {rid0, rid1, rid2}
        for rid, idx in ((rid0, 0), (rid1, 1), (rid2, 2)):
            np.testing.assert_array_equal(done[rid], want[idx],
                                          err_msg=f"request {idx}")
        assert eng.num_active == 0

    def test_slots_recycle_blocks(self):
        model = _model()
        rng = np.random.RandomState(1)
        eng = ContinuousBatchingEngine(model, max_batch=2, max_len=32,
                                       block_size=8, chunk_size=8,
                                       prefix_cache=False)
        free0 = len(eng._pager._free)
        for round_ in range(3):
            a = eng.add_request(rng.randint(0, 96, (6,)).astype("int32"))
            b = eng.add_request(rng.randint(0, 96, (4,)).astype("int32"))
            assert a is not None and b is not None
            # full batch: third request must be refused, not crash
            assert eng.add_request(np.ones(3, "int32")) is None
            _run_all(eng, max_new_tokens=6)
        assert len(eng._pager._free) == free0, "blocks leaked across rounds"

    def test_spf_policy_prefills_shortest_first(self):
        """shortest-prefill-first: with one prefill lane of budget, the
        short prompt finishes its prefill (and emits) before the long
        one that was admitted first."""
        model = _model()
        rng = np.random.RandomState(2)
        long_p = rng.randint(0, 96, (24,)).astype("int32")
        short_p = rng.randint(0, 96, (4,)).astype("int32")
        eng = ContinuousBatchingEngine(model, max_batch=2, max_len=64,
                                       block_size=8, chunk_size=4,
                                       max_step_tokens=6, policy="spf",
                                       prefix_cache=False, decode_burst=1)
        rid_long = eng.submit(long_p, max_new_tokens=1)
        rid_short = eng.submit(short_p, max_new_tokens=1)
        finished_order = []
        for _ in range(30):
            for rid, _toks in eng.step():
                finished_order.append(rid)
            if len(finished_order) == 2:
                break
        assert finished_order == [rid_short, rid_long]

    def test_decode_priority_caps_prefill_share(self):
        """decode_priority=0.5 with budget 8: prefill may take at most
        (1-0.5)*8 = 4 lanes per step, so a 12-token prompt needs 3 chunks
        even though the chunk_size would allow fewer."""
        model = _model()
        eng = ContinuousBatchingEngine(model, max_batch=2, max_len=64,
                                       block_size=8, chunk_size=8,
                                       max_step_tokens=8,
                                       decode_priority=0.5,
                                       prefix_cache=False)
        rid = eng.add_request(np.arange(12, dtype="int32") % 96,
                              max_new_tokens=2)
        _run_all(eng)
        st = eng.pop_stats(rid)
        assert st["prefill_chunks"] == 3


class TestPrefixCacheExactness:
    def test_warm_cache_bit_identical_to_cold(self):
        """ISSUE 5 acceptance: a warm prefix-cache run emits tokens
        bit-identical to the cold-path run — including a block-aligned
        full-prompt hit, which re-runs only its last token through
        copy-on-write."""
        model = _model()
        rng = np.random.RandomState(7)
        prefix = rng.randint(0, 96, (16,)).astype("int32")   # 2 blocks @ 8
        prompts = [np.concatenate([prefix,
                                   rng.randint(0, 96, (n,)).astype("int32")])
                   for n in (5, 3)]
        # 24 tokens = 3 aligned blocks: the full-hit + CoW path
        prompts.append(np.concatenate(
            [prefix, rng.randint(0, 96, (8,)).astype("int32")]))

        monitor.reset()
        monitor.enable()
        try:
            eng = ContinuousBatchingEngine(model, max_batch=4, max_len=64,
                                           block_size=8, chunk_size=16)

            def run():
                rids = [eng.submit(p, max_new_tokens=8) for p in prompts]
                done = _run_all(eng)
                return [done[r] for r in rids]

            cold = run()
            assert eng.prefix_cache.hits == 0
            warm = run()
            assert eng.prefix_cache.hits == len(prompts)
            for c, w in zip(cold, warm):
                np.testing.assert_array_equal(c, w)
            snap = monitor.snapshot()["metrics"]
            # the aligned full hit recomputed its last token into a
            # copy-on-write private block — the PR 1 counter fires
            assert snap["paddle_tpu_kv_cow_copies_total"]["values"][""] >= 1
            assert snap["paddle_tpu_serving_prefix_cache_hits_total"][
                "values"][""] == len(prompts)
            assert snap["paddle_tpu_serving_prefix_blocks_shared_total"][
                "values"][""] >= 2 * len(prompts)
        finally:
            monitor.disable()
            monitor.reset()

    def test_shared_blocks_survive_owner_eviction(self):
        """The radix cache pins registered blocks: after the producing
        request is evicted its prefix blocks stay out of the free pool
        and a later identical prompt adopts them."""
        model = _model()
        rng = np.random.RandomState(3)
        prompt = rng.randint(0, 96, (20,)).astype("int32")
        eng = ContinuousBatchingEngine(model, max_batch=2, max_len=64,
                                       block_size=8, chunk_size=32)
        eng.add_request(prompt, max_new_tokens=3)
        _run_all(eng)
        assert eng.num_active == 0
        assert len(eng.prefix_cache) == 2          # 20 tokens -> 2 full blocks
        pinned = [e.block for e in eng.prefix_cache._entries.values()]
        assert all(eng._pager._refs[b] == 1 for b in pinned)
        assert not set(pinned) & set(eng._pager._free)
        rid = eng.add_request(prompt, max_new_tokens=3)
        _run_all(eng)
        st = eng.pop_stats(rid)
        assert st["shared_tokens"] == 16


class TestBackpressure:
    def test_full_queue_raises_immediately_without_timeout(self):
        eng = ContinuousBatchingEngine(_model(), max_batch=1, max_len=32,
                                       block_size=8, max_queue=2)
        p = np.arange(5, dtype="int32")
        eng.submit(p)
        eng.step()                     # driving thread admits to the slot
        eng.submit(p), eng.submit(p)   # fills the queue
        with pytest.raises(AdmissionTimeout, match="queue full"):
            eng.submit(p)

    def test_timeout_blocks_then_raises(self):
        eng = ContinuousBatchingEngine(_model(), max_batch=1, max_len=32,
                                       block_size=8, max_queue=1)
        p = np.arange(5, dtype="int32")
        eng.submit(p)
        eng.step()                     # driving thread admits to the slot
        eng.submit(p)
        t0 = time.monotonic()
        with pytest.raises(AdmissionTimeout, match="after 0.2s"):
            eng.submit(p, timeout=0.2)
        assert time.monotonic() - t0 >= 0.2

    def test_blocking_submit_resolves_when_stepping_thread_drains(self):
        eng = ContinuousBatchingEngine(_model(), max_batch=1, max_len=32,
                                       block_size=8, chunk_size=8,
                                       max_queue=1)
        p = np.arange(5, dtype="int32")
        eng.submit(p, max_new_tokens=2)
        eng.step()                     # driving thread admits to the slot
        eng.submit(p, max_new_tokens=2)
        stop = threading.Event()

        def drive():
            while not stop.is_set():
                eng.step()
                time.sleep(0.001)

        th = threading.Thread(target=drive)
        th.start()
        try:
            rid = eng.submit(p, max_new_tokens=2, timeout=30.0)
            assert rid is not None
        finally:
            stop.set()
            th.join()

    def test_admission_rejected_counter(self):
        monitor.reset()
        monitor.enable()
        try:
            eng = ContinuousBatchingEngine(_model(), max_batch=1,
                                           max_len=32, block_size=8,
                                           max_queue=1)
            p = np.arange(4, dtype="int32")
            eng.submit(p)
            eng.step()                 # driving thread admits to the slot
            eng.submit(p)
            with pytest.raises(AdmissionTimeout):
                eng.submit(p)
            snap = monitor.snapshot()["metrics"]
            assert snap["paddle_tpu_serving_admission_rejected_total"][
                "values"][""] == 1
        finally:
            monitor.disable()
            monitor.reset()


class TestSanitizedSteadyState:
    def test_sanitize_all_steady_state_is_silent(self):
        """ISSUE 5 acceptance: under PADDLE_TPU_SANITIZE=all, steady-state
        serving (repeated admissions + chunked prefill + decode) triggers
        neither the recompile sentinel nor the host-sync tripwire, and
        the jit cache holds misses at zero after warmup: the engine's two
        programs (mixed step, decode burst) each compile exactly once."""
        model = _model()
        assert san.install_from_env("all") != ()
        try:
            eng = ContinuousBatchingEngine(model, max_batch=2, max_len=64,
                                           block_size=8, chunk_size=16)
            rng = np.random.RandomState(0)
            for _ in range(6):   # admissions keep arriving mid-decode
                eng.submit(rng.randint(0, 96, (int(rng.randint(3, 20)),))
                           .astype("int32"), max_new_tokens=6)
                for _ in range(10):
                    eng.step()
            _run_all(eng)
            assert san.trips() == []
            counts = {k: v for k, v in san.compile_counts().items()
                      if k.startswith("serving.step")}
            assert counts and all(v <= 2 for v in counts.values()), counts
        finally:
            san.disable()
            san.reset()


def test_prompt_length_validation():
    eng = ContinuousBatchingEngine(_model(), max_batch=2, max_len=16)
    with pytest.raises(ValueError, match="out of range"):
        eng.add_request(np.zeros(0, "int32"))
    with pytest.raises(ValueError, match="out of range"):
        eng.add_request(np.zeros(16, "int32"))


def test_admission_grants_no_blocks_before_prefill():
    """Admission is free: blocks are granted chunk-by-chunk as prefill
    consumes budget, so idle slots and freshly admitted requests park
    nothing on the pool."""
    model = _model()
    eng = ContinuousBatchingEngine(model, max_batch=8, max_len=32,
                                   block_size=8, chunk_size=16,
                                   prefix_cache=False)
    free0 = len(eng._pager._free)
    eng.add_request(np.arange(6, dtype="int32") % 96)
    assert len(eng._pager._free) == free0
    eng.step(max_new_tokens=4)
    # 6-token prompt + first token => exactly 1 block granted
    assert free0 - len(eng._pager._free) == 1


# --------------------------------------------------------------------------- #
# ISSUE 6: per-tenant QoS (weighted-fair queuing, priority lanes, shedding)
# --------------------------------------------------------------------------- #

class TestTenants:
    def test_priority_lane_pops_first(self):
        eng = ContinuousBatchingEngine(_model(), max_batch=2, max_len=32,
                                       block_size=8)
        eng.set_tenant("gold", priority=2)
        eng.set_tenant("bronze", priority=0)
        r = np.random.RandomState(0)
        b1 = eng.submit(r.randint(0, 96, (5,)).astype("int32"),
                        tenant="bronze")
        b2 = eng.submit(r.randint(0, 96, (5,)).astype("int32"),
                        tenant="bronze")
        g1 = eng.submit(r.randint(0, 96, (5,)).astype("int32"),
                        tenant="gold")
        order = [eng._pop_pending().rid for _ in range(3)]
        assert order == [g1, b1, b2]

    def test_weighted_fair_share_is_stride_scheduled(self):
        eng = ContinuousBatchingEngine(_model(), max_batch=2, max_len=32,
                                       block_size=8)
        eng.set_tenant("heavy", weight=2.0)
        eng.set_tenant("light", weight=1.0)
        r = np.random.RandomState(0)
        for _ in range(6):
            eng.submit(r.randint(0, 96, (5,)).astype("int32"),
                       tenant="heavy")
        for _ in range(6):
            eng.submit(r.randint(0, 96, (5,)).astype("int32"),
                       tenant="light")
        first9 = [eng._pop_pending().tenant for _ in range(9)]
        # stride scheduling on 1/weight: a weight-2 lane admits twice
        # per weight-1 admission under contention
        assert first9.count("heavy") == 6 and first9.count("light") == 3

    def test_idle_lane_cannot_bank_an_unfair_burst(self):
        eng = ContinuousBatchingEngine(_model(), max_batch=2, max_len=32,
                                       block_size=8)
        eng.set_tenant("a", weight=1.0)
        eng.set_tenant("b", weight=1.0)
        r = np.random.RandomState(0)
        for _ in range(4):
            eng.submit(r.randint(0, 96, (5,)).astype("int32"), tenant="a")
            eng._pop_pending()
        # b was idle the whole time; its lane re-syncs to the virtual
        # clock on first use instead of replaying its lag as a burst
        for _ in range(2):
            eng.submit(r.randint(0, 96, (5,)).astype("int32"), tenant="a")
            eng.submit(r.randint(0, 96, (5,)).astype("int32"), tenant="b")
        pops = [eng._pop_pending().tenant for _ in range(4)]
        assert pops.count("a") == 2 and pops.count("b") == 2

    def test_full_queue_sheds_newest_lowest_priority_victim(self):
        monitor.enable()
        monitor.reset()
        try:
            eng = ContinuousBatchingEngine(_model(), max_batch=2,
                                           max_len=32, block_size=8,
                                           max_queue=2)
            eng.set_tenant("gold", priority=1)
            r = np.random.RandomState(0)
            b1 = eng.submit(r.randint(0, 96, (5,)).astype("int32"))
            b2 = eng.submit(r.randint(0, 96, (5,)).astype("int32"))
            g1 = eng.submit(r.randint(0, 96, (5,)).astype("int32"),
                            tenant="gold")
            (shed,) = eng.pop_shed()
            assert isinstance(shed, RequestShed)
            assert shed.rid == b2 and shed.tenant == ""  # newest victim
            assert isinstance(shed, AdmissionTimeout)    # handler compat
            order = [eng._pop_pending().rid for _ in range(2)]
            assert order == [g1, b1]
            snap = monitor.snapshot()["metrics"]
            vals = snap["paddle_tpu_serving_shed_total"]["values"]
            assert vals == {"tenant=": 1}
        finally:
            monitor.disable()
            monitor.reset()

    def test_lowest_priority_arrival_is_shed_typed(self):
        eng = ContinuousBatchingEngine(_model(), max_batch=2, max_len=32,
                                       block_size=8, max_queue=1)
        eng.set_tenant("gold", priority=1)
        r = np.random.RandomState(0)
        eng.submit(r.randint(0, 96, (5,)).astype("int32"), tenant="gold")
        with pytest.raises(RequestShed) as ei:
            eng.submit(r.randint(0, 96, (5,)).astype("int32"),
                       tenant="bronze")
        assert ei.value.tenant == "bronze"

    def test_equal_priority_never_displaced(self):
        """Without priority lanes the old backpressure contract holds:
        plain AdmissionTimeout, nothing shed."""
        eng = ContinuousBatchingEngine(_model(), max_batch=2, max_len=32,
                                       block_size=8, max_queue=1)
        r = np.random.RandomState(0)
        eng.submit(r.randint(0, 96, (5,)).astype("int32"))
        with pytest.raises(AdmissionTimeout) as ei:
            eng.submit(r.randint(0, 96, (5,)).astype("int32"))
        assert not isinstance(ei.value, RequestShed)
        assert eng.pop_shed() == []

    def test_tenant_validation(self):
        eng = ContinuousBatchingEngine(_model(), max_batch=2, max_len=32,
                                       block_size=8)
        with pytest.raises(ValueError, match="weight"):
            eng.set_tenant("x", weight=0.0)
        eng.set_tenant("y", weight=1.0, priority=1)
        with pytest.raises(ValueError, match="weight"):
            eng.set_tenant("y", weight=-1.0)

    def test_priority_tenants_keep_goodput_under_overload(self):
        """The QoS acceptance shape, in-process: gold requests finish
        with the same tokens whether bronze floods or not, and bronze
        sheds typed instead of starving gold."""
        model = _model()
        eng = ContinuousBatchingEngine(model, max_batch=2, max_len=64,
                                       block_size=8, chunk_size=16,
                                       max_queue=3)
        eng.set_tenant("gold", weight=2.0, priority=1)
        eng.set_tenant("bronze", weight=1.0, priority=0)
        r = np.random.RandomState(7)
        gold_prompts = [r.randint(0, 96, (9,)).astype("int32")
                        for _ in range(3)]
        iso = {}
        rids = [eng.submit(p, max_new_tokens=4, tenant="gold")
                for p in gold_prompts]
        for rid, toks in _run_all(eng).items():
            iso[rid] = list(toks)
        shed = 0
        gold_rids = [eng.submit(p, max_new_tokens=4, tenant="gold",
                                timeout=10.0) for p in gold_prompts]
        for _ in range(8):
            try:
                eng.submit(r.randint(0, 96, (9,)).astype("int32"),
                           max_new_tokens=4, tenant="bronze")
            except RequestShed:
                shed += 1
        done = _run_all(eng, max_steps=400)
        assert shed > 0
        for old_rid, new_rid in zip(rids, gold_rids):
            assert list(done[new_rid]) == iso[old_rid]

    @staticmethod
    def _strict(model, **kw):
        eng = ContinuousBatchingEngine(model, max_batch=2, max_len=64,
                                       block_size=8, chunk_size=16,
                                       decode_burst=1,
                                       strict_priority=True, **kw)
        eng.set_tenant("gold", weight=2.0, priority=1)
        eng.set_tenant("bronze", weight=1.0, priority=0)
        return eng

    def test_strict_priority_defers_lower_work_while_higher_is_active(self):
        """One gold request holds one of two slots: queued bronze stays
        queued though a slot is free, and is admitted by the first step
        after gold's eviction. The same engine without the option hands
        bronze the free slot at once."""
        model = _model()
        r = np.random.RandomState(21)
        gold = r.randint(0, 96, (9,)).astype("int32")
        bronze = r.randint(0, 96, (9,)).astype("int32")
        for strict in (True, False):
            eng = self._strict(model)
            eng.strict_priority = strict
            g = eng.submit(gold, max_new_tokens=6, tenant="gold")
            eng.step()
            b = eng.submit(bronze, max_new_tokens=3, tenant="bronze")
            done = dict(eng.step())
            active = {s.rid for s in eng._slots if s is not None}
            assert active == ({g} if strict else {g, b})
            while strict and g not in done:
                assert eng.num_pending == 1
                # gold alone, until its last token is dispatched and its
                # row released (ISSUE 37: a call before it is handed back)
                assert [s.rid for s in eng._slots if s is not None] \
                    in ([g], [])
                done.update(eng.step())
            if strict:      # gold's row was free: the call that handed
                #             gold back admitted bronze before it routed
                assert [s.rid for s in eng._slots if s is not None] == [b]
            done.update(_run_all(eng))
            assert len(done[g]) == 6 and len(done[b]) == 3

    def test_strict_priority_keeps_high_tenant_tokens_bit_identical(self):
        """Gold's tokens with a bronze flood beside it equal its
        isolated run's, bit for bit, and no bronze request shares a
        step with gold: none holds a slot while a gold one is active
        or queued."""
        model = _model()
        r = np.random.RandomState(22)
        gold_prompts = [r.randint(0, 96, (n,)).astype("int32")
                        for n in (9, 12, 7)]
        eng = self._strict(model)
        rids = [eng.submit(p, max_new_tokens=8, tenant="gold")
                for p in gold_prompts]
        iso = _run_all(eng, max_steps=200)
        gold_rids = [eng.submit(p, max_new_tokens=8, tenant="gold")
                     for p in gold_prompts]
        bronze_rids = [eng.submit(r.randint(0, 96, (9,)).astype("int32"),
                                  max_new_tokens=4, tenant="bronze")
                       for _ in range(5)]
        done, gold_left = {}, set(gold_rids)
        for _ in range(400):
            if gold_left:
                # gold active or queued: no bronze request holds a slot
                assert all(s is None or s.tenant == "gold"
                           for s in eng._slots)
            for rid, toks in eng.step():
                done[rid] = np.asarray(toks)
                gold_left.discard(rid)
            if not (eng.num_active or eng.num_pending):
                break
        for old, new in zip(rids, gold_rids):
            np.testing.assert_array_equal(done[new], iso[old])
        assert all(len(done[b]) == 4 for b in bronze_rids)

    def test_strict_priority_sheds_the_flood_and_nothing_of_the_high(self):
        """Under ``max_queue`` the deferred flood fills the queue once
        and every later bronze arrival is shed typed; gold submits
        displace queued bronze; no gold request is ever shed."""
        model = _model()
        r = np.random.RandomState(23)
        eng = self._strict(model, max_queue=3)
        g0 = eng.submit(r.randint(0, 96, (9,)).astype("int32"),
                        max_new_tokens=20, tenant="gold")
        eng.step()                       # gold active: bronze is deferred
        shed_on_submit = 0
        for _ in range(8):
            try:
                eng.submit(r.randint(0, 96, (9,)).astype("int32"),
                           max_new_tokens=4, tenant="bronze")
            except RequestShed as e:
                assert e.tenant == "bronze"
                shed_on_submit += 1
            eng.step()                   # a free slot, and still deferred
        assert shed_on_submit == 8 - 3   # the queue holds 3, once
        gold_rids = [g0] + [
            eng.submit(r.randint(0, 96, (9,)).astype("int32"),
                       max_new_tokens=6, tenant="gold") for _ in range(2)]
        displaced = eng.pop_shed()
        assert len(displaced) == 2
        assert all(isinstance(e, RequestShed) and e.tenant == "bronze"
                   for e in displaced)
        done = _run_all(eng, max_steps=400)
        assert [len(done[g]) for g in gold_rids] == [20, 6, 6]
        assert eng.pop_shed() == []
        assert len(done) == 3 + 1        # gold's three and bronze's last


# --------------------------------------------------------------------------- #
# ISSUE 6: host-RAM KV spill/restore (preemption + spilled radix prefixes)
# --------------------------------------------------------------------------- #

class TestKVSpill:
    def test_preemption_under_pool_pressure_restores_bit_exact(self):
        """An injected pool exhaustion on the DECODE grant PREEMPTS the
        non-decoding request mid-prefill: its partial KV spills to host
        RAM, its blocks return to the pool, and it later resumes
        bit-identically to an undisturbed run. The radix cache is OFF so
        the exhaustion cannot be absorbed by cache relief — preemption
        is the request-KV spill path, independent of the prefix store."""
        model = _model()
        eng = ContinuousBatchingEngine(model, max_batch=2, max_len=64,
                                       block_size=8, chunk_size=8,
                                       decode_burst=1, kv_spill=True,
                                       prefix_cache=False)
        r = np.random.RandomState(8)
        pA = r.randint(0, 96, (10,)).astype("int32")
        pB = r.randint(0, 96, (20,)).astype("int32")
        ref = {}
        for p in (pA, pB):
            rid = eng.add_request(p, max_new_tokens=8)
            ref[len(ref)] = _run_all(eng)[rid]
        monitor.enable()
        monitor.reset()
        fi.reset()
        try:
            done = {}
            ridA = eng.add_request(pA, max_new_tokens=8)
            while not eng._decode_ready.any():   # A through prefill
                done.update(eng.step())
            ridB = eng.add_request(pB, max_new_tokens=8)
            done.update(eng.step())              # B's first prefill chunk
            assert eng.lens[[s is not None and s.rid == ridB
                             for s in eng._slots].index(True)] > 0
            # next step's decode grant explodes: A must keep decoding,
            # so mid-prefill B is the preemption victim. It explodes
            # twice: behind the first the engine fetches the step in
            # flight (ISSUE 37: what a preemption copies must be on the
            # host) and schedules anew, behind the second it preempts
            fi.arm("paged_kv.ensure", action="flag", nth=1, times=2)
            for _ in range(400):
                done.update(eng.step())
                if not (eng.num_active or eng.num_pending):
                    break
            assert fi.trips() == [("paged_kv.ensure", "flag")] * 2
            snap = monitor.snapshot()["metrics"]
            assert snap["paddle_tpu_serving_preemptions_total"][
                "values"][""] >= 1
            assert list(done[ridA]) == list(ref[0])
            assert list(done[ridB]) == list(ref[1])
        finally:
            fi.reset()
            monitor.disable()
            monitor.reset()

    def test_spilled_radix_prefix_restores_from_host_ram(self):
        """Evicted-but-hot prefixes survive in host RAM: a later match
        restores them into fresh pool blocks bit-exact (the restores
        counter + spilled-blocks gauge document the round trip)."""
        model = _model()
        eng = ContinuousBatchingEngine(model, max_batch=2, max_len=64,
                                       block_size=8, chunk_size=32,
                                       kv_spill=True)
        r = np.random.RandomState(9)
        prompt = r.randint(0, 96, (24,)).astype("int32")
        rid = eng.add_request(prompt, max_new_tokens=6)
        ref = _run_all(eng)[rid]
        pc = eng.prefix_cache
        n_cached = len(pc)
        assert n_cached >= 3
        monitor.enable()
        monitor.reset()
        try:
            # pool pressure evicts the whole chain: payloads park in
            # host RAM instead of vanishing
            freed = pc.evict(n_cached, pools=eng._pools)
            assert freed == n_cached and len(pc._spilled) == freed
            snap = monitor.snapshot()["metrics"]
            assert snap["paddle_tpu_kv_spilled_blocks"]["values"][""] \
                == freed
            hits0 = pc.hits
            rid2 = eng.add_request(prompt, max_new_tokens=6)
            assert pc.restores == freed      # the chain came back whole
            assert pc.hits == hits0 + 1
            assert np.array_equal(_run_all(eng)[rid2], ref)
            snap = monitor.snapshot()["metrics"]
            assert snap["paddle_tpu_kv_spill_restores_total"][
                "values"][""] == freed
        finally:
            monitor.disable()
            monitor.reset()

    def test_spill_disabled_drops_evicted_entries(self):
        model = _model()
        eng = ContinuousBatchingEngine(model, max_batch=2, max_len=64,
                                       block_size=8, chunk_size=32,
                                       kv_spill=False)
        r = np.random.RandomState(10)
        prompt = r.randint(0, 96, (24,)).astype("int32")
        rid = eng.add_request(prompt, max_new_tokens=4)
        _run_all(eng)
        pc = eng.prefix_cache
        freed = pc.evict(len(pc), pools=eng._pools)
        assert freed and len(pc._spilled) == 0
        assert pc.restores == 0


class TestSpeculativeDecoding:
    def test_spec_on_bit_identical_to_off(self):
        """ISSUE 7 acceptance: greedy outputs are bit-identical with
        speculation on vs off — drafts ride extra verify lanes of the
        same compiled mixed step and only the longest agreeing prefix is
        kept, so a wrong draft costs a lane, never a token."""
        model = _model()
        rng = np.random.RandomState(11)
        # a repetitive prompt (the n-gram drafter's home turf) plus two
        # random ones: the accept rate varies per lane, the tokens don't
        prompts = [np.tile(rng.randint(0, 96, (4,)).astype("int32"), 5),
                   rng.randint(0, 96, (9,)).astype("int32"),
                   rng.randint(0, 96, (13,)).astype("int32")]
        outs = {}
        for la in (0, 6):
            eng = ContinuousBatchingEngine(model, max_batch=4, max_len=64,
                                           block_size=8, chunk_size=16,
                                           spec_lookahead=la)
            rids = [eng.submit(p, max_new_tokens=12) for p in prompts]
            done = _run_all(eng, max_steps=200)
            outs[la] = [done[r] for r in rids]
            if la:
                assert eng.spec_drafted > 0
                assert 0 < eng.spec_accepted <= eng.spec_drafted
        for off, on in zip(outs[0], outs[6]):
            np.testing.assert_array_equal(off, on)

    def test_repeated_prompt_drafts_from_radix_chain(self):
        """The second draft source: spec engines register DECODE blocks
        into the radix chain, so a repeated prompt finds its previous
        run's continuation as chain tokens — greedy determinism makes
        those drafts near-perfect (the production repeat/template
        shape the spec bench measures)."""
        model = _model()
        rng = np.random.RandomState(12)
        prompt = rng.randint(0, 96, (10,)).astype("int32")
        eng = ContinuousBatchingEngine(model, max_batch=1, max_len=64,
                                       block_size=8, chunk_size=16,
                                       spec_lookahead=8, pool_blocks=24)
        rid = eng.submit(prompt, max_new_tokens=16)
        first = _run_all(eng, max_steps=200)[rid]
        d0, a0 = eng.spec_drafted, eng.spec_accepted
        rid = eng.submit(prompt, max_new_tokens=16)
        second = _run_all(eng, max_steps=200)[rid]
        np.testing.assert_array_equal(first, second)
        drafted = eng.spec_drafted - d0
        accepted = eng.spec_accepted - a0
        assert drafted > 0
        # the warm pass drafts from the registered chain: most drafted
        # tokens are the previous run's exact greedy output
        assert accepted / drafted >= 0.75, (accepted, drafted)

    def test_spec_metrics_and_verify_span(self):
        """The cataloged telemetry: drafted/accepted counters, the
        accept-rate gauge, the pool-bytes gauge, and one
        serving.spec_verify span per speculating step."""
        from paddle_tpu.monitor import trace
        model = _model()
        monitor.reset()
        monitor.enable()
        trace.enable()
        try:
            eng = ContinuousBatchingEngine(model, max_batch=2, max_len=64,
                                           block_size=8, chunk_size=16,
                                           spec_lookahead=6)
            rng = np.random.RandomState(13)
            eng.submit(np.tile(rng.randint(0, 96, (4,)).astype("int32"), 4),
                       max_new_tokens=10)
            _run_all(eng, max_steps=200)
            assert eng.spec_drafted > 0
            snap = monitor.snapshot()["metrics"]
            drafted = snap["paddle_tpu_serving_spec_draft_tokens_total"][
                "values"][""]
            accepted = snap["paddle_tpu_serving_spec_accepted_tokens_total"][
                "values"][""]
            assert drafted == eng.spec_drafted
            assert accepted == eng.spec_accepted
            rate = snap["paddle_tpu_serving_spec_accept_rate"]["values"][""]
            assert abs(rate - accepted / max(drafted, 1)) < 1e-9
            assert snap["paddle_tpu_serving_kv_pool_bytes"]["values"][""] \
                == eng.kv_pool_bytes > 0
            spans = [s for s in trace.span_dump()["spans"]
                     if s["name"] == "serving.spec_verify"]
            assert spans
            assert all(s["attrs"]["drafted"] >= s["attrs"]["accepted"] >= 0
                       for s in spans)
        finally:
            trace.disable()
            monitor.disable()
            monitor.reset()

    def test_spec_verify_fault_degrades_to_plain_decode(self):
        """ISSUE 7 satellite: a flag fault at serving.spec_verify makes
        the drafter degrade to plain 1-token decode — zero drafts while
        the drill holds, outputs bit-identical to the unspeculated run
        (never wrong output, only sacrificed speedup)."""
        model = _model()
        rng = np.random.RandomState(14)
        prompts = [np.tile(rng.randint(0, 96, (4,)).astype("int32"), 5),
                   rng.randint(0, 96, (9,)).astype("int32")]

        ref_eng = ContinuousBatchingEngine(model, max_batch=2, max_len=64,
                                           block_size=8, chunk_size=16)
        ref_rids = [ref_eng.submit(p, max_new_tokens=12) for p in prompts]
        ref = _run_all(ref_eng, max_steps=200)
        fi.reset()
        try:
            eng = ContinuousBatchingEngine(model, max_batch=2, max_len=64,
                                           block_size=8, chunk_size=16,
                                           spec_lookahead=6)
            fi.arm("serving.spec_verify", action="flag", nth=1,
                   times=10 ** 6)
            rids = [eng.submit(p, max_new_tokens=12) for p in prompts]
            done = _run_all(eng, max_steps=200)
            assert eng.spec_drafted == 0
            trips = fi.trips()
            assert trips and all(t == ("serving.spec_verify", "flag")
                                 for t in trips)
            for rid, rr in zip(rids, ref_rids):
                np.testing.assert_array_equal(done[rid], ref[rr])
        finally:
            fi.reset()

    def test_sanitize_all_spec_steady_state_single_program(self):
        """ISSUE 7 satellite: with speculation on, the fixed pack shape
        holds for EVERY accept count 0..K — under PADDLE_TPU_SANITIZE=all
        a varied-accept workload stays at the engine's compiled programs
        (no recompile storm, no host-sync trips)."""
        model = _model()
        assert san.install_from_env("all") != ()
        try:
            eng = ContinuousBatchingEngine(model, max_batch=2, max_len=64,
                                           block_size=8, chunk_size=16,
                                           spec_lookahead=6)
            rng = np.random.RandomState(15)
            for i in range(6):   # repeats + fresh prompts: accept counts
                if i % 2:        # swing between 0 and K across steps
                    p = np.tile(rng.randint(0, 96, (3,)).astype("int32"), 6)
                else:
                    p = rng.randint(0, 96, (int(rng.randint(3, 20)),)) \
                        .astype("int32")
                eng.submit(p, max_new_tokens=8)
                for _ in range(10):
                    eng.step()
            _run_all(eng, max_steps=200)
            assert eng.spec_drafted > 0
            assert san.trips() == []
            counts = {k: v for k, v in san.compile_counts().items()
                      if k.startswith("serving.step")}
            assert counts and all(v <= 2 for v in counts.values()), counts
        finally:
            san.disable()
            san.reset()


class TestQuantizedKV:
    def test_int8_divergence_bounded_vs_full_precision(self):
        """ISSUE 7 satellite: the int8 engine's outputs stay close to the
        full-precision engine on identical prompts — quantization noise
        may eventually flip an argmax, but most tokens (and the whole
        early sequence) must agree, and the quantized pools must cost
        under half the full-precision bytes."""
        model = _model()
        rng = np.random.RandomState(16)
        prompts = [rng.randint(0, 96, (n,)).astype("int32")
                   for n in (9, 5, 13)]
        outs, bytes_ = {}, {}
        for dt in (None, "int8"):
            eng = ContinuousBatchingEngine(model, max_batch=4, max_len=64,
                                           block_size=8, chunk_size=16,
                                           kv_cache_dtype=dt)
            rids = [eng.submit(p, max_new_tokens=12) for p in prompts]
            done = _run_all(eng, max_steps=200)
            outs[dt] = [done[r] for r in rids]
            bytes_[dt] = eng.kv_pool_bytes
        assert bytes_["int8"] < 0.5 * bytes_[None]
        for full, q in zip(outs[None], outs["int8"]):
            n = min(len(full), len(q))
            assert n >= 8
            agree = (np.asarray(full[:n]) == np.asarray(q[:n])).mean()
            assert agree >= 0.75, (full, q)
            np.testing.assert_array_equal(full[:4], q[:4])

    def test_int8_pools_hold_1_8x_the_requests_in_the_bf16_pool_bytes(self):
        """ISSUE 7 acceptance: at the byte budget of the bfloat16 engine's
        pool, int8 pools hold >= 1.8 x the concurrent requests, each to
        its full length (head_dim 64: 256 B a token and KV head in
        bfloat16, 128 + 8 B of scales in int8). Counted in blocks and
        slots and from the pool-bytes gauge, never timed."""
        paddle.seed(0)
        cfg = LlamaConfig(vocab_size=96, hidden_size=128,
                          intermediate_size=176, num_hidden_layers=2,
                          num_attention_heads=2, num_key_value_heads=1,
                          max_position_embeddings=128, dtype="bfloat16")
        model = LlamaForCausalLM(cfg)
        model.to(dtype="bfloat16")
        kw = dict(max_len=32, block_size=8, chunk_size=16,
                  prefix_cache=False)
        ref = ContinuousBatchingEngine(model, max_batch=5, **kw)
        blocks_a_request = -(-ref.max_len // ref.block_size)
        block_bytes = ContinuousBatchingEngine(
            model, max_batch=1, kv_cache_dtype="int8",
            **kw).kv_pool_bytes // (blocks_a_request + 1)
        pool_blocks = ref.kv_pool_bytes // block_bytes
        n = (pool_blocks - 1) // blocks_a_request   # whole-length requests
        assert n >= 1.8 * 5, (n, pool_blocks)
        monitor.reset()
        monitor.enable()
        try:
            eng = ContinuousBatchingEngine(
                model, max_batch=n, kv_cache_dtype="int8",
                pool_blocks=pool_blocks, **kw)
            rng = np.random.RandomState(19)
            rids = [eng.submit(rng.randint(0, 96, (4,)).astype("int32"))
                    for _ in range(n)]
            eng.step()                   # admission drains: every slot fills
            assert eng.num_active == n and eng.num_pending == 0
            gauge = monitor.snapshot()["metrics"][
                "paddle_tpu_serving_kv_pool_bytes"]["values"][""]
            assert gauge == eng.kv_pool_bytes <= ref.kv_pool_bytes
            # every request runs to the engine's max_len side by side
            # (no spill layer: a pool too small would raise): the pool
            # really holds n whole-length requests
            done = _run_all(eng, max_steps=400)
            assert all(len(done[r]) == eng.max_len - 4 for r in rids)
        finally:
            monitor.disable()
            monitor.reset()

    def test_int8_spec_bit_identical_to_int8_plain(self):
        """Speculation exactness is dtype-independent: drafts verified
        against quantized pools keep the int8 engine's own greedy outputs
        bit-identical, spec on vs off."""
        model = _model()
        rng = np.random.RandomState(17)
        prompts = [np.tile(rng.randint(0, 96, (4,)).astype("int32"), 5),
                   rng.randint(0, 96, (9,)).astype("int32")]
        outs = {}
        for la in (0, 6):
            eng = ContinuousBatchingEngine(model, max_batch=2, max_len=64,
                                           block_size=8, chunk_size=16,
                                           kv_cache_dtype="int8",
                                           spec_lookahead=la)
            rids = [eng.submit(p, max_new_tokens=12) for p in prompts]
            done = _run_all(eng, max_steps=200)
            outs[la] = [done[r] for r in rids]
            if la:
                assert eng.spec_drafted > 0
        for off, on in zip(outs[0], outs[6]):
            np.testing.assert_array_equal(off, on)

    def test_quantized_spill_restore_roundtrip_engine(self):
        """ISSUE 7 satellite: the host KV spill store parks/restores the
        quantized 4-leaf (kq, ks, vq, vs) layout bit-exactly — evicting a
        cached chain from int8 pools and re-admitting the prompt restores
        from host RAM and reproduces the outputs."""
        model = _model()
        eng = ContinuousBatchingEngine(model, max_batch=2, max_len=64,
                                       block_size=8, chunk_size=32,
                                       kv_cache_dtype="int8",
                                       kv_spill=True)
        r = np.random.RandomState(18)
        prompt = r.randint(0, 96, (24,)).astype("int32")
        rid = eng.add_request(prompt, max_new_tokens=6)
        ref = _run_all(eng, max_steps=200)[rid]
        pc = eng.prefix_cache
        n_cached = len(pc)
        assert n_cached >= 3
        freed = pc.evict(n_cached, pools=eng._pools)
        assert freed == n_cached and len(pc._spilled) == freed
        # every parked payload carries all four quantized leaves
        for se in pc._spilled.values():
            for entry in se.payload:
                assert len(entry) == 4
                kq, ks, vq, vs = entry
                assert kq.dtype == np.int8 and vq.dtype == np.int8
                assert ks.dtype == np.float32 and vs.dtype == np.float32
        rid2 = eng.add_request(prompt, max_new_tokens=6)
        assert pc.restores == freed
        np.testing.assert_array_equal(_run_all(eng, max_steps=200)[rid2],
                                      ref)


class TestDriverAndRecovery:
    def test_recover_on_idle_engine_is_clean(self):
        eng = ContinuousBatchingEngine(_model(), max_batch=2, max_len=32,
                                       block_size=8)
        assert eng.recover("manual drill") == 0
        assert eng.pop_aborted() == []
        assert len(eng.recovery_stats) == 1
        assert eng.recovery_stats[0]["aborted"] == 0

    def test_start_driver_is_idempotent_and_stops_clean(self):
        eng = ContinuousBatchingEngine(_model(), max_batch=2, max_len=32,
                                       block_size=8)
        eng.start_driver(max_new_tokens=3)
        first = eng._driver
        eng.start_driver(max_new_tokens=3)
        assert eng._driver is first
        rid = eng.submit(np.arange(5, dtype=np.int32), max_new_tokens=3,
                         timeout=5.0)
        t0 = time.monotonic()
        out = {}
        while rid not in out and time.monotonic() - t0 < 30:
            out.update(eng.pop_results())
            time.sleep(0.005)
        eng.stop_driver()
        assert len(out[rid]) == 3
        assert not eng._drive_stop.is_set() or eng._driver is None

    def test_tenant_queue_depth_gauge_tracks_lanes(self):
        monitor.enable()
        monitor.reset()
        try:
            eng = ContinuousBatchingEngine(_model(), max_batch=2,
                                           max_len=32, block_size=8)
            eng.set_tenant("t1")
            r = np.random.RandomState(0)
            eng.submit(r.randint(0, 96, (5,)).astype("int32"),
                       tenant="t1")
            eng.submit(r.randint(0, 96, (5,)).astype("int32"),
                       tenant="t1")
            snap = monitor.snapshot()["metrics"]
            vals = snap["paddle_tpu_serving_tenant_queue_depth"]["values"]
            assert vals["tenant=t1"] == 2
            assert snap["paddle_tpu_serving_queue_depth"]["values"][""] \
                == 2
        finally:
            monitor.disable()
            monitor.reset()
