"""One serving step in flight (ISSUE 37): ``step()`` dispatches step N + 1
before it fetches step N's tokens, and the next step's input tokens never
visit the host.

Contracts under test:

1. under traffic that reuses lanes (more requests than lanes, prompts longer
   than a chunk, bursts and mixed steps alternating) every request's tokens
   equal those of the same engine driven at depth 0 (each step fetched in the
   call that dispatched it: the engine before PR 37) and those of the serving
   tests' reference (the prompt alone in an engine of one lane); the two
   depths make the SAME steps in the same order, one call apart;
2. with an ``eos_token_id`` the model does emit, answers end at the EOS, the
   over-run lane's token is never reported and the freed lane's next request
   is unharmed;
3. the order of events: step N + 1's ``serving.dispatch`` opens before step
   N's ``serving.wait`` closes and ``dispatch_total{ahead=yes}`` counts it;
   with a drafter, under the numerics sanitizer, after ``cancel`` of an
   active request, across ``recover()``, across a ``decode_burst`` change
   and before a preemption the step in flight is routed or dropped first,
   and nothing is reported twice or lost;
4. ``while eng.num_active or eng.num_pending`` returns every request, the
   last included.

The MiMo-shaped and Olmo-Hybrid-shaped models' cases of 1 live beside their
references (tests/test_mimo_v2_serving.py, tests/test_olmo_hybrid_serving.py).
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.analysis import faultinject as fi
from paddle_tpu.analysis import sanitizers as san
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.serving import ContinuousBatchingEngine
from paddle_tpu.monitor import trace

# (prompt length, tokens asked): more requests than lanes, prompts of
# several chunks, of one token, answers of one token and of several bursts
REQUESTS = [(9, 12), (5, 7), (29, 9), (13, 1), (3, 18), (17, 2), (1, 6),
            (22, 11), (8, 5)]
ENGINE = dict(max_batch=3, max_len=64, block_size=8, chunk_size=8)
DISPATCHED = "paddle_tpu_serving_dispatch_total"

_MODEL = []


@pytest.fixture(autouse=True, scope="module")
def _model():
    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=96, hidden_size=64, intermediate_size=176,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=2, max_position_embeddings=128)
    _MODEL.append(LlamaForCausalLM(cfg))
    yield
    _MODEL.clear()


@pytest.fixture(autouse=True)
def _clean():
    monitor.disable()
    trace.disable()
    monitor.reset()
    fi.reset()
    yield
    monitor.disable()
    trace.disable()
    monitor.reset()
    fi.reset()


def _engine(depth=1, **over):
    eng = ContinuousBatchingEngine(_MODEL[0], **{**ENGINE, **over})
    if depth == 0:
        # the same code with nothing left in flight: what a drafter or the
        # numerics sanitizer makes of the engine, forced from outside
        eng._depth = lambda: 0
    return eng


def _prompts(requests=REQUESTS, seed=0):
    rng = np.random.RandomState(seed)
    return [(rng.randint(0, 96, (n,)).astype("int32"), m)
            for n, m in requests]


def _serve(eng, prompts, **step_kw):
    """Submit, step until the engine runs dry. Returns the answers in the
    order submitted, the kinds of the steps in the order DISPATCHED, and the
    number of calls; raises on a request reported twice."""
    rids = [eng.submit(p, max_new_tokens=m) for p, m in prompts]
    done, kinds, calls = {}, [], 0
    dispatch = eng._dispatch

    def spy(plan, *rest):
        kinds.append(plan[0])
        return dispatch(plan, *rest)

    eng._dispatch = spy
    try:
        while eng.num_active or eng.num_pending:
            for rid, toks in eng.step(**step_kw):
                assert rid not in done, f"request {rid} reported twice"
                done[rid] = list(toks)
            calls += 1
            assert calls < 500
    finally:
        del eng._dispatch
    assert eng._flight is None and eng.num_active == 0
    assert sorted(done) == sorted(rids), "a request was lost"
    return [done[r] for r in rids], kinds, calls


def _alone(prompts):
    """The serving tests' reference: each prompt alone, one lane, no burst,
    every step fetched before the next is scheduled."""
    out = []
    for p, m in prompts:
        solo = _engine(depth=0, max_batch=1, decode_burst=1,
                       prefix_cache=False)
        (toks,), _, _ = _serve(solo, [(p, m)])
        out.append(toks)
    return out


# --------------------------------------------------------------------------- #
# 1. the tokens, and the steps
# --------------------------------------------------------------------------- #

@pytest.fixture(scope="module")
def alone():
    return _alone(_prompts())


@pytest.mark.parametrize("over", [
    dict(decode_burst=4), dict(decode_burst=1),
    dict(decode_burst=4, prefix_cache=False),
    dict(decode_burst=2, chunk_size=5, policy="spf"),
    dict(decode_burst=4, kv_cache_dtype="int8")],
    ids=["bursts", "mixed-steps-only", "no-prefix-cache", "spf-odd-chunks",
         "int8-kv"])
def test_tokens_and_steps_are_those_of_depth_0_and_of_the_reference(
        alone, over):
    prompts = _prompts()
    got, kinds, calls = _serve(_engine(**over), prompts)
    want, kinds0, calls0 = _serve(_engine(depth=0, **over), prompts)
    assert got == want
    if "kv_cache_dtype" not in over:      # (int8 pools round: no reference)
        assert got == alone
    assert [len(t) for t in got] == [m for _, m in prompts]
    # a request's row is released when its LAST token is dispatched, so the
    # lane's next request is admitted by the same schedule as at depth 0:
    # the same steps, and one call more to fetch the last
    assert kinds == kinds0 and calls == calls0 + 1
    if over["decode_burst"] > 1:
        assert {"mixed", "burst"} <= set(kinds)
        assert any(a != b for a, b in zip(kinds, kinds[1:]))  # alternating


def test_a_step_limit_on_new_tokens_ends_requests_as_at_depth_0():
    """``step(max_new_tokens=)`` where a request names no limit of its own,
    and ``max_len`` where neither does."""
    prompts = [(p, None) for p, _ in _prompts()[:5]]
    for kw in (dict(max_new_tokens=6), {}):
        got, kinds, _ = _serve(_engine(max_len=40), prompts, **kw)
        want, kinds0, _ = _serve(_engine(depth=0, max_len=40), prompts, **kw)
        assert got == want and kinds == kinds0
        if kw:
            assert {len(t) for t in got} == {6}
        else:       # each row filled to max_len - 1, and its last token
            assert [len(p) + len(t) for (p, _), t in zip(prompts, got)] \
                == [40] * 5


def test_results_arrive_one_call_later():
    eng, eng0 = _engine(), _engine(depth=0)
    (p, _), = _prompts([(5, 1)])
    for e in (eng, eng0):
        e.submit(p, max_new_tokens=1)
    (rid0, toks0), = eng0.step()            # depth 0: the call that ran it
    assert eng.step() == []                 # dispatched, not fetched
    assert eng._flight is not None and eng.num_active
    # its row is free already: the token that ends it has been dispatched
    assert eng._slots == [None] * 3 and not eng._active.any()
    (rid, toks), = eng.step()               # nothing to dispatch: it routes
    assert (rid, toks) == (rid0, toks0) and eng._flight is None
    assert not eng.num_active and eng.step() == []


# --------------------------------------------------------------------------- #
# 2. an end by EOS
# --------------------------------------------------------------------------- #

def _cut(answer, eos):
    return answer[:answer.index(eos) + 1] if eos in answer else answer


@pytest.mark.parametrize("burst", [4, 1], ids=["bursts", "mixed-steps-only"])
def test_answers_end_at_the_eos_and_the_overrun_is_never_reported(burst):
    prompts = _prompts()
    free, _, _ = _serve(_engine(decode_burst=burst), prompts)
    # a token the model does emit, in the middle of some answers and not in
    # others
    counts = {}
    for a in free:
        for t in set(a[1:-1]):
            counts[t] = counts.get(t, 0) + 1
    eos = max(sorted(counts), key=lambda t: (counts[t] < len(free), counts[t]))
    want = [_cut(a, eos) for a in free]
    assert any(len(w) < len(a) for w, a in zip(want, free))
    eng = _engine(decode_burst=burst)
    got, _, _ = _serve(eng, prompts, eos_token_id=eos)
    got0, _, _ = _serve(_engine(depth=0, decode_burst=burst), prompts,
                        eos_token_id=eos)
    # cut at the EOS, nothing behind it reported; the requests that took
    # over the freed lanes (and every other) answer as they do alone
    assert got == want == got0
    assert len(eng._pager._free) == eng._pager.num_blocks - 1 \
        - len(eng.prefix_cache) if eng.prefix_cache is not None else True
    for rid in range(len(prompts)):
        st = eng.pop_stats(rid)
        assert st["tokens"] == len(got[rid]) == len(st["token_times_ns"])


# --------------------------------------------------------------------------- #
# 3. the order of events
# --------------------------------------------------------------------------- #

def _moved(name):
    return dict(monitor.snapshot()["metrics"][name]["values"])


def test_the_next_dispatch_opens_before_the_wait_closes():
    eng = _engine()
    _serve(eng, _prompts()[:2])             # compile both programs
    monitor.enable()
    trace.enable()
    _, kinds, calls = _serve(eng, _prompts([(13, 9), (4, 12)], seed=3))
    trace.disable()
    monitor.disable()
    spans = trace.spans()
    steps = [s for s in spans if s.name == "serving.step"]
    assert len(steps) == calls
    per_call = [{k.name: k for k in spans if k.parent_id == s.span_id}
                for s in steps]
    # the first call dispatches and fetches nothing, the last fetches and
    # dispatches nothing, every call between does both, in this order:
    # schedule N + 1, dispatch N + 1, wait for N, route N
    assert "serving.wait" not in per_call[0]
    assert "serving.dispatch" not in per_call[-1]
    for call in per_call[1:-1]:
        assert sorted(call, key=lambda n: call[n].t0_ns) == [
            "serving.pack_tokens", "serving.dispatch", "serving.wait",
            "serving.route"]
        assert call["serving.dispatch"].t0_ns < call["serving.wait"].t1_ns
    n = len(kinds)
    assert calls == n + 1
    assert _moved(DISPATCHED) == {"ahead=no": 1.0, "ahead=yes": n - 1.0}
    steps_total = _moved("paddle_tpu_serving_steps_total")
    assert steps_total == {f"kind={k}": float(kinds.count(k))
                           for k in set(kinds)}
    # schedule and dispatch under the kind of the step PREPARED, wait and
    # route under that of the step FETCHED: every step's kind is counted
    # once in each of the four phases
    ns = _moved("paddle_tpu_serving_step_phase_ns_total")
    assert set(ns) == {f"phase={p},kind={k}" for k in set(kinds)
                       for p in ("schedule", "dispatch", "wait", "route")}


def test_a_drafter_keeps_nothing_in_flight_and_serves_the_same_tokens():
    prompts = _prompts()
    want, _, _ = _serve(_engine(), prompts)
    eng = _engine(spec_lookahead=3)
    assert eng._depth() == 0
    monitor.enable()
    got, _, _ = _serve(eng, prompts)
    monitor.disable()
    assert got == want and eng.spec_accepted > 0
    assert set(_moved(DISPATCHED)) == {"ahead=no"}


def test_the_numerics_sanitizer_checks_every_step_before_the_next():
    prompts = _prompts()[:4]
    want, _, _ = _serve(_engine(), prompts)
    san.enable("numerics")
    try:
        eng = _engine()
        assert eng._depth() == 0
        monitor.enable()
        got, _, _ = _serve(eng, prompts)
        monitor.disable()
    finally:
        san.disable()
    assert got == want and san.trips() == []
    assert set(_moved(DISPATCHED)) == {"ahead=no"}


def test_cancel_of_an_active_request_routes_the_step_in_flight_first():
    prompts = _prompts([(9, 30), (5, 30), (12, 6)])
    want, _, _ = _serve(_engine(), prompts)
    eng = _engine()
    rids = [eng.submit(p, max_new_tokens=m) for p, m in prompts]
    done = {}
    for _ in range(4):
        done.update(eng.step())
    assert eng._flight is not None and not done
    victim = rids[1]
    before = len(next(s for s in eng._slots
                      if s is not None and s.rid == victim).outputs)
    monitor.enable()
    eng.cancel(victim)
    done.update(eng.step())
    monitor.disable()
    # the step in flight was fetched BEFORE the cancelled request's slot was
    # freed, and the step this call dispatched followed with nothing ahead
    assert _moved(DISPATCHED) == {"ahead=no": 1.0}
    assert eng.cancelled == 1 and victim not in done
    assert all(s is None or s.rid != victim for s in eng._slots)
    assert before >= 1
    while eng.num_active or eng.num_pending:
        for rid, toks in eng.step():
            assert rid not in done
            done[rid] = list(toks)
    assert sorted(done) == [rids[0], rids[2]]
    assert done[rids[0]] == want[0] and done[rids[2]] == want[2]
    assert eng.pop_stats(victim) is None


def test_cancel_of_a_request_that_ends_in_the_step_in_flight_lets_it_stand():
    (p, m), = _prompts([(6, 2)])
    want, _, _ = _serve(_engine(), [(p, m)])
    eng = _engine(decode_burst=1)
    rid = eng.submit(p, max_new_tokens=m)
    assert eng.step() == [] and eng.step() == []    # its last token in flight
    eng.cancel(rid)                                 # ... when the cancel comes
    assert eng.step() == [(rid, want[0])]
    assert eng.cancelled == 0 and not eng.num_active


def test_recover_drops_the_step_in_flight_and_loses_no_request():
    prompts = _prompts([(9, 3), (5, 30), (12, 30)])
    want, _, _ = _serve(_engine(), prompts)
    eng = _engine(decode_burst=1)
    rids = [eng.submit(p, max_new_tokens=m) for p, m in prompts]
    done = {}
    while rids[0] not in done and not (
            eng._flight is not None and rids[0] not in
            {s.rid for s in eng._slots if s is not None}):
        done.update(eng.step())
    # request 0's last token is in flight and its row already released:
    # it lives in the step in flight alone
    assert rids[0] not in done and eng._flight is not None
    fl = eng._flight
    assert eng.recover(reason="test") == 3
    assert eng._flight is None and not eng.num_active
    aborted = {a.rid: a for a in eng.pop_aborted()}
    assert sorted(aborted) == sorted(rids)
    for i, rid in enumerate(rids):
        got = list(aborted[rid].tokens)
        assert got == want[i][:len(got)] and len(got) < len(want[i])
        assert len(aborted[rid].stats["token_times_ns"]) == len(got)
    # the dropped step is never routed: a later call reports nothing of it
    assert eng.step() == [] and fl.epoch != eng._epoch
    # ... and what is submitted anew is served whole, by a warm engine
    again, _, _ = _serve(eng, prompts)
    assert again == want
    assert eng.pop_aborted() == []


@pytest.mark.parametrize("requests,behind", [
    ([(9, 3), (5, 30), (12, 30)], True),
    ([(6, 3)], False),
], ids=["a-step-dispatched-behind-it", "nothing-dispatched-behind-it"])
def test_recover_from_inside_the_wait_aborts_the_request_whose_last_token_it_holds(
        requests, behind):
    """The watchdog fires while the driving thread is blocked fetching step
    N, where a device hang sits. Request 0's last token is in step N: its row
    was released at N's dispatch, it has no lane in step N + 1, and step N is
    no longer ``_flight``. It is aborted like the others, not lost."""
    prompts = _prompts(requests)
    want, _, _ = _serve(_engine(decode_burst=1), prompts)
    eng = _engine(decode_burst=1)
    rids = [eng.submit(p, max_new_tokens=m) for p, m in prompts]
    fetch, fired = eng._fetch, []

    def hung(fl):
        if not fired and any(e[1].rid == rids[0] and e[5]
                             for e in fl.decode + fl.chunks):
            # in no slot, and in no step recover() could reach through
            # ``_flight``: there is one behind it, or none at all
            assert all(s is None or s.rid != rids[0] for s in eng._slots)
            assert (eng._flight is not None) == behind
            assert eng._flight is not fl
            fired.append(eng.recover(reason="hung in the wait"))
        return fetch(fl)                # the thread wakes, and routes nothing

    eng._fetch = hung
    done = {}
    while not fired:
        done.update(eng.step())
    del eng._fetch
    assert fired == [len(rids)] and not done
    assert eng._flight is None and not eng.num_active
    aborted = {a.rid: a for a in eng.pop_aborted()}
    assert sorted(aborted) == sorted(rids)
    for i, rid in enumerate(rids):
        got = list(aborted[rid].tokens)
        assert got == want[i][:len(got)] and len(got) < len(want[i])
        assert aborted[rid].stats["aborted"] \
            and len(aborted[rid].stats["token_times_ns"]) == len(got)
    # nothing of the dead epoch is left behind, or reported later
    assert not eng._unreported and not eng._req_spans
    assert all(eng.pop_stats(rid) is None for rid in rids)
    assert eng.step() == []
    again, _, _ = _serve(eng, prompts)
    assert again == want
    assert eng.pop_aborted() == []


def test_a_step_superseded_while_in_flight_is_dropped_by_the_next_call():
    """A recovery from ANOTHER thread leaves the driving thread's next call
    to find a flight of the dead epoch."""
    eng = _engine()
    (p, m), = _prompts([(6, 9)])
    eng.submit(p, max_new_tokens=m)
    eng.step()
    fl = eng._flight
    eng.recover(reason="test")
    eng._flight = fl                    # as if the call had raced the recovery
    assert eng.step() == [] and eng._flight is None
    assert len(eng.pop_aborted()) == 1


def test_a_decode_burst_change_routes_the_burst_in_flight_under_its_own_k():
    prompts = _prompts([(9, 40), (5, 33)])
    want, _, _ = _serve(_engine(), prompts)
    eng = _engine()
    rids = [eng.submit(p, max_new_tokens=m) for p, m in prompts]
    done = {}
    while not (eng._flight is not None and eng._flight.kind == "burst"):
        done.update(eng.step())
    eng.request_knobs(decode_burst=2)
    monitor.enable()
    done.update(eng.step())
    monitor.disable()
    assert eng.decode_burst == 2 and eng._flight.forwards == 2
    assert _moved(DISPATCHED) == {"ahead=no": 1.0}
    while eng.num_active or eng.num_pending:
        for rid, toks in eng.step():
            assert rid not in done
            done[rid] = list(toks)
    assert [done[r] for r in rids] == want
    # a knob that changes no program leaves the step in flight
    eng.submit(prompts[0][0], max_new_tokens=8)
    eng.step()
    eng.request_knobs(chunk_size=4, decode_priority=0.5)
    monitor.enable()
    eng.step()
    monitor.disable()
    assert _moved(DISPATCHED)["ahead=yes"] == 1.0


def test_a_preemption_waits_for_the_step_in_flight():
    """Pool pressure with a step in flight: the engine fetches first (what
    a preemption spills, KV and tokens, must be on the host), then preempts,
    and every request still answers as it does undisturbed."""
    prompts = _prompts([(10, 8), (20, 8)])
    over = dict(max_batch=2, decode_burst=1, kv_spill=True,
                prefix_cache=False)
    want, _, _ = _serve(_engine(**over), prompts)
    eng = _engine(**over)
    rids = [eng.submit(p, max_new_tokens=m) for p, m in prompts]
    done = {}
    while not eng._decode_ready.any():
        done.update(eng.step())
    monitor.enable()
    fi.arm("paged_kv.ensure", action="flag", nth=1, times=2)
    while eng.num_active or eng.num_pending:
        for rid, toks in eng.step():
            assert rid not in done
            done[rid] = list(toks)
    monitor.disable()
    assert fi.trips() == [("paged_kv.ensure", "flag")] * 2
    assert monitor.snapshot()["metrics"][
        "paddle_tpu_serving_preemptions_total"]["values"][""] == 1
    assert _moved(DISPATCHED)["ahead=no"] >= 1.0
    assert [done[r] for r in rids] == want


# --------------------------------------------------------------------------- #
# 4. the caller's loop
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("n", [1, 4])
def test_the_callers_loop_returns_every_request_the_last_included(n):
    eng = _engine()
    prompts = _prompts(REQUESTS[:n])
    rids = [eng.submit(p, max_new_tokens=m) for p, m in prompts]
    got = {}
    while eng.num_active or eng.num_pending:
        got.update(eng.step())
    assert sorted(got) == rids and eng._flight is None
    assert [len(got[r]) for r in rids] == [m for _, m in prompts]


def test_the_driving_thread_hands_back_every_request():
    import time

    eng = _engine()
    prompts = _prompts()
    want, _, _ = _serve(_engine(), prompts)
    eng.start_driver()
    try:
        rids = [eng.submit(p, max_new_tokens=m) for p, m in prompts]
        got, t0 = {}, time.monotonic()
        while len(got) < len(rids) and time.monotonic() - t0 < 60:
            got.update(eng.pop_results())
            time.sleep(0.001)
    finally:
        eng.stop_driver()
    assert [list(got[r]) for r in rids] == want
