"""Olmo-Hybrid-shaped serving at a small size on the CPU: the model through the
lockstep engine and through ``ContinuousBatchingEngine`` (chunked prefill with
a chunk that divides no prompt, mixed steps, bursts, slots that serve several
requests) against the benchmark's plain float32 reference, on logits; the state
pool's slots; what the cache kinds refuse; the scheduler's counters.

Every width is scaled down with its ratios kept: three linear layers to one
full layer, twice, value heads twice as wide as key heads, a convolution of
kernel 4, MHA without rotary, norms drawn away from 1.

Tolerance. The model and the reference are float32 with the same weights (the
reference rounds its leaves to the served dtype, float32 here). They differ
by the order of float32 sums (the chunked form against single steps, attention
over blocks against whole rows): 2e-3 on logits of size ~4 is what 8 layers of
post-normed sublayers make of 1e-5 a product; a planted fault (a stale state,
a dropped decay, a lost convolution input) moves logits by 0.1 or more.
"""
import dataclasses
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "benchmarks")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import common  # noqa: E402  (benchmarks/)
from builders import olmo_hybrid as B  # noqa: E402
from reference import olmo_hybrid as R  # noqa: E402

from paddle_tpu import monitor  # noqa: E402
from paddle_tpu.models import llama_decode  # noqa: E402
from paddle_tpu.models.olmo_hybrid import OlmoHybridDecodeEngine  # noqa: E402
from paddle_tpu.models.serving import ContinuousBatchingEngine  # noqa: E402
from paddle_tpu.ops.pallas import gated_delta_rule as G  # noqa: E402

SEED = 7
TOL = 2e-3
PERIOD = ["linear_attention"] * 3 + ["full_attention"]
CFG = dict(
    vocab_size=128, hidden_size=64, intermediate_size=128, num_hidden_layers=8,
    num_attention_heads=4, num_key_value_heads=4, layer_types=PERIOD * 3,
    linear_num_key_heads=4, linear_num_value_heads=4, linear_key_head_dim=8,
    linear_value_head_dim=16, linear_conv_kernel_dim=4,
    linear_allow_neg_eigval=True, rms_norm_eps=1e-6,
    max_position_embeddings=512, initializer_range=0.1, torch_dtype="float32",
    model={"dtype": "float32"},
    # a chunk of 20 divides no prompt below, and is no multiple of the block
    engine=dict(max_batch=3, block_size=8, chunk_size=20, max_len=256,
                prefix_cache=False))
# (prompt length, tokens asked): longer than a chunk of the recurrence (64),
# shorter than a block, a prompt of one token more than a chunk, and enough
# at once that slots are taken over several times
REQUESTS = [(50, 20), (9, 30), (170, 12), (33, 40), (100, 20), (21, 9),
            (64, 7), (1, 5)]

_MODEL = []


@pytest.fixture(autouse=True, scope="module")
def _model():
    model = B.construct(CFG)
    common.load_weights(model, B.weights(SEED, CFG, "float32"))
    model.eval()
    _MODEL.append(model)
    yield
    _MODEL.clear()


@pytest.fixture(scope="module")
def reference():
    return R.ServeReference(SEED, CFG)


def _engine(**over):
    return ContinuousBatchingEngine(_MODEL[0], **{**CFG["engine"], **over})


def _prompts():
    rng = np.random.default_rng(0)
    return [(rng.integers(0, CFG["vocab_size"], n, dtype=np.int32), m)
            for n, m in REQUESTS]


def _serve(eng, prompts):
    rids = [eng.submit(p, max_new_tokens=m) for p, m in prompts]
    done, kinds = {}, []
    while eng.num_active or eng.num_pending:
        for rid, toks in eng.step():
            done[rid] = np.asarray(toks, np.int32)
        kinds.append(eng._step_kind)
    return [done[r] for r in rids], kinds


def _worst_gap(reference, prompts, answers):
    """The widest gap by which a served token's logit lies below the
    reference's best, over every served token of every request."""
    worst = 0.0
    for (p, m), toks in zip(prompts, answers):
        assert len(toks) == m
        logits = np.asarray(reference.logits(np.concatenate([p, toks[:-1]])))
        rows = logits[len(p) - 1:]
        worst = max(worst, float(
            (rows.max(-1) - rows[np.arange(m), toks]).max()))
    return worst


def test_the_forward_pass_is_the_reference(reference):
    rng = np.random.default_rng(1)
    ids = rng.integers(0, CFG["vocab_size"], (2, 150), dtype=np.int32)
    logits = np.asarray(_MODEL[0](ids).value)
    for b in range(2):
        want = np.asarray(reference.logits(ids[b]))
        assert np.abs(logits[b] - want).max() < TOL


def test_lockstep_prefill_and_decode_are_the_reference(reference):
    """``LlamaDecodeEngine`` (no scheduler): prefill of two prompts of one
    length, then single steps from the slots' state."""
    rng = np.random.default_rng(2)
    ids = rng.integers(0, CFG["vocab_size"], (2, 70), dtype=np.int32)
    eng = OlmoHybridDecodeEngine(_MODEL[0], max_len=128,
                                 kv_cache_layout="paged", block_size=8)
    logits, cache, pos = eng.prefill(ids)
    seqs = [list(row) for row in ids]
    got = [[np.asarray(logits[b])] for b in range(2)]
    for _ in range(12):
        tok = np.asarray(jnp.argmax(logits, -1), np.int32)
        for b in range(2):
            seqs[b].append(int(tok[b]))
        logits, cache = eng.decode_step(tok[:, None], cache, pos)
        pos += 1
        for b in range(2):
            got[b].append(np.asarray(logits[b]))
    for b in range(2):
        want = np.asarray(reference.logits(np.asarray(seqs[b], np.int32)))
        assert np.abs(np.stack(got[b]) - want[69:]).max() < TOL


@pytest.mark.parametrize("burst", [4, 1], ids=["bursts", "mixed-steps-only"])
def test_the_engine_serves_the_references_tokens(reference, burst):
    """Chunked prefill (20 divides no prompt), decode lanes beside chunks,
    several runs in a step, slots taken over by later requests; with bursts
    and with every decode token from a mixed step."""
    prompts = _prompts()
    answers, kinds = _serve(_engine(decode_burst=burst), prompts)
    assert ("burst" in kinds) == (burst > 1) and "mixed" in kinds
    assert _worst_gap(reference, prompts, answers) < TOL


def _dispatched(eng):
    """Record the kinds of the steps ``eng`` dispatches, in order."""
    kinds, dispatch = [], eng._dispatch

    def spy(plan, *rest):
        kinds.append(plan[0])
        return dispatch(plan, *rest)

    eng._dispatch = spy
    return kinds


def test_one_step_in_flight_serves_the_tokens_and_steps_of_depth_0(reference):
    """ISSUE 37: the engine dispatches step N + 1 before it fetches step N
    (state slots noted and handed on at dispatch: a slot's next request
    starts from zeros in a step that runs behind its last request's last).
    The same engine with every step fetched in the call that dispatched it
    (depth 0: the engine before) serves the same tokens by the same steps,
    and both are the reference's."""
    prompts = _prompts()
    eng, eng0 = _engine(), _engine()
    eng0._depth = lambda: 0
    kinds, kinds0 = _dispatched(eng), _dispatched(eng0)
    answers, _ = _serve(eng, prompts)
    answers0, _ = _serve(eng0, prompts)
    assert [a.tolist() for a in answers] == [a.tolist() for a in answers0]
    assert kinds == kinds0 and {"mixed", "burst"} <= set(kinds)
    assert any(a != b for a, b in zip(kinds, kinds[1:]))
    assert _worst_gap(reference, prompts, answers) < TOL
    assert eng._flight is None and eng0._flight is None


def test_a_slots_second_request_is_served_as_by_a_fresh_engine():
    """max_batch 1: every request but the first takes over a slot whose state
    and kept convolution inputs another request left."""
    prompts = _prompts()[:4]
    together, _ = _serve(_engine(max_batch=1), prompts)
    for one, want in zip(prompts, together):
        alone, _ = _serve(_engine(max_batch=1), [one])
        np.testing.assert_array_equal(alone[0], want)


def test_padding_lanes_and_the_null_slot_change_no_state():
    """One request decoding in slot 0 of 3: the other slots' state and kept
    inputs stay as they were (poisoned here), through mixed steps (padding
    lanes) and bursts (rows without a request), and the null slot stays
    finite."""
    eng = _engine(decode_burst=2)
    e = eng._inner
    linear = [i for i, ki in enumerate(e.layer_kind)
              if not e.kinds[ki].paged]
    assert len(linear) == 6
    for i in linear:
        state, conv = eng._pools[i]
        eng._pools[i] = (state.at[1].set(7.0), conv.at[1].set(7.0))
    (p, m), = _prompts()[:1]
    eng.submit(p, max_new_tokens=m)
    kinds = set()
    while eng.num_active or eng.num_pending:
        eng.step()
        kinds.add(eng._step_kind)
    assert kinds == {"mixed", "burst"}
    for i in linear:
        state, conv = (np.asarray(x) for x in eng._pools[i])
        # a mixed step touches no slot without a lane
        assert np.isfinite(state).all() and np.isfinite(conv).all()
        assert np.abs(state[0]).max() > 0
    # slot 2 never ran in a mixed step; a burst runs every row (an idle one
    # on its own slot, from position 0: zeros in, finite out)
    alone, _ = _serve(_engine(decode_burst=2), _prompts()[:1])
    again, _ = _serve(eng, _prompts()[:1])
    np.testing.assert_array_equal(alone[0], again[0])


def test_a_mixed_steps_padding_lanes_write_nowhere():
    """The mixed step alone (decode_burst 1): slots without a lane keep
    their bits."""
    eng = _engine(decode_burst=1)
    e = eng._inner
    li = next(i for i, ki in enumerate(e.layer_kind) if not e.kinds[ki].paged)
    state, conv = eng._pools[li]
    eng._pools[li] = (state.at[1:3].set(7.0), conv.at[1:3].set(7.0))
    (p, m), = _prompts()[:1]
    eng.submit(p, max_new_tokens=m)
    while eng.num_active or eng.num_pending:
        eng.step()
    state, conv = (np.asarray(x) for x in eng._pools[li])
    np.testing.assert_array_equal(state[1:3], 7.0)
    np.testing.assert_array_equal(conv[1:3], 7.0)
    np.testing.assert_array_equal(state[3], 0.0)          # the null slot


@pytest.mark.parametrize("asked,named", [
    (dict(prefix_cache=True), "prefix_cache=False"),
    (dict(kv_spill=True), "kv_spill=False"),
    (dict(spec_lookahead=2), "spec_lookahead=0"),
    (dict(prefix_cache=True, kv_spill=True, spec_lookahead=3),
     "prefix_cache=False and kv_spill=False and spec_lookahead=0"),
])
def test_what_a_recurrent_state_cannot_do_is_refused(asked, named):
    """Reuse by another sequence, a spill and a draft's rollback are read off
    the kinds' descriptions; the text names the kind and every option."""
    with pytest.raises(ValueError, match="linear layers") as err:
        _engine(**asked)
    assert named in str(err.value) and "recurrent" in str(err.value)
    assert "silent wrong reuse is not an option" in str(err.value)


def test_the_kinds_say_what_they_permit():
    full, linear = _MODEL[0].config.kinds()
    assert (full.paged, full.reuse, full.spill, full.rollback) == \
        (True, True, True, True)
    assert (linear.paged, linear.reuse, linear.spill, linear.rollback) == \
        (False, False, False, False)
    window = dataclasses.replace(full, window=16)
    assert (window.reuse, window.spill, window.rollback) == \
        (False, False, True)
    assert set(linear.why_not) == {"reuse", "spill", "rollback"}
    assert set(window.why_not) == {"reuse", "spill"}


def test_the_state_pool_is_sized_and_reported():
    eng = _engine()
    heads, dk, dv = 4, 8, 16
    slot = heads * dk * dv * 4 + 3 * heads * (2 * dk + dv) * 4   # float32
    assert eng.state_pool_bytes == (3 + 1) * 6 * slot
    full = (3 * 32 + 1) * 8 * 4 * 16 * 2 * 4 * 2       # blocks x ... x layers
    assert eng.kv_pool_bytes == full + eng.state_pool_bytes
    assert eng.status()["kv"]["state_pool_bytes"] == eng.state_pool_bytes
    for i, ki in enumerate(eng._inner.layer_kind):
        if not eng._inner.kinds[ki].paged:
            state, conv = eng._pools[i]
            assert state.shape == (4, 2, 8, 32) and state.dtype == jnp.float32
            assert conv.shape == (4, 3, 4 * (8 + 8 + 16))


def test_the_scheduler_counts_the_recurrences_tokens_runs_and_bytes():
    monitor.reset()
    monitor.enable()
    try:
        eng = _engine()
        prompts = _prompts()
        answers, kinds = _serve(eng, prompts)
        snap = monitor.snapshot()["metrics"]
    finally:
        monitor.disable()
        monitor.reset()
    tokens = snap["paddle_tpu_serving_linear_tokens_total"]["values"]
    runs = snap["paddle_tpu_serving_linear_runs_total"]["values"]
    fed = sum(len(p) for p, _ in prompts)
    served = sum(m - 1 for _, m in prompts)
    # every prompt token runs once, in a chunk of 20 or what the step's
    # budget leaves of it; a chunk of ONE token is a run of one, counted with
    # the decode lanes; every served token but a request's last is fed back,
    # a burst's spare iterations (a request that ends inside one) besides
    ones = fed - tokens["path=chunk"]
    assert 0 <= ones <= len(prompts)
    assert sum(len(p) // 20 for p, _ in prompts) <= runs["path=chunk"] \
        <= tokens["path=chunk"] // 2
    assert tokens["path=step"] >= served + ones
    assert tokens["path=step"] == runs["path=step"]
    assert snap["paddle_tpu_state_slots_reset_total"]["values"][""] == \
        len(prompts)
    steps = snap["paddle_tpu_cache_byte_steps_total"]["values"]
    slot = 4 * 8 * 16 * 4 + 3 * 4 * 32 * 4
    assert steps["kind=linear"] % (6 * slot) == 0 and steps["kind=linear"] > 0
    block = 8 * 4 * 16 * 2 * 4
    assert steps["kind=full"] == block * snap[
        "paddle_tpu_kv_block_steps_total"]["values"]["kind=full"]
    assert snap["paddle_tpu_state_pool_bytes"]["values"][""] == \
        eng.state_pool_bytes
    assert snap["paddle_tpu_serving_attn_kind_blocks_total"]["values"][
        "kind=full"] > 0


def test_the_state_slots_span_lies_under_pack_tokens():
    from paddle_tpu.monitor import trace

    trace.enable()
    try:
        eng = _engine()
        _serve(eng, _prompts()[:2])
        spans = trace.spans()
    finally:
        trace.disable()
        trace.reset()
    by_id = {s.span_id: s for s in spans}
    mine = [s for s in spans if s.name == "serving.state_slots"]
    assert mine and all(
        by_id[s.parent_id].name == "serving.pack_tokens" for s in mine)
    assert sum(s.attrs["reset"] for s in mine) == 2


# -- planted faults: each has to move the served logits past the tolerance ----
def _no_decay(eng):
    for p in eng._inner.layers:
        if "A_log" in p:
            p["A_log"] = jnp.full_like(p["A_log"], -40.0)    # exp(g) = 1


def _beta_not_doubled(eng):
    e = eng._inner
    e.kinds = tuple(k if k.paged else dataclasses.replace(k, neg_eigval=False)
                    for k in e.kinds)


def _state_not_reset(eng, monkeypatch):
    real = G.gated_delta

    def stale(q, k, v, g, beta, state, positions, plan=None):
        if plan is not None:
            plan = dict(plan, fresh=jnp.zeros_like(plan["fresh"]),
                        code=jnp.minimum(plan["code"], 1),
                        order_fresh=jnp.zeros_like(plan["order_fresh"]))
        return real(q, k, v, g, beta, state, positions + 1, plan)

    monkeypatch.setattr(G, "gated_delta", stale)


def _conv_not_carried(eng, monkeypatch):
    from paddle_tpu.models import linear_attention as L

    real = L.causal_conv

    def forgetful(xin, conv, taps, positions, plan):
        return real(xin, jnp.zeros_like(conv), taps, positions, plan)

    monkeypatch.setattr(L, "causal_conv", forgetful)


@pytest.mark.parametrize("fault", [_no_decay, _beta_not_doubled,
                                   _state_not_reset, _conv_not_carried])
def test_a_planted_fault_moves_the_served_logits(reference, monkeypatch,
                                                 fault):
    eng = _engine()
    if fault in (_no_decay, _beta_not_doubled):
        fault(eng)
    else:
        fault(eng, monkeypatch)
    prompts = _prompts()
    answers, _ = _serve(eng, prompts)
    assert _worst_gap(reference, prompts, answers) > 20 * TOL


def test_the_full_layers_of_thirty_heads_keep_flat_pools():
    """At the published widths a [.., 30, 128] pool's heads would sit on
    sublanes the one-row-a-head kernel cannot slice (30 is no multiple of
    8): the kind asks for flat pools, which the grouped-query kernel reads."""
    from paddle_tpu.models.olmo_hybrid import OlmoHybridConfig

    full, linear = OlmoHybridConfig().kinds()
    assert full.flat and full.num_kv == 30 and full.head_dim == 128
    assert full.rotary_dim == 0 and full.window is None
    assert (linear.num_heads, linear.key_dim, linear.value_dim) == \
        (30, 96, 192)
    assert linear.conv_width == 11520 and linear.conv_kernel == 4


def test_no_model_name_in_the_serving_block_or_the_scheduler():
    for name in ("serving.py", "llama_decode.py", "paged_kv.py",
                 "linear_attention.py"):
        with open(os.path.join(ROOT, "paddle_tpu", "models", name)) as f:
            text = f.read().lower()
        assert "olmo" not in text, name
    assert llama_decode.StateKind.paged is False
