"""MiMo-V2-Flash-shaped serving at a small size on the CPU: the model through
``ContinuousBatchingEngine`` (chunked prefill, mixed steps, bursts, requests
that outgrow their window by several blocks) against the benchmark's plain
float32 reference, on logits; the cache manager's window release; the expert
layer's shares against the uncut layer.

Every width is scaled down with its ratios kept: the QK head (24) is wider
than the V head (16), full layers have 1 KV head and window layers 2, rotary
covers int(0.334 * 24) = 8 dims, the window (20) is shorter than the prompts
and no multiple of the block (8), sinks and selection bias are drawn non-zero.
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
from builders import mimo_v2_flash as B  # noqa: E402
from reference import mimo_v2_flash as R  # noqa: E402

from paddle_tpu.incubate.distributed.models.moe.held_experts import (  # noqa: E402
    held_experts_mlp)
from paddle_tpu.models import paged_kv  # noqa: E402
from paddle_tpu.models.mimo_v2 import MiMoV2DecodeEngine  # noqa: E402
from paddle_tpu.models.serving import ContinuousBatchingEngine  # noqa: E402

SEED = 7
CFG = dict(
    vocab_size=128, hidden_size=64, intermediate_size=128, num_hidden_layers=4,
    num_attention_heads=8, num_key_value_heads=1, head_dim=24, v_head_dim=16,
    swa_num_key_value_heads=2, swa_head_dim=24, swa_v_head_dim=16,
    sliding_window=20, hybrid_layer_pattern=[0, 1, 1, 0],
    moe_layer_freq=[0, 1, 1, 1], rope_theta=5e6, swa_rope_theta=1e4,
    partial_rotary_factor=0.334, attention_value_scale=0.707,
    add_swa_attention_sink_bias=True, add_full_attention_sink_bias=False,
    moe_intermediate_size=32, n_routed_experts=4,
    published={"n_routed_experts": 16}, num_experts_per_tok=4,
    layernorm_epsilon=1e-5, max_position_embeddings=256,
    initializer_range=0.1, model={"dtype": "float32"},
    engine=dict(max_batch=3, block_size=8, chunk_size=16, max_len=128,
                prefix_cache=False))
# (prompt length, tokens asked): longer than a chunk, shorter than a block,
# five blocks past the window, and enough at once that lanes fill and drain
REQUESTS = [(50, 20), (9, 30), (70, 12), (33, 40), (100, 20)]


_MODEL = []


@pytest.fixture(autouse=True, scope="module")
def _model():
    # 16 experts have fewer near-ties for a bias to flip than 256: a wider
    # draw, for the program's weights and the reference's alike
    std, B.SELECTION_BIAS_STD = B.SELECTION_BIAS_STD, 0.5
    model = B.construct(CFG)
    common.load_weights(model, B.weights(SEED, CFG, "float32"))
    model.eval()
    _MODEL.append(model)
    yield
    _MODEL.clear()
    B.SELECTION_BIAS_STD = std


def _engine():
    return ContinuousBatchingEngine(_MODEL[0], **CFG["engine"])


def _prompts():
    rng = np.random.default_rng(0)
    return [(rng.integers(0, CFG["vocab_size"], n, dtype=np.int32), m)
            for n, m in REQUESTS]


def _serve(eng, prompts, between_steps=None):
    rids = [eng.submit(p, max_new_tokens=m) for p, m in prompts]
    out, kinds = {}, set()
    while eng.num_active or eng.num_pending:
        for rid, toks in eng.step():
            out[rid] = toks
        kinds.add(eng._step_kind)
        if between_steps is not None:
            between_steps(eng)
    return [np.asarray(out[r]) for r in rids], kinds


def _widest_gap(prompts, served):
    """How far below the reference's best a served token's logit lies, at the
    worst: the benchmark's own comparison (``ServeReference.gaps``)."""
    n, rows = len(prompts), max(len(o) for o in served)
    tokens = np.zeros((n, CFG["engine"]["max_len"]), np.int32)
    positions = np.zeros((n, rows), np.int32)
    query = np.zeros((n, rows), np.int32)
    valid = np.zeros((n, rows), bool)
    for j, ((p, _), o) in enumerate(zip(prompts, served)):
        tokens[j, :len(p)] = p
        tokens[j, len(p):len(p) + len(o) - 1] = o[:-1]
        positions[j, :len(o)] = len(p) - 1 + np.arange(len(o))
        query[j, :len(o)] = o
        valid[j, :len(o)] = True
    gaps, _ = R.ServeReference(SEED, CFG).gaps(tokens, positions, query)
    return float(np.asarray(gaps)[valid].max())


# -- faults a program could have without any shape error ----------------------
def _no_sink(e):
    for p in e.layers:
        p.pop("sink", None)


def _no_selection_bias(e):
    for p in e.layers:
        if "router_bias" in p:
            p["router_bias"] = jnp.zeros_like(p["router_bias"])


def _no_value_scale(e):
    e.v_scale = 1.0


def _one_theta(e):
    e.kinds = (e.kinds[0], dataclasses.replace(e.kinds[1],
                                               theta=e.kinds[0].theta))


def _window_one_wider(e):
    e.kinds = (e.kinds[0], dataclasses.replace(e.kinds[1],
                                               window=e.kinds[1].window + 1))


def _rotary_on_the_whole_head(e):
    e.kinds = tuple(dataclasses.replace(k, rotary_dim=k.head_dim)
                    for k in e.kinds)


def _the_next_experts(e):
    e.held_lo = 1


# Both sides compute in float32. A sound engine reads 0 at nearly every token:
# its first choice IS the reference's. Where the reference has two tokens (or
# the router its 4th and 5th expert) within float32 rounding of each other it
# reads a little: 0.005 at these weights (initializer_range 0.1, so that the
# attention is peaked enough for a position's rotation to matter). Each fault
# below moves the logits so far that some served token lies 0.06 (the second
# theta) to 1.8 (rotary on the whole head) below the reference's best.
TOLERANCE = 2e-2


@pytest.mark.parametrize("fault", [
    None, _no_sink, _no_selection_bias, _no_value_scale, _one_theta,
    _window_one_wider, _rotary_on_the_whole_head, _the_next_experts,
], ids=lambda f: "sound" if f is None else f.__name__.lstrip("_"))
def test_engine_against_the_reference_on_logits(fault):
    eng = _engine()
    if fault is not None:
        fault(eng._inner)                 # before the programs are traced
    prompts = _prompts()
    served, kinds = _serve(eng, prompts)
    assert [len(o) for o in served] == [m for _, m in REQUESTS]
    gap = _widest_gap(prompts, served)
    if fault is None:
        assert kinds >= {"mixed", "burst"}
        assert gap < TOLERANCE
    else:
        assert gap > 2 * TOLERANCE


def _dispatched(eng):
    """Record the kinds of the steps ``eng`` dispatches, in order."""
    kinds, dispatch = [], eng._dispatch

    def spy(plan, *rest):
        kinds.append(plan[0])
        return dispatch(plan, *rest)

    eng._dispatch = spy
    return kinds


def test_one_step_in_flight_serves_the_tokens_and_steps_of_depth_0():
    """ISSUE 37: the engine dispatches step N + 1 before it fetches step N
    (window blocks released, expert pairs counted and rows handed on at
    dispatch). The same engine with every step fetched in the call that
    dispatched it (depth 0: the engine before) serves the same tokens by
    the same steps, and both are the reference's."""
    prompts = _prompts()
    eng, eng0 = _engine(), _engine()
    eng0._depth = lambda: 0
    kinds, kinds0 = _dispatched(eng), _dispatched(eng0)
    served, _ = _serve(eng, prompts)
    served0, _ = _serve(eng0, prompts)
    assert [o.tolist() for o in served] == [o.tolist() for o in served0]
    assert kinds == kinds0 and {"mixed", "burst"} <= set(kinds)
    assert any(a != b for a, b in zip(kinds, kinds[1:]))
    assert _widest_gap(prompts, served) < TOLERANCE
    assert eng._flight is None and eng0._flight is None


def test_lockstep_generate_and_forward_agree_with_the_engine():
    """The same block under lockstep prefill + decode (``generate``) and the
    model's own cache-free forward: greedy tokens equal the engine's."""
    eng = _engine()
    prompt, n = _prompts()[3]
    served, _ = _serve(eng, [(prompt, n)])
    inner = MiMoV2DecodeEngine(_MODEL[0], max_len=128,
                               kv_cache_layout="paged", block_size=8)
    toks = np.asarray(inner.generate(prompt[None], max_new_tokens=n))[0]
    assert toks.tolist() == served[0].tolist()
    full = np.concatenate([prompt, served[0][:-1]])
    logits = np.asarray(_MODEL[0](full[None]).value)[0]
    assert np.argmax(logits[len(prompt) - 1:], -1).tolist() == served[0].tolist()


# -- the cache manager ---------------------------------------------------------
POISON = 1e30


def test_window_blocks_are_freed_and_a_poisoned_freed_block_changes_nothing():
    """A long lane's window cache stays bounded while its full cache grows,
    and what was handed back is never read again: 1e30 written over every
    free block of every pool after each step leaves the tokens as they were
    (not NaN: a block granted anew holds the poison at the positions not yet
    written, which every path masks by its scores and multiplies by a
    probability of exactly 0)."""
    prompts = _prompts()
    clean, _ = _serve(_engine(), prompts)
    most = {"full": 0, "window": 0}

    def poison(eng):
        e = eng._inner
        for (k, v), ki in zip(eng._pools, e.layer_kind):
            assert k.ndim == 3                       # flat pools
        for ki, pg in enumerate(eng._pagers):
            name = e.kinds[ki].name
            most[name] = max(most[name], pg.blocks_in_use)
            free = jnp.asarray(np.asarray(pg._free, np.int32))
            if len(pg._free):
                eng._pools = [
                    (k.at[free].set(POISON), v.at[free].set(POISON))
                    if e.layer_kind[li] == ki else (k, v)
                    for li, (k, v) in enumerate(eng._pools)]

    eng = _engine()
    poisoned, _ = _serve(eng, prompts, between_steps=poison)
    assert [o.tolist() for o in poisoned] == [o.tolist() for o in clean]
    # 3 lanes: a window of 20 spans at most 4 blocks of 8 and a chunk of 16
    # adds 2 before the next release; the full cache holds up to 15 a lane
    lanes, bs = CFG["engine"]["max_batch"], CFG["engine"]["block_size"]
    assert most["window"] <= lanes * ((20 - 1) // bs + 2) + 16 // bs + lanes
    assert most["full"] >= 20 > most["window"]
    assert all(pg.blocks_in_use == 0 for pg in eng._pagers)
    # a row's table is sparse at the head: released slots point at block 0
    pg = paged_kv.PagedKVCache(1, 12, 8, 2, 24, batch=1, max_blocks_per_seq=8,
                               v_head_dim=16, flat=True, window=20)
    pg.ensure_capacity([41])
    assert pg.release_behind([40]) == 2            # positions 21.. are kept
    assert pg._tables_np[0].tolist()[:2] == [0, 0] and pg.blocks_in_use == 4
    pg.ensure_capacity([50])                       # grants go on at the tail
    assert (pg._tables_np[0] > 0).tolist() == [False] * 2 + [True] * 5 + [False]
    assert pg.k[0].shape == (12, 8, 48) and pg.v[0].shape == (12, 8, 32)


def test_a_model_with_window_layers_refuses_prefix_cache_and_spill():
    model = _MODEL[0]
    for kw in ({"prefix_cache": True}, {"prefix_cache": False, "kv_spill": True}):
        with pytest.raises(ValueError, match="sliding-window"):
            ContinuousBatchingEngine(model, max_batch=2, max_len=64,
                                     block_size=8, chunk_size=8, **kw)


# -- the expert layer's shares -------------------------------------------------
def test_the_shares_of_the_expert_layer_add_up_to_the_uncut_layer():
    """Four chips of 4 experts each: every share routes over all 16 and
    computes its own experts' part; the parts add up to what the reference
    gives for the whole layer, and each part alone is what the reference gives
    when it is told the same share."""
    rng = np.random.default_rng(3)
    hdim, width, routed, top = 64, 32, 16, 4
    h = jnp.asarray(rng.normal(size=(40, hdim)), jnp.float32)
    p = {"mlp.gate.weight": rng.normal(size=(hdim, routed)) * 0.3,
         "mlp.gate.e_score_correction_bias": rng.normal(size=(routed,)) * 0.1,
         "mlp.experts.gate_proj": rng.normal(size=(routed, hdim, width)) * 0.1,
         "mlp.experts.up_proj": rng.normal(size=(routed, hdim, width)) * 0.1,
         "mlp.experts.down_proj": rng.normal(size=(routed, width, hdim)) * 0.1}
    p = {k: jnp.asarray(v, jnp.float32) for k, v in p.items()}
    cfg = {"num_experts_per_tok": top, "n_routed_experts": routed}
    whole = R.experts(h, p, cfg, None)
    parts, pairs = [], []
    for lo in range(0, routed, 4):
        y, n = held_experts_mlp(
            h, p["mlp.gate.weight"], p["mlp.gate.e_score_correction_bias"],
            *(p["mlp.experts." + w][lo:lo + 4]
              for w in ("gate_proj", "up_proj", "down_proj")), lo, top)
        parts.append(y)
        pairs.append(np.asarray(n))
    np.testing.assert_allclose(sum(parts), whole, atol=2e-6)
    assert sum(n[0] for n in pairs) == 40 * top == pairs[0][1]
    assert all(0 < n[2] <= 4 for n in pairs)
    # a share alone: the reference given the first four experts
    first = {k: (v[:4] if k.startswith("mlp.experts") else v)
             for k, v in p.items()}
    np.testing.assert_allclose(
        parts[0], R.experts(h, first, dict(cfg, n_routed_experts=4), None),
        atol=2e-6)
    assert float(jnp.abs(parts[0] - whole).max()) > 1e-3


# -- the kernel against the plain path, in interpret mode ----------------------
@pytest.mark.parametrize("sink", [False, True], ids=["no-sink", "sink"])
@pytest.mark.parametrize("window", [None, 8, 9, 16, 20])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gqa_kernel_against_the_plain_path(monkeypatch, dtype, window, sink):
    """Window starts on, just before and just after block edges (block 8:
    windows of 8, 9, 16 and 20 at positions 0, 7, 8, 23, 40, 47), sink on and
    off, K rows wider than V rows, 4 query heads a KV head."""
    from paddle_tpu.ops.pallas.paged_attention import paged_attention_gqa

    rng = np.random.default_rng(1)
    T, n_q, kv, dk, dv, bs, width, nb = 6, 8, 2, 24, 16, 8, 6, 40
    q = jnp.asarray(rng.normal(size=(T, n_q, dk)), dtype)
    k = jnp.asarray(rng.normal(size=(nb, bs, kv * dk)), dtype)
    v = jnp.asarray(rng.normal(size=(nb, bs, kv * dv)), dtype)
    tables = jnp.asarray(rng.permutation(np.arange(1, nb))[:T * width]
                         .reshape(T, width), jnp.int32)
    pos = jnp.asarray([0, 7, 8, 23, 40, 47], jnp.int32)
    sk = jnp.asarray(rng.normal(size=(n_q,)), jnp.float32) if sink else None
    got = paged_attention_gqa(q, k, v, tables, pos, None, window, sk)
    want = paged_kv.paged_attention_decode_plain(q, k, v, tables, pos, None,
                                                 window, sk)
    assert got.shape == (T, n_q, dv) and got.dtype == dtype
    # float32: the same arithmetic in another order. bfloat16: the kernel
    # rounds the probabilities to the pool's dtype for the values product
    # (2**-9 relative on sums of ~1) and both round the result
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol)
    # the dispatcher takes the kernel for flat pools where it applies
    monkeypatch.setattr(paged_kv, "_kernel_applies", lambda *a: True)
    via = paged_kv.paged_attention_decode(q, k, v, tables, pos, window=window,
                                          sink=sk)
    np.testing.assert_array_equal(np.asarray(via, np.float32),
                                  np.asarray(got, np.float32))


def test_engine_tiles_a_chunk_in_both_cache_kinds_and_counts_its_blocks_once(
        monkeypatch):
    """(ISSUE 33) one 50-token prompt through the engine with the kernels on
    (interpret mode): the same greedy tokens as through the gather path, and
    ``attn_kind_blocks_total`` a kind counts a chunk's blocks once: a full
    layer from block 0, a window layer (20 positions, block 8) from the
    FIRST lane's window's first block."""
    from paddle_tpu import monitor

    prompt = [(_prompts()[0][0], 4)]
    want, _ = _serve(_engine(), prompt)
    monkeypatch.setattr(paged_kv, "_kernel_applies", lambda *a: True)
    name = "paddle_tpu_serving_attn_kind_blocks_total"
    lanes = "paddle_tpu_serving_attn_lanes_total"
    monitor.enable()
    seen = []

    def read():
        m = monitor.snapshot()["metrics"]
        seen.append({**m[name]["values"], **m[lanes]["values"]})

    read()
    try:
        got, _ = _serve(_engine(), prompt, between_steps=lambda eng: read())
    finally:
        monitor.disable()
    np.testing.assert_array_equal(got[0], want[0])
    moved = [{k: after[k] - before.get(k, 0.0) for k in after}
             for before, after in zip(seen, seen[1:])]
    # chunks at 0..15, 16..31 and 32..47 are tiles; 48, 49 walk alone
    assert moved[:4] == [
        {"kind=full": 2.0, "kind=window": 2.0, "path=tiled": 16.0,
         "path=lane": 0.0},
        {"kind=full": 4.0, "kind=window": 4.0, "path=tiled": 16.0,
         "path=lane": 0.0},
        {"kind=full": 6.0, "kind=window": 5.0, "path=tiled": 16.0,
         "path=lane": 0.0},
        {"kind=full": 14.0, "kind=window": 8.0, "path=tiled": 0.0,
         "path=lane": 2.0}]


def _gqa_pack(lanes, total, width):
    """``lanes``: (table row, position) a lane, padded to ``total`` lanes
    with slot 0 at position 0, as the engine pads; one table of 4 rows."""
    rng = np.random.default_rng(2)
    row_tables = rng.permutation(np.arange(1, 4 * width + 1)).reshape(
        4, width).astype(np.int32)
    pad = total - len(lanes)
    rows = np.array([r for r, _ in lanes] + [0] * pad, np.int32)
    pos = np.array([p for _, p in lanes] + [0] * pad, np.int32)
    return row_tables[rows], pos, rows


def _run(row, start, n):
    return [(row, start + i) for i in range(n)]


GQA_TILES = {
    # (a) one run that starts mid-block (block 8) and crosses two boundaries
    "midblock-run": dict(lanes=_run(2, 5, 14), window=None, sink=False),
    # (b) decode lanes, two runs of different rows, padding lanes behind
    "two-runs": dict(lanes=[(0, 30), (1, 9)] + _run(2, 3, 12) + _run(3, 20, 7),
                     window=9, sink=True),
    # (c) runs shorter than a tile (32 lanes), and one too short to be one
    "short-runs": dict(lanes=[(0, 44)] + _run(2, 14, 5) + _run(3, 30, 3),
                       window=None, sink=True),
    # a run of two tiles whose second is not full
    "two-tiles": dict(lanes=[(1, 9)] + _run(2, 1, 40), window=16, sink=False),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(GQA_TILES))
def test_gqa_tiles_against_the_plain_path(case, dtype):
    """(ISSUE 33) lanes of one table row at consecutive positions walk the
    row once as query tiles; the lanes no tile serves keep the per-lane
    kernel's bits. K rows wider than V rows, 4 query heads a KV head."""
    from paddle_tpu.ops.pallas import paged_attention as pa

    spec = GQA_TILES[case]
    T, n_q, kv, dk, dv, bs, width = 48, 8, 2, 24, 16, 8, 6
    tables, pos, rows = _gqa_pack(spec["lanes"], T, width)
    n = len(spec["lanes"])
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(T, n_q, dk)), dtype)
    k = jnp.asarray(rng.normal(size=(4 * width + 1, bs, kv * dk)), dtype)
    v = jnp.asarray(rng.normal(size=(4 * width + 1, bs, kv * dv)), dtype)
    sk = jnp.asarray(rng.normal(size=(n_q,)), jnp.float32) \
        if spec["sink"] else None
    args = (q, k, v, jnp.asarray(tables), jnp.asarray(pos), None,
            spec["window"], sk)
    plan = pa.plan_tiles(rows, pos, pa.tile_lanes(n_q // kv, True), np)
    assert plan["tiles"] == (1 if case in ("midblock-run", "short-runs")
                             else 2)
    got = pa.paged_attention_gqa(*args, rows=jnp.asarray(rows))
    want = paged_kv.paged_attention_decode_plain(*args)
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32)[:n],
                               np.asarray(want, np.float32)[:n], atol=tol)
    alone = pa.paged_attention_gqa(*args)
    left = ~plan["tiled"][:n]
    np.testing.assert_array_equal(np.asarray(got, np.float32)[:n][left],
                                  np.asarray(alone, np.float32)[:n][left])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_gqa_tiles_with_a_run_longer_than_the_window(monkeypatch, dtype):
    """(d) a window of 128 with a sink and a run of 150 lanes, at the
    cell's head widths (K heads of 192 beside V heads of 128: each head's
    product runs over the 128-aligned stretch of the merged row that holds
    it), through the dispatcher as the mixed step calls it."""
    T, n_q, kv, dk, dv, bs, width = 160, 8, 2, 192, 128, 16, 16
    lanes = [(0, 200), (1, 17)] + _run(2, 61, 150)
    tables, pos, rows = _gqa_pack(lanes, T, width)
    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.normal(size=(T, n_q, dk)), dtype)
    k = jnp.asarray(rng.normal(size=(4 * width + 1, bs, kv * dk)), dtype)
    v = jnp.asarray(rng.normal(size=(4 * width + 1, bs, kv * dv)), dtype)
    sk = jnp.asarray(rng.normal(size=(n_q,)), jnp.float32)
    monkeypatch.setattr(paged_kv, "_kernel_applies", lambda *a: True)
    got = paged_kv.paged_attention_decode(
        q, k, v, jnp.asarray(tables), jnp.asarray(pos), window=128, sink=sk,
        rows=jnp.asarray(rows))
    want = paged_kv.paged_attention_decode_plain(
        q, k, v, jnp.asarray(tables), jnp.asarray(pos), None, 128, sk)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32)[:len(lanes)],
                               np.asarray(want, np.float32)[:len(lanes)],
                               atol=tol)


def test_gqa_with_no_shared_row_is_the_per_lane_kernel_bit_for_bit():
    """(e) told that no two neighbouring lanes share a row, the tiled entry
    runs the per-lane kernel over every lane: the same bits."""
    from paddle_tpu.ops.pallas.paged_attention import paged_attention_gqa

    rng = np.random.default_rng(1)
    T, n_q, kv, dk, dv, bs, width, nb = 6, 8, 2, 24, 16, 8, 6, 40
    q = jnp.asarray(rng.normal(size=(T, n_q, dk)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(nb, bs, kv * dk)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(nb, bs, kv * dv)), jnp.bfloat16)
    tables = jnp.asarray(rng.permutation(np.arange(1, nb))[:T * width]
                         .reshape(T, width), jnp.int32)
    pos = jnp.asarray([0, 7, 8, 23, 40, 47], jnp.int32)
    sk = jnp.asarray(rng.normal(size=(n_q,)), jnp.float32)
    want = paged_attention_gqa(q, k, v, tables, pos, None, 9, sk)
    got = paged_attention_gqa(q, k, v, tables, pos, None, 9, sk,
                              rows=jnp.arange(T, dtype=jnp.int32))
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def test_kernel_applies_reads_flat_pools_from_their_shapes(monkeypatch):
    class Dev:
        platform = "tpu"

    monkeypatch.setattr(jax, "devices", lambda *a: [Dev()])
    q = jnp.zeros((4, 64, 192), jnp.bfloat16)
    k = jnp.zeros((9, 64, 4 * 192), jnp.bfloat16)
    v = jnp.zeros((9, 64, 4 * 128), jnp.bfloat16)
    assert paged_kv._kernel_applies(q, k, v)                  # 768 and 512 lanes
    assert paged_kv._kernel_applies(q, jnp.zeros((9, 64, 8 * 192), jnp.bfloat16),
                                    jnp.zeros((9, 64, 8 * 128), jnp.bfloat16))
    assert not paged_kv._kernel_applies(q[..., :24], k[..., :48], v[..., :32])
    assert not paged_kv._kernel_applies(q, k[:, :8], v[:, :8])   # block of 8
    assert not paged_kv._kernel_applies(q, k.astype(jnp.int8), v)
