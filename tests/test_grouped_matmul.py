"""The grouped matmul of the held experts (ops/pallas/grouped_matmul.py), in
interpret mode on the CPU: the kernels against ``jax.lax.ragged_dot`` over
awkward group sizes, ``held_experts_mlp`` with the kernels forced against the
benchmark's plain reference, the rule that picks the path, and the count of
the row tiles' rows, from the expert layer up to the serving engine's
counter."""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "benchmarks")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import common  # noqa: E402  (benchmarks/)
from builders import mimo_v2_flash as B  # noqa: E402
from reference import mimo_v2_flash as R  # noqa: E402

from paddle_tpu.incubate.distributed.models.moe import held_experts  # noqa: E402
from paddle_tpu.models.serving import ContinuousBatchingEngine  # noqa: E402
from paddle_tpu.ops.pallas import grouped_matmul as gmm  # noqa: E402

K, N = 256, 384

# group sizes a held expert, and the sorted rows there are (the rest lie
# behind the groups' total, as pairs on absent experts do)
GROUPS = {
    "an-expert-with-no-row": ([3, 0, 5, 7], 40),
    "all-rows-on-one-expert": ([0, 0, 48, 0], 48),
    "shorter-and-longer-than-a-tile": ([1, 37, 15, 17], 96),
    "whole-tiles": ([16, 32, 0, 16], 64),
    "no-row-at-all": ([0, 0, 0], 24),
    "one-expert": ([9], 16),
}


def _operands(dtype, sizes, rows, seed=0):
    rng = np.random.default_rng(seed)
    xs = jnp.asarray(rng.normal(size=(rows, K)), dtype)
    w1, w3 = (jnp.asarray(rng.normal(size=(len(sizes), K, N)) * 0.05, dtype)
              for _ in range(2))
    return xs, w1, w3, jnp.asarray(sizes, jnp.int32)


def _padded(xs, sizes, tm, fill=0.0):
    """``xs`` in the padded layout of ``plan_row_tiles`` (rows no group owns
    hold ``fill``), the plan, which padded rows are real, and the sorted row
    each came from."""
    rows = xs.shape[0]
    plan = gmm.plan_row_tiles(sizes, tm, rows)
    at = np.arange(gmm.padded_rows(rows, sizes.shape[0], tm))
    real = at % tm < np.asarray(plan["n"])[at // tm]
    src = np.minimum(np.asarray(plan["row0"])[at // tm] + at % tm, rows - 1)
    xp = jnp.where(jnp.asarray(real)[:, None], xs[src], fill).astype(xs.dtype)
    return xp, plan, real, src


def _close(got, want, dtype):
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol,
                               rtol=tol)


def _own(rows):
    """Each sorted row its own token, at weight 1: ``gmm_down`` then leaves
    every sorted row's product in that row."""
    return jnp.arange(rows, dtype=jnp.int32), jnp.ones(rows, jnp.float32)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("case", list(GROUPS))
def test_plain_product_against_ragged_dot(case, dtype):
    """``gmm_down`` (one streamed matrix), each sorted row its own token at
    weight 1: every row a group owns is what ``ragged_dot`` gives for it,
    from the float32 accumulator; the rows behind the groups are zero."""
    sizes, rows = GROUPS[case]
    xs, w1, _w3, sizes = _operands(dtype, sizes, rows)
    xp, plan, real, src = _padded(xs, sizes, gmm.row_tile(dtype))
    got = gmm.gmm_down(xp, w1, plan, *_own(rows), rows)
    assert got.dtype == jnp.float32 and got.shape == (rows, N)
    assert int(plan["tiles"]) == sum(-(-s // gmm.row_tile(dtype))
                                     for s in GROUPS[case][0])
    want = lax.ragged_dot(xs, w1, sizes, preferred_element_type=jnp.float32)
    total = sum(GROUPS[case][0])
    assert real.sum() == total
    _close(got[:total], np.asarray(want)[:total], dtype)
    assert not np.asarray(got[total:]).any()


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("case", list(GROUPS))
def test_fused_swiglu_against_silu_of_ragged_dots(case, dtype):
    """``gmm_up``, each sorted row read from ``h`` at its own token: gate and
    up side by side, ``silu(a) * b`` on the float32 accumulators."""
    sizes, rows = GROUPS[case]
    xs, w1, w3, sizes = _operands(dtype, sizes, rows, seed=1)
    _, plan, real, src = _padded(xs, sizes, gmm.row_tile(dtype))
    got = gmm.gmm_up(xs, w1, w3, plan, _own(rows)[0])
    a = lax.ragged_dot(xs, w1, sizes, preferred_element_type=jnp.float32)
    b = lax.ragged_dot(xs, w3, sizes, preferred_element_type=jnp.float32)
    want = jax.nn.silu(a) * b
    _close(np.asarray(got, np.float32)[real], np.asarray(want)[src[real]],
           dtype)


@pytest.mark.parametrize("panel_bytes,tn", [(2 ** 20, 384), (2 ** 18, 128),
                                            (1, 128)])
def test_every_panel_width_gives_the_same_product(monkeypatch, panel_bytes,
                                                  tn):
    """One panel for the whole width, three, and the 128 lanes a panel has at
    least: the panel's width is no part of the result."""
    sizes, rows = GROUPS["shorter-and-longer-than-a-tile"]
    xs, w1, w3, sizes = _operands(jnp.float32, sizes, rows, seed=2)
    xp, plan, real, _ = _padded(xs, sizes, 8)
    token, ones = _own(rows)
    want = np.asarray(gmm.gmm_up(xs, w1, w3, plan, token))[real]
    want_down = np.asarray(gmm.gmm_down(xp, w1, plan, token, ones, rows))
    monkeypatch.setattr(gmm, "_PANEL_BYTES", panel_bytes)
    assert gmm._panel(K, N, jnp.float32) == tn
    np.testing.assert_allclose(
        np.asarray(gmm.gmm_up(xs, w1, w3, plan, token))[real], want,
        atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(gmm.gmm_down(xp, w1, plan, token, ones, rows)), want_down,
        atol=1e-5, rtol=1e-5)


def test_rows_no_group_owns_change_no_counted_row():
    """The padding of a tile, everything behind the last tile in use, and
    the rows of ``h`` that no real row names may hold anything (NaN here):
    a row is its own product, and a padding row is neither read nor
    added."""
    sizes, rows = GROUPS["shorter-and-longer-than-a-tile"]
    xs, w1, w3, sizes = _operands(jnp.bfloat16, sizes, rows, seed=3)
    clean, plan, real, _ = _padded(xs, sizes, 16)
    dirty, _, _, _ = _padded(xs, sizes, 16, fill=np.nan)
    assert np.isnan(np.asarray(dirty, np.float32)).any()
    token, ones = _own(rows)
    total = sum(GROUPS["shorter-and-longer-than-a-tile"][0])
    h_dirty = xs.at[total:].set(np.nan)
    got = np.asarray(gmm.gmm_up(h_dirty, w1, w3, plan, token), np.float32)
    np.testing.assert_array_equal(
        got[real], np.asarray(gmm.gmm_up(xs, w1, w3, plan, token),
                              np.float32)[real])
    assert np.isfinite(got[real]).all()
    got = np.asarray(gmm.gmm_down(dirty, w1, plan, token, ones, rows))
    np.testing.assert_array_equal(
        got, np.asarray(gmm.gmm_down(clean, w1, plan, token, ones, rows)))
    assert np.isfinite(got).all()


# -- rows in by token, out by token: the kernels against the layer's formula --
def _formula(h, w1, w3, w2, sizes, token, weight):
    """What the expert layer computed around ``ragged_dot`` before the rows
    moved into the kernels: the sorted rows gathered from ``h`` by token,
    the three grouped products, each row weighted from float32 (rows behind
    the groups dropped) and scatter-added into its token's row."""
    xs = h[token]
    a = lax.ragged_dot(xs, w1, sizes, preferred_element_type=jnp.float32)
    b = lax.ragged_dot(xs, w3, sizes, preferred_element_type=jnp.float32)
    y = lax.ragged_dot((jax.nn.silu(a) * b).astype(h.dtype), w2, sizes,
                       preferred_element_type=jnp.float32)
    w = jnp.where(jnp.arange(token.shape[0]) < sizes.sum(), weight, 0.0)
    y = jnp.where((w > 0)[:, None], y * w[:, None], 0.0)
    return jnp.zeros((h.shape[0], w2.shape[2]), jnp.float32).at[token].add(y)


def _routed(dtype, case, top_k=4, seed=4):
    """A case's sorted rows as pairs of ``top_k`` a token: each sorted row a
    token (a permutation of the pairs), a routing weight, ``h`` and the
    three matrices."""
    sizes, rows = GROUPS[case]
    rng = np.random.default_rng(seed)
    T = rows // top_k
    token = jnp.asarray(rng.permutation(rows) // top_k, jnp.int32)
    weight = jnp.asarray(rng.uniform(0.05, 1.0, rows), jnp.float32)
    h = jnp.asarray(rng.normal(size=(T, K)), dtype)
    w1, w3 = (jnp.asarray(rng.normal(size=(len(sizes), K, N)) * 0.05, dtype)
              for _ in range(2))
    w2 = jnp.asarray(rng.normal(size=(len(sizes), N, K)) * 0.05, dtype)
    return h, w1, w3, w2, jnp.asarray(sizes, jnp.int32), token, weight


def _by_token(h, w1, w3, w2, sizes, token, weight):
    plan = gmm.plan_row_tiles(sizes, gmm.row_tile(h.dtype), token.shape[0])
    return gmm.gmm_down(gmm.gmm_up(h, w1, w3, plan, token), w2, plan, token,
                        weight, h.shape[0])


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("case", list(GROUPS))
def test_rows_in_and_out_by_token_against_the_formula(case, dtype):
    """``gmm_up`` reading each real row from ``h`` by its token and
    ``gmm_down`` adding its weighted product into its token's row give what
    the layer's formula gives around ``ragged_dot``; a step whose groups are
    empty gives exact zeros."""
    ops = _routed(dtype, case)
    got = _by_token(*ops)
    assert got.dtype == jnp.float32 and got.shape == (ops[0].shape[0], K)
    _close(got, _formula(*ops), dtype)
    if not sum(GROUPS[case][0]):
        assert not np.asarray(got).any()


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_nan_in_rows_no_tile_in_use_reads_reaches_no_output(dtype):
    """NaN in the rows of ``h`` whose tokens have no pair in a group (the
    sorted rows behind the groups name them, and the padding of the tiles
    does too): the output equals the clean one's, and those tokens' rows are
    zero."""
    h, w1, w3, w2, sizes, token, weight = _routed(
        dtype, "shorter-and-longer-than-a-tile")
    total = int(sizes.sum())
    # the tokens of the sorted rows behind the groups own no row in a group
    token = jnp.concatenate([token[:total] % 8,
                             8 + jnp.arange(token.shape[0] - total) % 16])
    read = np.zeros(h.shape[0], bool)
    read[np.asarray(token[:total])] = True
    dirty = jnp.where(jnp.asarray(read)[:, None], h, np.nan).astype(dtype)
    got = np.asarray(_by_token(dirty, w1, w3, w2, sizes, token, weight))
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(
        got, np.asarray(_by_token(h, w1, w3, w2, sizes, token, weight)))
    assert not got[~read].any()
    _close(got, _formula(h, w1, w3, w2, sizes, token, weight), dtype)


def test_the_plan_at_one_row_a_tile_is_the_sorted_rows():
    """What ``ragged_dot`` is handed: tiles of one row, no padding."""
    sizes = jnp.asarray([3, 0, 5], jnp.int32)
    plan = gmm.plan_row_tiles(sizes, 1, 12)
    assert gmm.padded_rows(12, 3, 1) == 12
    np.testing.assert_array_equal(plan["row0"][:8], np.arange(8))
    np.testing.assert_array_equal(plan["n"], [1] * 8 + [0] * 4)
    np.testing.assert_array_equal(plan["expert"][:8], [0] * 3 + [2] * 5)
    assert int(plan["tiles"]) == 8


# -- the expert layer with the kernels forced ----------------------------------
def _layer(rng, hdim, width, routed, dtype=jnp.float32):
    p = {"mlp.gate.weight": rng.normal(size=(hdim, routed)) * 0.3,
         "mlp.gate.e_score_correction_bias": rng.normal(size=(routed,)) * 0.1,
         "mlp.experts.gate_proj": rng.normal(size=(routed, hdim, width)) * 0.1,
         "mlp.experts.up_proj": rng.normal(size=(routed, hdim, width)) * 0.1,
         "mlp.experts.down_proj": rng.normal(size=(routed, width, hdim)) * 0.1}
    return {k: jnp.asarray(v, dtype) for k, v in p.items()}


def _share(h, p, lo, held, top, valid=None):
    return held_experts.held_experts_mlp(
        h, p["mlp.gate.weight"], p["mlp.gate.e_score_correction_bias"],
        *(p["mlp.experts." + w][lo:lo + held]
          for w in ("gate_proj", "up_proj", "down_proj")), lo, top, valid)


def test_held_experts_through_the_kernels_against_the_reference(monkeypatch):
    """The existing shares test's sizes scaled to whole 128-lane rows (hidden
    128, width 256), the kernels forced (interpret mode) and ``lo`` traced:
    four shares of 4 experts add up to the reference's whole layer, each is
    the reference's own share, and the fourth count is the rows of the row
    tiles: every held expert's pairs rounded up to whole tiles of 8."""
    monkeypatch.setattr(held_experts, "_kernel_applies", lambda *a: True)
    rng = np.random.default_rng(3)
    hdim, width, routed, top = 128, 256, 16, 4
    h = jnp.asarray(rng.normal(size=(40, hdim)), jnp.float32)
    p = _layer(rng, hdim, width, routed)
    cfg = {"num_experts_per_tok": top, "n_routed_experts": routed}
    whole = R.experts(h, p, cfg, None)
    traced = jax.jit(lambda h, w, lo: held_experts.held_experts_mlp(
        h, p["mlp.gate.weight"], p["mlp.gate.e_score_correction_bias"], *w,
        lo, top))
    chosen = np.asarray(held_experts.route_sigmoid_topk(
        h, p["mlp.gate.weight"], p["mlp.gate.e_score_correction_bias"],
        top)[0])
    parts = []
    for lo in range(0, routed, 4):
        w = tuple(p["mlp.experts." + n][lo:lo + 4]
                  for n in ("gate_proj", "up_proj", "down_proj"))
        y, n = traced(h, w, jnp.int32(lo))
        parts.append(y)
        sizes = [(chosen == e).sum() for e in range(lo, lo + 4)]
        assert np.asarray(n).tolist() == [
            sum(sizes), 40 * top, sum(s > 0 for s in sizes),
            sum(-(-s // 8) * 8 for s in sizes)]
    np.testing.assert_allclose(sum(parts), whole, atol=5e-5)
    first = {k: (v[:4] if k.startswith("mlp.experts") else v)
             for k, v in p.items()}
    np.testing.assert_allclose(
        parts[0], R.experts(h, first, dict(cfg, n_routed_experts=4), None),
        atol=5e-5)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_kernels_and_ragged_dot_give_one_layer(monkeypatch, dtype):
    """Either path over the same tokens, ``valid`` marking some: the same
    sum per token (the fused SwiGLU is float32 where the other path rounds
    its two factors first), the same three counts, and ``kernel_rows``: every
    lane's pairs on held experts where ``ragged_dot`` ran (tiles of one
    row), each expert's rounded up to whole row tiles for the kernels."""
    rng = np.random.default_rng(5)
    h = jnp.asarray(rng.normal(size=(24, 128)), dtype)
    p = _layer(rng, 128, 128, 8, dtype)
    valid = jnp.asarray(rng.random(24) < 0.7)
    want, n_plain = _share(h, p, 2, 4, 3, valid)
    monkeypatch.setattr(held_experts, "_kernel_applies", lambda *a: True)
    got, n_kernel = _share(h, p, 2, 4, 3, valid)
    _close(got, want, dtype)
    np.testing.assert_array_equal(n_plain[:3], n_kernel[:3])
    chosen = np.asarray(held_experts.route_sigmoid_topk(
        h, p["mlp.gate.weight"], p["mlp.gate.e_score_correction_bias"], 3)[0])
    sizes = [int((chosen == e).sum()) for e in range(2, 6)]
    tm = gmm.row_tile(dtype)
    assert int(n_plain[3]) == sum(sizes) > int(n_plain[0])
    assert int(n_kernel[3]) == sum(-(-s // tm) * tm for s in sizes)


# (lo, held, a shift of the held experts' selection bias, which lanes are
# valid): the routing a step of the layer can see
STEPS = {
    "no-pair-on-a-held-expert": (2, 4, -50.0, None),
    "every-pair-on-held-experts": (0, 8, 0.0, None),
    "a-token-with-several-held-pairs": (2, 4, 50.0, None),
    "valid-masks-lanes": (2, 4, 0.0, "some"),
    "traced-lo": (None, 4, 0.0, None),
}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("step", list(STEPS))
def test_the_layer_by_token_against_its_formula(monkeypatch, step, dtype):
    """The layer with the kernels forced (rows in and out by token) against
    the same layer on ``ragged_dot`` (the sorted rows gathered, weighted and
    scatter-added): the same sum a token and the same three counts, whatever
    the routing: no pair on a held expert (the output exactly zero, no row
    visited), every pair on one (the static worst case), every token with
    all its pairs on held experts, lanes left out of the counts by
    ``valid``, and ``lo`` traced."""
    lo, held, shift, valid = STEPS[step]
    rng = np.random.default_rng(6)
    top, T = 3, 16
    h = jnp.asarray(rng.normal(size=(T, 128)), dtype)
    p = _layer(rng, 128, 128, 8, dtype)
    if valid:
        valid = jnp.asarray(rng.random(T) < 0.6)
    chosen = {}

    def run(kernel, at):
        monkeypatch.setattr(held_experts, "_kernel_applies",
                            lambda *a: kernel)
        bias = p["mlp.gate.e_score_correction_bias"].at[at:at + held].add(
            shift)
        chosen[at] = np.asarray(held_experts.route_sigmoid_topk(
            h, p["mlp.gate.weight"], bias, top)[0])
        w = tuple(p["mlp.experts." + n][at:at + held]
                  for n in ("gate_proj", "up_proj", "down_proj"))
        layer = jax.jit(lambda h, w, lo: held_experts.held_experts_mlp(
            h, p["mlp.gate.weight"], bias, *w, lo, top, valid))
        return layer(h, w, jnp.int32(at))

    for at in ([lo] if lo is not None else [0, 2, 4]):
        want, n_want = run(False, at)
        got, n_got = run(True, at)
        _close(got, want, dtype)
        np.testing.assert_array_equal(n_got[:3], n_want[:3])
        here = (chosen[at] >= at) & (chosen[at] < at + held)
        assert int(n_got[0]) == (here & (np.asarray(valid)[:, None]
                                         if valid is not None else True)).sum()
        if step == "no-pair-on-a-held-expert":
            assert not here.any() and not np.asarray(got).any()
            assert np.asarray(n_got).tolist() == [0, T * top, 0, 0]
        if step == "every-pair-on-held-experts":
            assert here.all() and int(n_got[0]) == T * top
        if step == "a-token-with-several-held-pairs":
            assert (here.sum(1) == top).all()
        if step == "valid-masks-lanes":
            assert 0 < int(n_got[1]) < T * top


def test_the_rule_reads_platform_dtypes_and_widths(monkeypatch):
    """The kernels on a TPU at lane-aligned widths in one dtype, bfloat16 or
    float32; ``ragged_dot`` everywhere else. Nothing but the inputs is
    read."""
    h = jnp.zeros((8, 256), jnp.bfloat16)
    w1 = jnp.zeros((4, 256, 128), jnp.bfloat16)
    w2 = jnp.zeros((4, 128, 256), jnp.bfloat16)
    assert not held_experts._kernel_applies(h, w1, w2)          # the CPU

    class Dev:
        platform = "tpu"

    monkeypatch.setattr(jax, "devices", lambda *a: [Dev()])
    f32 = [a.astype(jnp.float32) for a in (h, w1, w2)]
    assert held_experts._kernel_applies(h, w1, w2)
    assert held_experts._kernel_applies(*f32)
    assert held_experts._kernel_applies(h[:1], w1, w2)           # any rows
    # ... while the tokens' float32 activations and output stay in VMEM
    assert not held_experts._kernel_applies(
        jax.ShapeDtypeStruct((65536, 256), jnp.bfloat16), w1, w2)
    assert not held_experts._kernel_applies(h, f32[1], w2)       # mixed
    assert not held_experts._kernel_applies(
        *(a.astype(jnp.float16) for a in (h, w1, w2)))
    assert not held_experts._kernel_applies(                     # width 64
        h, w1[:, :, :64], w2[:, :64])
    assert not held_experts._kernel_applies(                     # hidden 192
        h[:, :192], w1[:, :192], w2[:, :, :192])
    # a contraction too long for a panel of 128 lanes to fit the VMEM twice
    long = jax.ShapeDtypeStruct((4, 128 * 1024, 128), jnp.float32)
    assert not held_experts._kernel_applies(
        jax.ShapeDtypeStruct((8, 128 * 1024), jnp.float32), long,
        jax.ShapeDtypeStruct((4, 128, 128 * 1024), jnp.float32))


# -- up to the engine's counter --------------------------------------------------
CFG = dict(
    vocab_size=64, hidden_size=128, intermediate_size=128, num_hidden_layers=3,
    num_attention_heads=4, num_key_value_heads=1, head_dim=24, v_head_dim=16,
    swa_num_key_value_heads=2, swa_head_dim=24, swa_v_head_dim=16,
    sliding_window=20, hybrid_layer_pattern=[0, 1, 0],
    moe_layer_freq=[0, 1, 1], rope_theta=5e6, swa_rope_theta=1e4,
    partial_rotary_factor=0.334, attention_value_scale=0.707,
    add_swa_attention_sink_bias=True, add_full_attention_sink_bias=False,
    moe_intermediate_size=128, n_routed_experts=4,
    published={"n_routed_experts": 8}, num_experts_per_tok=2,
    layernorm_epsilon=1e-5, max_position_embeddings=128,
    initializer_range=0.1, model={"dtype": "float32"},
    engine=dict(max_batch=2, block_size=8, chunk_size=8, max_len=64,
                prefix_cache=False))
NAME = "paddle_tpu_serving_expert_pairs_total"


@pytest.fixture(scope="module")
def model():
    m = B.construct(CFG)
    common.load_weights(m, B.weights(11, CFG, "float32"))
    m.eval()
    return m


def _serve(model, prompts):
    """Greedy tokens a request, the kinds of step that ran, and how far each
    series of the experts' counter moved in each kind of step."""
    from paddle_tpu import monitor

    eng = ContinuousBatchingEngine(model, **CFG["engine"])
    rids = [eng.submit(p, max_new_tokens=m) for p, m in prompts]
    out, moved = {}, {}
    monitor.enable()
    try:
        before = dict(monitor.snapshot()["metrics"][NAME]["values"])
        while eng.num_active or eng.num_pending:
            for rid, toks in eng.step():
                out[rid] = toks
            after = dict(monitor.snapshot()["metrics"][NAME]["values"])
            into = moved.setdefault(eng._step_kind, {})
            for key, n in after.items():
                into[key] = into.get(key, 0.0) + n - before.get(key, 0.0)
            before = after
    finally:
        monitor.disable()
    return [np.asarray(out[r]) for r in rids], moved


def test_both_serving_programs_carry_kernel_rows_into_the_counter(
        monkeypatch, model):
    """A tiny lane-aligned expert model through the engine: with
    ``ragged_dot`` the series ``where=kernel_rows`` moves by the pairs in the
    groups (tiles of one row: at least the held pairs of the valid lanes);
    with the kernels forced (interpret mode) the same greedy tokens come
    back, the mixed steps and the bursts both move it, by whole row tiles of
    8 that cover at least as many rows, and the other series as before."""
    rng = np.random.default_rng(0)
    prompts = [(rng.integers(0, CFG["vocab_size"], n, dtype=np.int32), m)
               for n, m in [(13, 9), (5, 12)]]
    want, plain = _serve(model, prompts)
    assert set(plain) == {"mixed", "burst"}
    monkeypatch.setattr(held_experts, "_kernel_applies", lambda *a: True)
    got, moved = _serve(model, prompts)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    for kind in ("mixed", "burst"):
        rows, one_row = (m[kind]["where=kernel_rows"] for m in (moved, plain))
        # the tiles hold every lane's pairs; ``held`` is over valid lanes
        assert one_row >= plain[kind]["where=held"] > 0
        assert rows > one_row and rows % 8 == 0
        for where in ("held", "routed", "experts_hit", "expert_calls"):
            assert moved[kind]["where=" + where] == \
                plain[kind]["where=" + where]
