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


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("case", list(GROUPS))
def test_plain_product_against_ragged_dot(case, dtype):
    """``gmm_down`` (one streamed matrix): every row a group owns is what
    ``ragged_dot`` gives for it, in the activations' dtype."""
    sizes, rows = GROUPS[case]
    xs, w1, _w3, sizes = _operands(dtype, sizes, rows)
    xp, plan, real, src = _padded(xs, sizes, gmm.row_tile(dtype))
    got = gmm.gmm_down(xp, w1, plan)
    assert got.dtype == dtype and got.shape == (xp.shape[0], N)
    assert int(plan["tiles"]) == sum(-(-s // gmm.row_tile(dtype))
                                     for s in GROUPS[case][0])
    want = lax.ragged_dot(xs, w1, sizes, preferred_element_type=jnp.float32)
    assert real.sum() == sum(GROUPS[case][0])
    _close(np.asarray(got, np.float32)[real], np.asarray(want)[src[real]],
           dtype)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("case", list(GROUPS))
def test_fused_swiglu_against_silu_of_ragged_dots(case, dtype):
    """``gmm_up``: gate and up side by side, ``silu(a) * b`` on the float32
    accumulators."""
    sizes, rows = GROUPS[case]
    xs, w1, w3, sizes = _operands(dtype, sizes, rows, seed=1)
    xp, plan, real, src = _padded(xs, sizes, gmm.row_tile(dtype))
    got = gmm.gmm_up(xp, w1, w3, plan)
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
    want = np.asarray(gmm.gmm_up(xp, w1, w3, plan))[real]
    want_down = np.asarray(gmm.gmm_down(xp, w1, plan))[real]
    monkeypatch.setattr(gmm, "_PANEL_BYTES", panel_bytes)
    assert gmm._panel(K, N, jnp.float32) == tn
    np.testing.assert_allclose(
        np.asarray(gmm.gmm_up(xp, w1, w3, plan))[real], want, atol=1e-5,
        rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(gmm.gmm_down(xp, w1, plan))[real], want_down, atol=1e-5,
        rtol=1e-5)


def test_rows_no_group_owns_change_no_counted_row():
    """The padding of a tile and everything behind the last tile in use may
    hold anything (NaN here): a row is its own product."""
    sizes, rows = GROUPS["shorter-and-longer-than-a-tile"]
    xs, w1, w3, sizes = _operands(jnp.bfloat16, sizes, rows, seed=3)
    clean, plan, real, _ = _padded(xs, sizes, 16)
    dirty, _, _, _ = _padded(xs, sizes, 16, fill=np.nan)
    assert np.isnan(np.asarray(dirty, np.float32)).any()
    for run in (lambda x: gmm.gmm_up(x, w1, w3, plan),
                lambda x: gmm.gmm_down(x, w1, plan)):
        got = np.asarray(run(dirty), np.float32)[real]
        np.testing.assert_array_equal(
            got, np.asarray(run(clean), np.float32)[real])
        assert np.isfinite(got).all()


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
