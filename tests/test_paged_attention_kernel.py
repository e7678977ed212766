"""The paged-attention Pallas kernel against the plain gather path (ISSUE 30).

On the CPU the kernel runs in Pallas interpret mode: the same kernel code the
TPU compiles (tests/test_tpu_compile.py compiles it for a described v5e),
walking each lane's own block-table row up to its own position. Contracts:

1. same meaning as ``paged_kv.paged_attention_decode_plain`` — lane ``t``
   attends to positions ``0..positions[t]`` inclusive of its row — to 1e-5
   on float32 pools and to bfloat16's last bits on bfloat16 pools, for MHA
   and GQA, at the lengths where the block walk turns (0, block - 1, block,
   the full table), for lanes of one chunk sharing a row, for padding lanes
   on the null block, for rows sharing a ref-counted block, with a
   non-default scale, and inside ``lax.scan`` as the decode burst calls it;
2. ``paged_attention_decode`` picks the path from its inputs alone, and a
   ``ContinuousBatchingEngine`` gives the same greedy tokens through either
   over mixed steps and bursts.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models import paged_kv
from paddle_tpu.ops.pallas.paged_attention import paged_attention

BS, WIDTH, D = 16, 4, 128          # block size, table width, head dim
FULL = BS * WIDTH - 1


def _pools(rng, n_kv, dtype, nb=12):
    shape = (nb, BS, n_kv, D)
    return (jnp.asarray(rng.standard_normal(shape), dtype),
            jnp.asarray(rng.standard_normal(shape), dtype))


def _rows(rng, n, nb=12):
    return np.stack([rng.permutation(np.arange(1, nb))[:WIDTH]
                     for _ in range(n)]).astype(np.int32)


def _lengths(rng):
    """One lane at each length where the walk over the blocks turns."""
    pos = np.array([0, BS - 1, BS, BS + 1, 2 * BS - 1, 2 * BS, FULL],
                   np.int32)
    return _rows(rng, len(pos)), pos


def _chunk(rng):
    """Lanes of one prefill chunk: ONE table row at consecutive positions
    that cross a block boundary, behind two decode lanes."""
    tables = _rows(rng, 3)
    tables = np.concatenate([tables[:2], np.repeat(tables[2:], 10, 0)])
    pos = np.concatenate([[37, 5], BS - 4 + np.arange(10)]).astype(np.int32)
    return tables, pos


def _padding(rng):
    """Padding lanes as the engine packs them: position 0 on a row of null
    blocks; their result is ignored, the others' must not move."""
    tables = _rows(rng, 4)
    tables[2:] = 0
    return tables, np.array([FULL - 3, 20, 0, 0], np.int32)


def _shared(rng):
    """Two rows map the same (ref-counted) first block: a prefix-cache hit
    or a beam fork. The kernel only reads it."""
    tables = _rows(rng, 3)
    tables[1, 0] = tables[0, 0]
    tables[2, :2] = tables[0, :2]
    return tables, np.array([BS + 3, 9, 3 * BS], np.int32)


CASES = {"lengths": _lengths, "chunk": _chunk, "padding": _padding,
         "shared": _shared}
HEADS = {"mha": (4, 4), "gqa4to1": (8, 2)}
TOL = {jnp.float32: 1e-5, jnp.bfloat16: 2e-2}


LANES = 12          # every case is padded to one shape: one compile each
_KERNEL = jax.jit(paged_attention, static_argnames=("scale",))


def _compare(q, k, v, tables, pos, dtype, n, **kw):
    got = _KERNEL(q, k, v, tables, pos, **kw)
    want = paged_kv.paged_attention_decode_plain(q, k, v, tables, pos, **kw)
    assert got.dtype == q.dtype and got.shape == q.shape
    np.testing.assert_allclose(np.asarray(got, np.float32)[:n],
                               np.asarray(want, np.float32)[:n],
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("heads", sorted(HEADS))
@pytest.mark.parametrize("case", sorted(CASES) + ["scale", "scan"])
def test_kernel_matches_the_plain_path(case, heads, dtype):
    rng = np.random.default_rng(sorted(CASES).index(case)
                                if case in CASES else 7)
    n_q, n_kv = HEADS[heads]
    k, v = _pools(rng, n_kv, dtype)
    tables, pos = CASES.get(case, _lengths)(rng)
    n = 2 if case == "padding" else len(pos)     # lanes whose result counts
    pad = LANES - len(pos)                       # the rest: null-block lanes
    tables = jnp.asarray(np.pad(tables, ((0, pad), (0, 0))))
    pos = jnp.asarray(np.pad(pos, (0, pad)))
    q = jnp.asarray(rng.standard_normal((LANES, n_q, D)), dtype)
    if case == "scale":
        _compare(q, k, v, tables, pos, dtype, n, scale=0.25)
    elif case == "scan":
        # the decode burst: the call inside lax.scan, positions advancing
        def burst(attn):
            def body(lens, _):
                return lens + 1, attn(q, k, v, tables, lens)
            return jax.jit(lambda: jax.lax.scan(
                body, jnp.minimum(pos, FULL - 3), None, length=3)[1])()
        np.testing.assert_allclose(
            np.asarray(burst(paged_attention), np.float32)[:, :n],
            np.asarray(burst(paged_kv.paged_attention_decode_plain),
                       np.float32)[:, :n], atol=TOL[dtype], rtol=TOL[dtype])
    else:
        _compare(q, k, v, tables, pos, dtype, n)


def test_kernel_reads_no_block_past_the_lanes_position():
    """Blocks past ``position // block_size`` hold NaN: a kernel that read
    them (the plain path masks them AFTER the gather) would return NaN."""
    rng = np.random.default_rng(3)
    k, v = _pools(rng, 2, jnp.float32, nb=13)
    tables = rng.permutation(np.arange(1, 13)).reshape(3, WIDTH)  # no sharing
    pos = np.array([BS - 1, BS, 5], np.int32)
    for row, p in zip(tables, pos):
        for blk in row[p // BS + 1:]:
            k = k.at[blk].set(jnp.nan)
            v = v.at[blk].set(jnp.nan)
    q = jnp.asarray(rng.standard_normal((3, 4, D)), jnp.float32)
    out = paged_attention(q, k, v, jnp.asarray(tables), jnp.asarray(pos))
    assert np.isfinite(np.asarray(out)).all()


def test_dispatch_reads_its_inputs_and_keeps_the_plain_path_on_the_cpu(
        monkeypatch):
    rng = np.random.default_rng(4)
    k, v = _pools(rng, 2, jnp.float32)
    tables, pos = (jnp.asarray(a) for a in _lengths(rng))
    q = jnp.asarray(rng.standard_normal((len(pos), 4, D)), jnp.float32)
    assert not paged_kv._kernel_applies(q, k)            # the CPU: plain
    plain = paged_kv.paged_attention_decode(q, k, v, tables, pos)
    np.testing.assert_array_equal(
        np.asarray(plain),
        np.asarray(paged_kv.paged_attention_decode_plain(q, k, v, tables,
                                                         pos)))
    # what the predicate looks at on a TPU: dtypes and the head dim
    monkeypatch.setattr(jax, "devices",
                        lambda *a: [type("D", (), {"platform": "tpu"})()])
    assert paged_kv._kernel_applies(q, k)
    assert paged_kv._kernel_applies(q.astype(jnp.bfloat16),
                                    k.astype(jnp.bfloat16))
    assert not paged_kv._kernel_applies(q[..., :64], k[..., :64])
    assert not paged_kv._kernel_applies(q, k.astype(jnp.int8))
    assert not paged_kv._kernel_applies(q.astype(jnp.float16), k)
    big = jax.ShapeDtypeStruct((9, 256, 64, 128), jnp.bfloat16)   # 4 MiB
    assert not paged_kv._kernel_applies(q, big)       # a block: 16 in VMEM


def _engine():
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.serving import ContinuousBatchingEngine

    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=96, hidden_size=256, intermediate_size=128,
                      num_hidden_layers=2, num_attention_heads=2,
                      num_key_value_heads=1, max_position_embeddings=64)
    return ContinuousBatchingEngine(LlamaForCausalLM(cfg), max_batch=3,
                                    max_len=48, block_size=8, chunk_size=8,
                                    decode_burst=3)


def _serve(eng):
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, 96, n).astype(np.int32)
               for n in (5, 19, 11, 3, 26)]
    rids = [eng.submit(p, max_new_tokens=7 + i) for i, p in enumerate(prompts)]
    out, kinds = {}, set()
    while eng.num_active or eng.num_pending:
        for rid, tokens in eng.step():
            out[rid] = tokens
        kinds.add(eng._step_kind)
    return [out[r] for r in rids], kinds


def test_engine_gives_the_same_greedy_tokens_through_the_kernel(monkeypatch):
    """No switch chooses the path, so the test turns the dispatch's own
    predicate (as the ``mosaic`` fixture of test_tpu_compile.py turns
    ``_interpret``): head dim 128, GQA 2:1, chunked prefill of prompts
    longer than a chunk, bursts of 3, 5 requests through 3 slots."""
    from paddle_tpu import monitor

    want, kinds = _serve(_engine())
    assert kinds >= {"mixed", "burst"}
    monkeypatch.setattr(paged_kv, "_kernel_applies", lambda q, pool: True)
    lanes = "paddle_tpu_serving_attn_lanes_total"

    def served():
        monitor.enable()
        before = dict(monitor.snapshot()["metrics"][lanes]["values"])
        try:
            got, kinds = _serve(_engine())
        finally:
            monitor.disable()
        after = monitor.snapshot()["metrics"][lanes]["values"]
        assert kinds >= {"mixed", "burst"}
        return got, {k: after[k] - before.get(k, 0.0) for k in after}

    # most of the 64 prompt tokens ride query tiles (chunks of 4 lanes or
    # more); the 3-token prompt and the chunks the budget cuts shorter stay
    # per lane, as every decode lane does
    got, moved = served()
    assert got == want
    assert 48 <= moved["path=tiled"] < 64 - 3
    # (ISSUE 33) the same requests with every lane on a walk of its own: no
    # run is long enough to be a tile
    monkeypatch.setattr(pa_mod, "MIN_RUN", 10 ** 6)
    per_lane, moved_alone = served()
    assert per_lane == got
    assert moved_alone["path=tiled"] == 0
    assert moved_alone["path=lane"] == moved["path=lane"] + moved["path=tiled"]


# -- query tiles (ISSUE 33): lanes that share a table row walk it once ---------
from paddle_tpu.ops.pallas import paged_attention as pa_mod  # noqa: E402

TILE_LANES = 48


def _run(row, start, n):
    return [(row, start + i) for i in range(n)]


# (table row, position) a lane; rows 0 and 1 are decode lanes' own
TILE_CASES = {
    # (a) one run that starts mid-block and crosses two block boundaries
    "midblock-run": _run(2, 10, 30),
    # (b) decode lanes, two runs of different rows, padding lanes behind
    # (slot 0 at position 0, as the engine pads)
    "two-runs": [(0, 37), (1, 5)] + _run(2, 3, 12) + _run(3, 20, 10)
                + [(0, 0)] * 4,
    # (c) a run shorter than a tile, and one too short to be a tile at all
    "short-runs": [(0, 50)] + _run(2, 14, 5) + _run(3, 30, 3),
    # more lanes than one tile of the GQA case holds (32): two tiles
    "two-tiles": [(1, 9)] + _run(2, 17, 40),
    # a run up to the table's last position
    "to-the-end": _run(3, FULL - 20, 21) + [(0, 3)],
}


def _tile_inputs(case, n_q, n_kv, dtype, seed=5):
    rng = np.random.default_rng(seed)
    k, v = _pools(rng, n_kv, dtype, nb=20)
    row_tables = _rows(rng, 4, nb=20)
    lanes = TILE_CASES[case]
    pad = TILE_LANES - len(lanes)
    rows = np.array([r for r, _ in lanes] + [0] * pad, np.int32)
    pos = np.array([p for _, p in lanes] + [0] * pad, np.int32)
    q = jnp.asarray(rng.standard_normal((TILE_LANES, n_q, D)), dtype)
    return (q, k, v, jnp.asarray(row_tables[rows]), jnp.asarray(pos),
            jnp.asarray(rows), len(lanes))


_TILED = jax.jit(lambda q, k, v, t, p, r: paged_attention(q, k, v, t, p,
                                                          rows=r))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("heads", sorted(HEADS))
@pytest.mark.parametrize("case", sorted(TILE_CASES))
def test_tiles_match_the_plain_path(case, heads, dtype):
    n_q, n_kv = HEADS[heads]
    q, k, v, tables, pos, rows, n = _tile_inputs(case, n_q, n_kv, dtype)
    plan = pa_mod.plan_tiles(np.asarray(rows), np.asarray(pos),
                             pa_mod.tile_lanes(n_q // n_kv, False), np)
    assert plan["tiled"].sum() >= 5 and plan["tiles"] >= 1
    assert plan["tiles"] == (2 if (case, heads) == ("two-tiles", "gqa4to1")
                             or case == "two-runs" else 1)
    got = _TILED(q, k, v, tables, pos, rows)
    want = paged_kv.paged_attention_decode_plain(q, k, v, tables, pos)
    np.testing.assert_allclose(np.asarray(got, np.float32)[:n],
                               np.asarray(want, np.float32)[:n],
                               atol=TOL[dtype], rtol=TOL[dtype])
    # the lanes no tile serves: the per-lane walk's result, bit for bit
    alone = _KERNEL(q, k, v, tables, pos)
    left = ~plan["tiled"][:n]
    np.testing.assert_array_equal(np.asarray(got, np.float32)[:n][left],
                                  np.asarray(alone, np.float32)[:n][left])


@pytest.mark.parametrize("heads", sorted(HEADS))
def test_a_step_with_no_shared_row_is_the_per_lane_kernel_bit_for_bit(heads):
    """(e) told that no two lanes share a row (a burst, lockstep decode,
    decode lanes alone, two lanes of one row that are not neighbours in
    position), the tiled entry gives the per-lane kernel's bits."""
    n_q, n_kv = HEADS[heads]
    rng = np.random.default_rng(9)
    k, v = _pools(rng, n_kv, jnp.bfloat16)
    tables, pos = _lengths(rng)
    tables = np.concatenate([tables, tables[:2]])       # rows 0, 1 again
    pos = np.concatenate([pos, [pos[0] + 2, pos[1] + 5]]).astype(np.int32)
    rows = np.concatenate([np.arange(7), [0, 1]]).astype(np.int32)
    q = jnp.asarray(rng.standard_normal((len(pos), n_q, D)), jnp.bfloat16)
    plan = pa_mod.plan_tiles(rows, pos, pa_mod.tile_lanes(n_q // n_kv, False),
                             np)
    assert not plan["tiled"].any() and plan["tiles"] == 0
    got = paged_attention(q, k, v, jnp.asarray(tables), jnp.asarray(pos),
                          rows=jnp.asarray(rows))
    want = paged_attention(q, k, v, jnp.asarray(tables), jnp.asarray(pos))
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def test_a_tile_reads_no_block_past_its_last_lane():
    """Blocks past the run's last position hold NaN, and so does every
    block of the rows no lane of the step sits on."""
    q, k, v, tables, pos, rows, n = _tile_inputs("midblock-run", 4, 4,
                                                 jnp.float32)
    keep = set(np.asarray(tables)[0, :39 // BS + 1].tolist()) | {0}
    for blk in range(k.shape[0]):
        if blk not in keep:
            k = k.at[blk].set(jnp.nan)
            v = v.at[blk].set(jnp.nan)
    out = _TILED(q, k, v, tables, pos, rows)
    assert np.isfinite(np.asarray(out)[:n]).all()


def test_the_host_counts_what_the_device_plans():
    """``plan_tiles`` is one piece of code for the device (jnp) and the
    host's count (numpy): the same plan from both, and a tile's blocks
    counted once."""
    for case, lanes in TILE_CASES.items():
        rows = np.array([r for r, _ in lanes], np.int32)
        pos = np.array([p for _, p in lanes], np.int32)
        for tq in (32, 128):
            host = pa_mod.plan_tiles(rows, pos, tq, np)
            dev = pa_mod.plan_tiles(jnp.asarray(rows), jnp.asarray(pos), tq)
            for key, value in host.items():
                np.testing.assert_array_equal(value, np.asarray(dev[key]),
                                              err_msg=f"{case} {key}")
    rows, pos = (np.array(a, np.int32)
                 for a in zip(*TILE_CASES["midblock-run"]))
    plan = pa_mod.plan_tiles(rows, pos, 128, np)
    # positions 10..39 of one row: blocks 0, 1, 2 once, not 30 walks
    assert pa_mod.blocks_walked(pos, BS, plan=plan) == (3, 30)
    assert pa_mod.blocks_walked(pos, BS) == (int((pos // BS + 1).sum()), 0)
    # with a window of 8 the tile starts at its FIRST lane's first block
    assert pa_mod.blocks_walked(pos, BS, plan=plan, window=8) == (3, 30)
    assert pa_mod.blocks_walked(pos + 16, BS, plan=pa_mod.plan_tiles(
        rows, pos + 16, 128, np), window=8) == (3, 30)   # blocks 1..3
