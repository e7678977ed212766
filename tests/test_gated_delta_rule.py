"""The gated delta rule on the CPU: the chunked form against single steps, the
Pallas kernels in interpret mode against the plain forms, the plan of a step's
lanes, and what padding lanes and the null slot may touch.

Tolerances: everything here is float32 with products at the highest
precision, so the forms differ by rounding alone. A chunk of 64 tokens sums 64
rank-one updates in another order than 64 steps do: 2e-5 absolute on outputs
and states of size ~1 is ~100 float32 roundings, and a planted error (a dropped
decay, a stale state) moves them by 1e-2 or more.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu  # noqa: F401
from paddle_tpu.ops.pallas import gated_delta_rule as G

TOL = 2e-5
H, DK, DV = 4, 8, 64


def _normal(key, shape):
    return jax.random.normal(key, shape, jnp.float32)


def _draw(seed, n, heads=H, dk=DK, dv=DV):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(_normal(ks[0], (n, heads, dk))) * np.float32(dk ** -0.5)
    k = unit(_normal(ks[1], (n, heads, dk)))
    v = _normal(ks[2], (n, heads, dv))
    g = np.float32(-0.3) * jnp.exp(_normal(ks[3], (n, heads)))
    beta = np.float32(2.0) * jax.nn.sigmoid(_normal(ks[4], (n, heads)))
    return q, k, v, g, beta


def _steps(q, k, v, g, beta, s):
    """Token by token from ``s`` [H, dk, dv]."""
    outs = []
    for t in range(q.shape[0]):
        o, s = G.step_reference(q[t:t + 1], k[t:t + 1], v[t:t + 1],
                                g[t:t + 1], beta[t:t + 1], s[None])
        s = s[0]
        outs.append(o[0])
    return jnp.stack(outs), s


@pytest.mark.parametrize("seed,n,heads,dk,dv", [
    (0, 64, 4, 8, 64), (1, 64, 2, 16, 32), (2, 64, 3, 8, 16)])
def test_the_chunked_form_is_the_one_token_form(seed, n, heads, dk, dv):
    q, k, v, g, beta = _draw(seed, n, heads, dk, dv)
    s0 = _normal(jax.random.PRNGKey(9), (heads, dk, dv))
    o_seq, s_seq = _steps(q, k, v, g, beta, s0)
    o_ch, s_ch = G.chunk_reference(q, k, v, g, beta, s0)
    assert float(jnp.abs(o_ch - o_seq).max()) < TOL
    assert float(jnp.abs(s_ch - s_seq).max()) < TOL


def test_rows_of_zeros_change_no_state():
    """What pads a run's last chunk: k, v, g, beta all 0."""
    q, k, v, g, beta = _draw(3, 40)
    pad = lambda x: jnp.concatenate(  # noqa: E731
        [x, jnp.zeros((24,) + x.shape[1:], x.dtype)])
    s0 = _normal(jax.random.PRNGKey(1), (H, DK, DV))
    _, s_short = _steps(q, k, v, g, beta, s0)
    _, s_pad = G.chunk_reference(*(pad(x) for x in (q, k, v, g, beta)), s0)
    assert float(jnp.abs(s_pad - s_short).max()) < TOL


@pytest.mark.parametrize("heads", [4, 3])
def test_packing_the_state_is_undone_by_unpacking(heads):
    pack = G.pack_of(heads, DV)
    s = _normal(jax.random.PRNGKey(0), (5, heads, DK, DV))
    packed = G.pack_state(s, pack)
    assert packed.shape == (5, heads // pack, DK, pack * DV)
    np.testing.assert_array_equal(G.unpack_state(packed, pack), s)


# a step's pack: two decode lanes, a chunk that starts its sequence and is cut
# into two (64 + 6), a chunk that goes on from its slot's state at position 64,
# a chunk of ONE token (a run of one, like a decode lane), then padding
SLOTS = 6
LANES = [(0, 7), (2, 3)] + [(1, p) for p in range(70)] \
    + [(3, 64 + p) for p in range(41)] + [(4, 12)]
T = 130


def _pack():
    rows = np.zeros(T, np.int32)
    pos = np.zeros(T, np.int32)
    valid = np.zeros(T, bool)
    for i, (r, p) in enumerate(LANES):
        rows[i], pos[i], valid[i] = r, p, True
    return jnp.asarray(rows), jnp.asarray(pos), jnp.asarray(valid)


def _expected(q, k, v, g, beta, state_h):
    """Lane by lane, a run from its slot's state (zeros at position 0)."""
    state = np.array(state_h)
    out = np.zeros((T, H, DV), np.float32)
    for i, (r, p) in enumerate(LANES):
        s = jnp.asarray(state[r]) * np.float32(0.0 if p == 0 else 1.0)
        o, s = G.step_reference(q[i:i + 1], k[i:i + 1], v[i:i + 1],
                                g[i:i + 1], beta[i:i + 1], s[None])
        state[r], out[i] = np.asarray(s[0]), np.asarray(o[0])
    return out, state


def test_plan_runs_cuts_the_pack_into_chunks_and_single_lanes():
    plan = {k: np.asarray(v) for k, v in G.plan_runs(*_pack(), SLOTS).items()}
    assert int(plan["chunks"]) == 3 and int(plan["singles"]) == 3
    assert plan["lane0"][:3].tolist() == [2, 66, 72]
    assert plan["n"][:3].tolist() == [64, 6, 41]
    assert plan["slot"][:3].tolist() == [1, 1, 3]
    # from zeros; going on from the chunk before; from the slot's state
    assert plan["code"][:3].tolist() == [2, 0, 1]
    assert (plan["n"][3:] == 0).all() and (plan["slot"][3:] == SLOTS - 1).all()
    assert plan["order"][:3].tolist() == [0, 1, 113]
    assert plan["order_slot"][:3].tolist() == [0, 2, 4]
    assert (plan["order_slot"][3:] == SLOTS - 1).all()
    assert plan["single"].sum() == 3 and plan["chunked"].sum() == 111
    assert not plan["single"][len(LANES):].any()
    assert not plan["chunked"][len(LANES):].any()
    assert plan["last"].nonzero()[0].tolist() == [0, 1, 71, 112, 113]


@pytest.mark.parametrize("kernels", [False, True],
                         ids=["plain", "kernels-interpreted"])
def test_a_step_of_runs_is_its_lanes_one_by_one(monkeypatch, kernels):
    """Two requests' runs, decode lanes and padding in ONE step, through the
    plain forms and through both kernels (interpret mode): every lane's
    output and every slot's state are what single steps give; padding lanes
    give zeros; a slot without a lane keeps its state."""
    if kernels:
        monkeypatch.setattr(G, "_kernel_applies", lambda q, v, s: True)
    rows, pos, valid = _pack()
    q, k, v, g, beta = _draw(4, T)
    pack = G.pack_of(H, DV)
    state_h = _normal(jax.random.PRNGKey(5), (SLOTS, H, DK, DV)
                      ).at[SLOTS - 1].set(0.0)
    want_o, want_s = _expected(q, k, v, g, beta, state_h)

    def run(q, k, v, g, beta, state, pos, rows, valid):
        return G.gated_delta(q, k, v, g, beta, state, pos,
                             G.plan_runs(rows, pos, valid, SLOTS))

    o, state = jax.jit(run)(q, k, v, g, beta, G.pack_state(state_h, pack),
                            pos, rows, valid)
    got_s = np.asarray(G.unpack_state(state, pack))
    assert np.abs(np.asarray(o) - want_o).max() < TOL
    assert np.abs(got_s[:-1] - want_s[:-1]).max() < TOL
    np.testing.assert_array_equal(np.asarray(o)[len(LANES):], 0.0)
    np.testing.assert_array_equal(got_s[5], np.asarray(state_h)[5])
    assert np.isfinite(got_s[-1]).all()


@pytest.mark.parametrize("kernels", [False, True],
                         ids=["plain", "kernels-interpreted"])
def test_a_burst_lane_is_its_slots_next_token(monkeypatch, kernels):
    """No plan: lane i runs on slot i; a lane at position 0 starts from
    zeros whatever its slot holds; slots behind the lanes keep their state."""
    if kernels:
        monkeypatch.setattr(G, "_kernel_applies", lambda q, v, s: True)
    B = 4
    q, k, v, g, beta = _draw(6, B)
    pack = G.pack_of(H, DV)
    state_h = _normal(jax.random.PRNGKey(7), (SLOTS, H, DK, DV)
                      ).at[SLOTS - 1].set(0.0)
    pos = jnp.asarray([5, 0, 2, 9], jnp.int32)
    keep = jnp.asarray([1, 0, 1, 1], jnp.float32)[:, None, None, None]
    want_o, want_s = G.step_reference(q, k, v, g, beta, state_h[:B] * keep)
    o, state = jax.jit(G.gated_delta)(q, k, v, g, beta,
                                      G.pack_state(state_h, pack), pos)
    got = np.asarray(G.unpack_state(state, pack))
    assert float(jnp.abs(o - want_o).max()) < TOL
    assert np.abs(got[:B] - np.asarray(want_s)).max() < TOL
    np.testing.assert_array_equal(got[B:], np.asarray(state_h)[B:])


def test_a_run_across_two_steps_is_the_run_in_one():
    """A request's runs follow each other across steps at any length: 37
    tokens, then 91 from the state the first left, against 128 at once."""
    q, k, v, g, beta = _draw(8, 128)
    pack = G.pack_of(H, DV)
    zeros = G.pack_state(jnp.zeros((2, H, DK, DV), jnp.float32), pack)

    def run(lo, hi, state):
        n = hi - lo
        rows, valid = jnp.zeros(n, jnp.int32), jnp.ones(n, bool)
        pos = jnp.arange(lo, hi, dtype=jnp.int32)
        cut = lambda x: x[lo:hi]  # noqa: E731
        return G.gated_delta(cut(q), cut(k), cut(v), cut(g), cut(beta),
                             state, pos, G.plan_runs(rows, pos, valid, 2))

    o_all, s_all = run(0, 128, zeros)
    o_a, s_a = run(0, 37, zeros)
    o_b, s_b = run(37, 128, s_a)
    assert float(jnp.abs(jnp.concatenate([o_a, o_b]) - o_all).max()) < TOL
    assert float(jnp.abs(s_b - s_all).max()) < TOL
