"""The gated delta rule with a decay a key channel (KDA) on the CPU: the
chunked form and the two Pallas kernels (interpret mode) against the token by
token recurrence, over runs that cross chunks, beside padding lanes and the
null slot, with decays down to -30 a token and write strengths in (0, 2).

Tolerances: float32 with products at the highest precision, so the forms
differ by rounding alone (the module docstring of
``tests/test_gated_delta_rule.py``): 2e-5 absolute on outputs and states of
size ~1. Steep decays (down to -30 a token) take a channel's running sum in a
chunk to ~-300, and ``exp(c_i - c_j)`` of two such sums carries their float32
rounding, ~300 x 6e-8 relative: 1e-4 there. A decay of -30 makes
``exp(-c_j)`` overflow float32 within three tokens, so a chunked form that
split ``exp(c_i - c_j)`` would give inf or nan there, not a small error; a
decay a head in place of a decay a channel moves outputs by 1e-2 or more.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu  # noqa: F401
from paddle_tpu.ops.pallas import gated_delta_rule as G

TOL = 2e-5
STEEP_TOL = 1e-4
H, DK, DV = 2, 16, 32


def _normal(key, shape):
    return jax.random.normal(key, shape, jnp.float32)


def _draw(seed, n, steep=False, heads=H, dk=DK, dv=DV):
    """q, k unit (q scaled), v normal, ``g`` [n, heads, dk] <= 0, beta in
    (0, 2). ``steep``: a quarter of the (token, channel) decays are drawn in
    [-30, -10], the rest near 0, so some channels forget at once while their
    neighbours keep."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(_normal(ks[0], (n, heads, dk))) * np.float32(dk ** -0.5)
    k = unit(_normal(ks[1], (n, heads, dk)))
    v = _normal(ks[2], (n, heads, dv))
    g = np.float32(-0.2) * jnp.exp(_normal(ks[3], (n, heads, dk)))
    if steep:
        hard = jax.random.uniform(ks[5], (n, heads, dk)) < 0.25
        g = jnp.where(hard, jax.random.uniform(ks[6], (n, heads, dk),
                                               minval=-30.0, maxval=-10.0), g)
    beta = np.float32(2.0) * jax.nn.sigmoid(_normal(ks[4], (n, heads)))
    return q, k, v, g.astype(jnp.float32), beta


@jax.jit
def _steps(q, k, v, g, beta, s):
    """Token by token from ``s`` [H, dk, dv]: ``S <- Diag(exp g) S``, then
    the delta rule, written out here and not taken from the module."""
    def one(s, x):
        qt, kt, vt, gt, bt = x
        s = s * jnp.exp(gt)[:, :, None]
        u = bt[:, None] * (vt - jnp.einsum("hd,hdv->hv", kt, s,
                                           precision="highest"))
        s = s + kt[:, :, None] * u[:, None, :]
        return s, jnp.einsum("hd,hdv->hv", qt, s, precision="highest")

    s, o = jax.lax.scan(one, s, (q, k, v, g, beta))
    return o, s


@pytest.mark.parametrize("steep", [False, True], ids=["mild", "steep"])
def test_the_chunked_form_is_the_one_token_form(steep):
    q, k, v, g, beta = _draw(1, 64, steep)
    s0 = _normal(jax.random.PRNGKey(9), (H, DK, DV))
    o_seq, s_seq = _steps(q, k, v, g, beta, s0)
    o_ch, s_ch = G.kda_chunk_reference(q, k, v, g, beta, s0)
    tol = STEEP_TOL if steep else TOL
    assert np.isfinite(np.asarray(o_ch)).all()
    assert float(jnp.abs(o_ch - o_seq).max()) < tol
    assert float(jnp.abs(s_ch - s_seq).max()) < tol


def test_the_step_form_reads_a_decay_a_channel():
    q, k, v, g, beta = _draw(2, 1)
    s0 = _normal(jax.random.PRNGKey(3), (1, H, DK, DV))
    want_o, want_s = _steps(q, k, v, g, beta, s0[0])
    o, s = G.step_reference(q, k, v, g, beta, s0)
    assert float(jnp.abs(o - want_o).max()) < TOL
    assert float(jnp.abs(s[0] - want_s).max()) < TOL


def test_rows_of_zeros_change_no_state():
    """What pads a run's last chunk: k, v, g, beta all 0."""
    q, k, v, g, beta = _draw(3, 40, steep=True)
    pad = lambda x: jnp.concatenate(  # noqa: E731
        [x, jnp.zeros((24,) + x.shape[1:], x.dtype)])
    s0 = _normal(jax.random.PRNGKey(1), (H, DK, DV))
    _, s_short = _steps(q, k, v, g, beta, s0)
    _, s_pad = G.kda_chunk_reference(*(pad(x) for x in (q, k, v, g, beta)),
                                     s0)
    assert float(jnp.abs(s_pad - s_short).max()) < STEEP_TOL


# a step's pack: two decode lanes, a run that starts its sequence and is cut
# into two chunks (64 + 6), a run that goes on from its slot's state at
# position 64, a run of ONE token, then padding
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
        o, s = _steps(q[i:i + 1], k[i:i + 1], v[i:i + 1], g[i:i + 1],
                      beta[i:i + 1], s)
        state[r], out[i] = np.asarray(s), np.asarray(o[0])
    return out, state


@pytest.mark.parametrize("steep", [False, True], ids=["mild", "steep"])
@pytest.mark.parametrize("kernels", [False, True],
                         ids=["plain", "kernels-interpreted"])
def test_a_step_of_runs_is_its_lanes_one_by_one(monkeypatch, kernels, steep):
    """Runs, decode lanes and padding in ONE step, through the plain forms
    and through ``%kda_chunk`` and ``%kda_step`` (interpret mode): every
    lane's output and every slot's state are what single steps give, finite
    at decays of -30; padding lanes give zeros; a slot without a lane keeps
    its state."""
    if kernels:
        monkeypatch.setattr(G, "_kernel_applies", lambda q, v, s: True)
    rows, pos, valid = _pack()
    q, k, v, g, beta = _draw(4, T, steep)
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
    tol = STEEP_TOL if steep else TOL
    assert np.isfinite(np.asarray(o)).all() and np.isfinite(got_s).all()
    assert np.abs(np.asarray(o) - want_o).max() < tol
    assert np.abs(got_s[:-1] - want_s[:-1]).max() < tol
    np.testing.assert_array_equal(np.asarray(o)[len(LANES):], 0.0)
    np.testing.assert_array_equal(got_s[5], np.asarray(state_h)[5])


@pytest.mark.parametrize("kernels", [False, True],
                         ids=["plain", "kernels-interpreted"])
def test_a_burst_lane_is_its_slots_next_token(monkeypatch, kernels):
    """No plan: lane i runs on slot i; a lane at position 0 starts from
    zeros whatever its slot holds; slots behind the lanes keep their state."""
    if kernels:
        monkeypatch.setattr(G, "_kernel_applies", lambda q, v, s: True)
    B = 4
    q, k, v, g, beta = _draw(6, B, steep=True)
    pack = G.pack_of(H, DV)
    state_h = _normal(jax.random.PRNGKey(7), (SLOTS, H, DK, DV)
                      ).at[SLOTS - 1].set(0.0)
    pos = jnp.asarray([5, 0, 2, 9], jnp.int32)
    keep = np.asarray([1, 0, 1, 1], np.float32)
    o, state = jax.jit(G.gated_delta)(q, k, v, g, beta,
                                      G.pack_state(state_h, pack), pos)
    got = np.asarray(G.unpack_state(state, pack))
    for b in range(B):
        want_o, want_s = _steps(q[b:b + 1], k[b:b + 1], v[b:b + 1],
                                g[b:b + 1], beta[b:b + 1],
                                state_h[b] * keep[b])
        assert float(jnp.abs(o[b] - want_o[0]).max()) < TOL
        assert np.abs(got[b] - np.asarray(want_s)).max() < TOL
    np.testing.assert_array_equal(got[B:], np.asarray(state_h)[B:])


def test_a_run_across_two_steps_is_the_run_in_one(monkeypatch):
    """Through the kernels (interpret mode), a request's runs follow each
    other across steps: 37 tokens, then 91 from the state the first left,
    against 128 at once and against the recurrence."""
    monkeypatch.setattr(G, "_kernel_applies", lambda q, v, s: True)
    q, k, v, g, beta = _draw(8, 128, steep=True)
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
    tol = STEEP_TOL
    # (slot 1 is the null slot: what padding wrote there is read by no one)
    assert float(jnp.abs(jnp.concatenate([o_a, o_b]) - o_all).max()) < tol
    assert float(jnp.abs(s_b[0] - s_all[0]).max()) < tol
    o_seq, s_seq = _steps(q, k, v, g, beta, jnp.zeros((H, DK, DV)))
    assert float(jnp.abs(o_all - o_seq).max()) < tol
    assert float(jnp.abs(G.unpack_state(s_all, pack)[0] - s_seq).max()) < tol


def test_a_scalar_decay_a_head_is_not_a_decay_a_channel():
    """The rule the shape of ``g`` picks is the one that runs: the same
    tokens with each head's mean decay in every channel give other outputs."""
    q, k, v, g, beta = _draw(10, 64, steep=True)
    s0 = jnp.zeros((H, DK, DV), jnp.float32)
    o_ch, _ = G.kda_chunk_reference(q, k, v, g, beta, s0)
    o_sc, _ = G.chunk_reference(q, k, v, g.mean(-1), beta, s0)
    assert float(jnp.abs(o_ch - o_sc).max()) > 1e-2
