"""Pallas kernel tests (interpret mode on CPU — same kernel code the TPU compiles).

Mirrors the reference's flash-attention op tests (test/legacy_test/test_flash_attention.py:
forward vs math-softmax reference, grads vs reference grads, causal + GQA variants).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu.ops.pallas.flash_attention import flash_attention_fwd


def _ref_sdpa(q, k, v, causal):
    qt, kt, vt = [jnp.swapaxes(x, 1, 2) for x in (q, k, v)]
    hq, hk = qt.shape[1], kt.shape[1]
    if hq != hk:
        kt = jnp.repeat(kt, hq // hk, 1)
        vt = jnp.repeat(vt, hq // hk, 1)
    s = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) / np.sqrt(q.shape[-1])
    if causal:
        m = jnp.tril(jnp.ones((s.shape[-2], s.shape[-1]), bool))
        s = jnp.where(m, s, -1e30)
    p = jax.nn.softmax(s, -1)
    return jnp.swapaxes(jnp.einsum("bhqk,bhkd->bhqd", p, vt), 1, 2)


@pytest.fixture(autouse=True)
def _interpret_mode(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")


class TestFlashAttentionKernel:
    @pytest.mark.parametrize("B,S,Hq,Hkv,D,causal", [
        (2, 256, 4, 4, 64, True),
        (2, 256, 4, 2, 64, True),     # GQA
        (1, 128, 2, 2, 32, False),
        (1, 384, 2, 1, 64, True),     # MQA, non-pow2 seq blocks
    ])
    def test_forward_matches_reference(self, B, S, Hq, Hkv, D, causal):
        r = np.random.RandomState(0)
        q = jnp.asarray(r.randn(B, S, Hq, D), jnp.float32)
        k = jnp.asarray(r.randn(B, S, Hkv, D), jnp.float32)
        v = jnp.asarray(r.randn(B, S, Hkv, D), jnp.float32)
        out = flash_attention_fwd(q, k, v, causal=causal)
        ref = _ref_sdpa(q, k, v, causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_backward_matches_reference(self):
        r = np.random.RandomState(1)
        q = jnp.asarray(r.randn(2, 256, 4, 64), jnp.float32)
        k = jnp.asarray(r.randn(2, 256, 2, 64), jnp.float32)
        v = jnp.asarray(r.randn(2, 256, 2, 64), jnp.float32)

        def loss_fa(q, k, v):
            return (flash_attention_fwd(q, k, v, causal=True) ** 2).sum()

        def loss_ref(q, k, v):
            return (_ref_sdpa(q, k, v, True) ** 2).sum()

        g = jax.grad(loss_fa, (0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, (0, 1, 2))(q, k, v)
        for a, b in zip(g, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-3, atol=1e-3)

    def test_unsupported_shapes_raise(self):
        q = jnp.zeros((1, 100, 2, 64), jnp.float32)  # seq 100 not divisible
        with pytest.raises(ValueError):
            flash_attention_fwd(q, q, q, block_q=64, block_k=64)

    def test_sdpa_pallas_path_matches_math(self, monkeypatch):
        # force the dispatch through the pallas kernel on CPU (interpret)
        import importlib

        fa_mod = importlib.import_module(
            "paddle_tpu.nn.functional.flash_attention")

        monkeypatch.setattr(fa_mod, "_use_pallas", lambda q: True)
        r = np.random.RandomState(2)
        q = paddle.to_tensor(r.randn(2, 128, 4, 64).astype("float32"),
                             stop_gradient=False)
        k = paddle.to_tensor(r.randn(2, 128, 4, 64).astype("float32"))
        v = paddle.to_tensor(r.randn(2, 128, 4, 64).astype("float32"))
        out = F.scaled_dot_product_attention(q, k, v, is_causal=True)
        monkeypatch.setattr(fa_mod, "_use_pallas", lambda q: False)
        ref = F.scaled_dot_product_attention(q.detach(), k, v, is_causal=True)
        np.testing.assert_allclose(out.numpy(), ref.numpy(), rtol=2e-5, atol=2e-5)
        out.sum().backward()
        assert q.grad is not None and np.isfinite(q.grad.numpy()).all()


class TestCrossLengthCausal:
    def test_decode_style_bottom_right_alignment(self):
        # Sq < Sk causal must align bottom-right like the math path (_math_sdpa)
        r = np.random.RandomState(3)
        q = jnp.asarray(r.randn(1, 128, 2, 64), jnp.float32)
        k = jnp.asarray(r.randn(1, 256, 2, 64), jnp.float32)
        v = jnp.asarray(r.randn(1, 256, 2, 64), jnp.float32)
        out = flash_attention_fwd(q, k, v, causal=True)
        qt, kt, vt = [jnp.swapaxes(x, 1, 2) for x in (q, k, v)]
        s = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) / np.sqrt(64)
        m = jnp.tril(jnp.ones((128, 256), bool), k=128)
        s = jnp.where(m, s, -1e30)
        ref = jnp.swapaxes(
            jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), vt), 1, 2)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


class TestTruncNormTail:
    def test_far_tail_window_terminates(self):
        from paddle_tpu.nn.initializer import TruncatedNormal

        arr = np.asarray(TruncatedNormal(a=6.0, b=7.0)((8, 8)))
        assert ((arr >= 6.0) & (arr <= 7.0)).all()


def test_causal_sq_gt_sk_rejected():
    """ADVICE round-1: rows attending to nothing would produce garbage grads."""
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.flash_attention import flash_attention_fwd

    q = jnp.zeros((1, 8, 2, 16))
    kv = jnp.zeros((1, 4, 2, 16))
    with pytest.raises(ValueError, match="Sq<=Sk"):
        flash_attention_fwd(q, kv, kv, causal=True)


def test_flash_runs_per_head_shard_under_an_auto_mesh_axis(mesh8):
    """Inside mesh.parallelize's step (dp manual, mp auto) GSPMD cannot
    partition a Mosaic kernel: the dispatcher wraps it in a nested shard_map
    over the auto axis, heads sharded. Loss and grads equal the math path."""
    import sys

    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    fa = sys.modules["paddle_tpu.nn.functional.flash_attention"]
    mesh = Mesh(np.array(mesh8[:4]).reshape(2, 2), ("dp", "mp"))
    r = np.random.RandomState(0)
    q, k, v = (jnp.asarray(r.randn(2, 128, 4, 32), jnp.float32)
               for _ in range(3))

    def body(q, k, v):
        def loss(q, k, v):
            return (fa._flash(q, k, v, True, None) ** 2).sum()

        val, grads = jax.value_and_grad(loss, (0, 1, 2))(q, k, v)
        return jax.lax.psum(val, "dp"), grads

    step = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P("dp"),) * 3,
        out_specs=(P(), (P("dp"),) * 3), axis_names=frozenset({"dp"}),
        check_vma=False))
    args = [jax.device_put(x, NamedSharding(mesh, P("dp", None, "mp", None)))
            for x in (q, k, v)]
    # the outer dp shard_map, plus the nested one around forward and backward
    assert str(jax.make_jaxpr(step)(*args)).count("shard_map") > 1
    val, grads = step(*args)

    def ref(q, k, v):
        return (_ref_sdpa(q, k, v, True) ** 2).sum()

    want, want_grads = jax.value_and_grad(ref, (0, 1, 2))(q, k, v)
    np.testing.assert_allclose(float(val), float(want), rtol=1e-5)
    for a, b in zip(grads, want_grads):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-3)
