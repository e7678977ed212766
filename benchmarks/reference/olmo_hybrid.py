"""Plain reference of the Olmo-Hybrid-shaped decoder, cut in depth.

Written from the layer equations (ISSUE 36 / the configuration's ``assumed``)
in float32 ``jax.numpy`` with every matmul at ``Precision.HIGHEST``: no cache,
no kernel, no batching, no chunked form, nothing imported from the program.
``x`` is [T, hidden] of ONE request, ``RMS`` is RMSNorm at ``rms_norm_eps``;
layer ``i`` is linear where ``layer_types[i] == "linear_attention"``.

- Both kinds: ``h = x + RMS_post_attn(mixer(x))``;
  ``y = h + RMS_post_ff(W_down(silu(W_gate h) * W_up h))``; no bias anywhere; a
  final ``RMS`` before the untied head.
- Full layer: ``q = RMS_q(x Wq)``, ``k = RMS_k(x Wk)`` over the whole
  projections, heads of ``hidden / heads``, ``v = x Wv``; NO rotary embedding;
  causal softmax attention at ``head_dim ** -0.5``; ``Wo``.
- Linear layer, H heads of ``d_k`` / ``d_v``: ``q~ = x Wq``, ``k~ = x Wk``,
  ``v~ = x Wv``, ``z = x Wg``, ``a = x Wa``, ``b = x Wb``. Each of q~, k~, v~
  through its own causal depthwise convolution over the sequence (kernel 4,
  the last tap on the token itself, no bias) and SiLU. Per head
  ``q = l2norm(q) * d_k ** -0.5``, ``k = l2norm(k)`` (``x * rsqrt(sum x^2 +
  1e-6)``); ``beta = 2 sigmoid(b)``; ``g = -exp(A_log) softplus(a + dt_bias)``.
  With ``S`` in ``[d_k, d_v]``, zero at the sequence's start, token by token:
  ``S <- exp(g_t) S``; ``u = beta_t (v_t - S^T k_t)``; ``S <- S + k_t u^T``;
  ``o_t = S^T q_t``. Then per head ``o = RMS_o(o) * silu(z)`` (weight
  ``[d_v]``) and ``Wo``.

Departures from the published description: none known; what the published
``config.json`` does not say is the configuration file's ``assumed``.

The recurrence is a ``lax.scan`` over single tokens, never the chunked form:
it has to be independent of the program. Attention is computed in query
blocks and the sample is cropped to its longest request.

``quant="fp8"`` is the control of "How correct is decided": both operands of
every matmul (projections, scores, values, MLP, head) rounded to float8 e4m3
with a per-tensor scale, one precision below the configuration's bfloat16; the
recurrence's own products stay float32. It has to come out as not correct.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

import weights as W
from builders import olmo_hybrid as B
from reference.llama import F32, mm, rmsnorm
from reference.mimo_v2_flash import attention

CROP_TO = 512           # a request is cropped to this times a power of two


def causal_conv(x, taps):
    """x (T, C) through a depthwise causal convolution: ``y_t = sum_j
    taps[j] x_{t - (K - 1) + j}``, zeros before the sequence."""
    K = taps.shape[0]
    padded = jnp.pad(x, ((K - 1, 0), (0, 0)))
    return sum(taps[j] * padded[j:j + x.shape[0]] for j in range(K))


def l2norm(x):
    return x * lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + F32(1e-6))


def delta_rule(q, k, v, g, beta):
    """q, k (T, H, dk), v (T, H, dv), g, beta (T, H): the gated delta rule,
    one token at a time from the zero state. Returns o (T, H, dv)."""
    H, dk, dv = q.shape[1], q.shape[2], v.shape[2]

    def one(S, x):
        qt, kt, vt, gt, bt = x
        S = S * jnp.exp(gt)[:, None, None]
        u = bt[:, None] * (vt - jnp.einsum("hdv,hd->hv", S, kt,
                                           precision=lax.Precision.HIGHEST))
        S = S + kt[:, :, None] * u[:, None, :]
        return S, jnp.einsum("hdv,hd->hv", S, qt,
                             precision=lax.Precision.HIGHEST)

    _, o = lax.scan(one, jnp.zeros((H, dk, dv), F32), (q, k, v, g, beta))
    return o


def linear_mixer(p, x, cfg, quant):
    T = x.shape[0]
    H = cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    pre = "linear_attn."
    proj = lambda n: mm("th,hd->td", x, p[pre + n + "_proj.weight"],  # noqa: E731
                        quant)
    conv = lambda n: jax.nn.silu(causal_conv(  # noqa: E731
        proj(n), p[pre + n + "_conv1d.weight"]))
    q = l2norm(conv("q").reshape(T, H, dk)) * F32(dk ** -0.5)
    k = l2norm(conv("k").reshape(T, H, dk))
    v = conv("v").reshape(T, H, dv)
    beta = jax.nn.sigmoid(proj("b"))
    if cfg["linear_allow_neg_eigval"]:
        beta = beta * F32(2.0)
    g = -jnp.exp(p[pre + "A_log"]) * jax.nn.softplus(
        proj("a") + p[pre + "dt_bias"])
    o = delta_rule(q, k, v, g, beta)
    o = rmsnorm(o, p[pre + "o_norm.weight"], cfg["rms_norm_eps"]) \
        * jax.nn.silu(proj("g").reshape(T, H, dv))
    return mm("td,dh->th", o.reshape(T, H * dv), p[pre + "o_proj.weight"],
              quant)


def full_mixer(p, x, cfg, quant):
    T = x.shape[0]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    D = cfg["hidden_size"] // H
    eps = cfg["rms_norm_eps"]
    pre = "self_attn."
    proj = lambda n: mm("th,hd->td", x, p[pre + n + "_proj.weight"],  # noqa: E731
                        quant)
    q = rmsnorm(proj("q"), p[pre + "q_norm.weight"], eps).reshape(T, H, D)
    k = rmsnorm(proj("k"), p[pre + "k_norm.weight"], eps).reshape(T, KV, D)
    o = attention(q, k, proj("v").reshape(T, KV, D), None, None, quant)
    return mm("td,dh->th", o.reshape(T, H * D), p[pre + "o_proj.weight"],
              quant)


def block(p, x, cfg, i, quant):
    """Decoder layer ``i`` on x (T, hidden); ``p`` its leaves by short name."""
    eps = cfg["rms_norm_eps"]
    mixer = linear_mixer if B.layer_types(cfg)[i] == B.LINEAR else full_mixer
    h = x + rmsnorm(mixer(p, x, cfg, quant),
                    p["post_attention_layernorm.weight"], eps)
    a = mm("th,hm->tm", h, p["mlp.gate_proj.weight"], quant)
    b = mm("th,hm->tm", h, p["mlp.up_proj.weight"], quant)
    y = mm("tm,mh->th", jax.nn.silu(a) * b, p["mlp.down_proj.weight"], quant)
    return h + rmsnorm(y, p["post_feedforward_layernorm.weight"], eps)


def head_logits(norm_w, head_w, rows, cfg, quant):
    return mm("rh,hv->rv", rmsnorm(rows, norm_w, cfg["rms_norm_eps"]),
              head_w, quant)


class ServeReference:
    """Teacher-forced forward over prompt + served tokens, one request and one
    layer at a time, the layer's weights drawn again from the seed inside the
    program: one layer of float32 weights is the most the device ever holds."""

    def __init__(self, seed, cfg, quant=None):
        self.cfg, self.quant = dict(cfg), quant
        self.key = W.seed_key(seed)
        self.specs = B.leaf_specs(cfg)
        self._layers = {}               # one program a kind of layer
        self._embed = jax.jit(lambda key, tokens: jnp.take(
            self._leaf(key, 0), tokens, axis=0))
        self._head = jax.jit(self._head_impl)

    def _leaf(self, key, index, like=None):
        # rounded to the served dtype (``torch_dtype``), then widened: the
        # same values. ``index`` (traced) picks the stream, ``like`` the
        # leaf's shape
        spec = self.specs[index if like is None else like]
        return B.leaf(key, index, spec,
                      jnp.dtype(self.cfg["torch_dtype"])).astype(F32)

    # the seed's key is an ARGUMENT of the programs: closed over, it would be
    # a constant of each, and every new seed would compile them anew

    def _layer(self, i):
        cfg = self.cfg
        make = B.layer_types(cfg)[i]
        if make not in self._layers:
            first = B.layer_base(cfg, i)
            names = [s[0].split(".", 3)[3] for s in B.layer_specs(cfg, i)]

            def run(key, x, base, i=i, first=first, names=names):
                p = {n: self._leaf(key, base + j, first + j)
                     for j, n in enumerate(names)}
                return block(p, x, cfg, i, self.quant)

            self._layers[make] = jax.jit(run)
        return self._layers[make]

    def _head_impl(self, key, h, positions, query):
        n = len(self.specs)
        rows = jnp.take(h, positions, axis=0)                    # (R, hidden)
        logits = head_logits(self._leaf(key, n - 2), self._leaf(key, n - 1),
                             rows, self.cfg, self.quant)
        at = jnp.take_along_axis(logits, query[:, None], -1)[:, 0]
        return jnp.max(logits, -1) - at, jnp.argmax(logits, -1).astype(jnp.int32)

    def logits(self, tokens):
        """All logits (T, vocab) of ONE sequence ``tokens`` (T,): for tests
        at a small size."""
        h = self._hidden(jnp.asarray(tokens, jnp.int32))
        n = len(self.specs)
        return head_logits(self._leaf(self.key, n - 2),
                           self._leaf(self.key, n - 1), h, self.cfg,
                           self.quant)

    def _hidden(self, tokens):
        h = self._embed(self.key, tokens)
        for i in range(self.cfg["num_hidden_layers"]):
            h = self._layer(i)(self.key, h,
                               jnp.int32(B.layer_base(self.cfg, i)))
        return h

    def gaps(self, tokens, positions, query):
        """``tokens`` (N, T) prompt + served tokens, padded at the end;
        ``positions`` (N, R) the positions whose next token was served (0
        where a row has fewer); ``query`` (N, R) the tokens to score there.
        Returns (gap, argmax), each (N, R): how far the queried token's logit
        lies below this forward's best, and this forward's own first choice.
        Each request is cropped to its own last position, rounded up to
        CROP_TO times a power of two (what lies behind a position changes
        nothing before it: every layer is causal)."""
        tokens = jnp.asarray(tokens, jnp.int32)
        positions = jnp.asarray(positions, jnp.int32)
        query = jnp.asarray(query, jnp.int32)
        gaps, firsts = [], []
        for n in range(tokens.shape[0]):
            need, T = int(positions[n].max()) + 1, CROP_TO
            while T < need:                # few lengths, so few programs
                T *= 2
            T = min(T, tokens.shape[1])
            gap, first = self._head(self.key, self._hidden(tokens[n, :T]),
                                    positions[n], query[n])
            gaps.append(gap)
            firsts.append(first)
        return jnp.stack(gaps), jnp.stack(firsts)
