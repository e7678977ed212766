"""Plain reference of the Solar-Open2-shaped decoder, cut to one chip's share.

Written from the layer equations (the configuration's ``assumed``) in float32
``jax.numpy`` with every matmul at ``Precision.HIGHEST``: no cache, no kernel,
no batching, no chunked form, nothing imported from the program. ``x`` is [T,
hidden] of ONE request, ``RMS`` is RMSNorm at ``rms_norm_eps``; layer ``i`` is
a GQA layer where ``i in gqa_layers``, a KDA layer elsewhere.

- Both kinds, pre-norm: ``h = x + mixer(RMS(x))``; ``y = h + MoE(RMS(h))``;
  a final ``RMS`` before the untied head over the vocabulary slice held.
- GQA layer: ``q = x Wq`` (64 x 128), ``k = x Wk``, ``v = x Wv`` (8 x 128);
  NO rotary; causal softmax at ``128 ** -0.5``, query head ``h`` reading KV
  head ``h // 8``; ``o = attn * sigmoid(x Wg)`` elementwise; ``o Wo``.
- KDA layer, H heads of ``d``: ``q~ = x Wq``, ``k~ = x Wk``, ``v~ = x Wv``,
  each through its own causal depthwise convolution over the sequence
  (kernel 4, the last tap on the token itself, no bias) and SiLU. Per head
  ``q = l2norm(q) * d ** -0.5``, ``k = l2norm(k)`` (``x * rsqrt(sum x^2 +
  1e-6)``); ``beta = 2 sigmoid(x Wb)`` a head; ``g = -exp(A_log_h) *
  softplus(x Wfa Wfb + dt_bias)`` in ``[H, d]`` a token. With ``S`` in ``[d,
  d]`` a head, zero at the sequence's start, token by token: ``S <- Diag(exp
  g_t) S``; ``u = beta_t (v_t - S^T k_t)``; ``S <- S + k_t u^T``; ``o_t =
  S^T q_t``. Then per head ``o = RMS(o) * w * sigmoid(x Wga Wgb)`` (weight
  ``[d]``) and ``Wo``.
- MoE: ``s = sigmoid(h Wr)`` over all published experts; the 8 largest of
  ``s + c`` are chosen; ``w_e = s_e / (sum of the chosen s)``; ``y = sum
  over chosen AND HELD e of w_e SwiGLU_e(h) + SwiGLU_shared(h)``: the share
  of the routed result that the experts held here give (the first
  ``n_routed_experts`` of ``published.n_routed_experts``), and the shared
  expert whole; what the absent experts would add is left out, here as in
  the program.

Departures from the published description: none known; what the published
``config.json`` does not say is the configuration file's ``assumed``.

The recurrence is a ``lax.scan`` over single tokens. Attention is computed in
query blocks, each held expert runs over every token and is weighted by 0
where it was not chosen, and the sample is cropped to its longest request.

``quant="fp8"`` is the control of "How correct is decided": both operands of
every matmul (projections, gates, scores, values, experts, head) rounded to
float8 e4m3 with a per-tensor scale, one precision below the configuration's
bfloat16; the recurrence's own products stay float32. It has to come out as
not correct.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

import weights as W
from builders import solar_open2 as B
from reference.llama import F32, mm, rmsnorm
from reference.mimo_v2_flash import attention
from reference.olmo_hybrid import causal_conv, l2norm

CROP_TO = 512           # a request is cropped to this times a power of two


def delta_rule(q, k, v, g, beta):
    """q, k (T, H, d), v (T, H, d), g (T, H, d), beta (T, H): the delta rule
    with a decay a key channel, one token at a time from the zero state.
    Returns o (T, H, d)."""
    H, dk, dv = q.shape[1], q.shape[2], v.shape[2]

    def one(S, x):
        qt, kt, vt, gt, bt = x
        S = S * jnp.exp(gt)[:, :, None]
        u = bt[:, None] * (vt - jnp.einsum("hdv,hd->hv", S, kt,
                                           precision=lax.Precision.HIGHEST))
        S = S + kt[:, :, None] * u[:, None, :]
        return S, jnp.einsum("hdv,hd->hv", S, qt,
                             precision=lax.Precision.HIGHEST)

    _, o = lax.scan(one, jnp.zeros((H, dk, dv), F32), (q, k, v, g, beta))
    return o


def kda_mixer(p, x, cfg, quant):
    T = x.shape[0]
    lin = cfg["linear_attn_config"]
    H, d = lin["num_heads"], lin["head_dim"]
    pre = "self_attn."
    proj = lambda n, y=x: mm("th,hd->td", y,  # noqa: E731
                             p[pre + n + "_proj.weight"], quant)
    conv = lambda n: jax.nn.silu(causal_conv(  # noqa: E731
        proj(n), p[pre + n + "_conv1d.weight"]))
    q = l2norm(conv("q").reshape(T, H, d)) * F32(d ** -0.5)
    k = l2norm(conv("k").reshape(T, H, d))
    v = conv("v").reshape(T, H, d)
    beta = jax.nn.sigmoid(proj("b"))
    if cfg["kda_allow_neg_eigval"]:
        beta = beta * F32(2.0)
    g = -jnp.exp(p[pre + "A_log"])[:, None] * jax.nn.softplus(
        proj("f_b", proj("f_a")) + p[pre + "dt_bias"]).reshape(T, H, d)
    o = delta_rule(q, k, v, g, beta)
    o = rmsnorm(o, p[pre + "o_norm.weight"], cfg["rms_norm_eps"]) \
        * jax.nn.sigmoid(proj("g_b", proj("g_a")).reshape(T, H, d))
    return mm("td,dh->th", o.reshape(T, H * d), p[pre + "o_proj.weight"],
              quant)


def gqa_mixer(p, x, cfg, quant):
    T = x.shape[0]
    H, KV, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    pre = "self_attn."
    proj = lambda n: mm("th,hd->td", x, p[pre + n + "_proj.weight"],  # noqa: E731
                        quant)
    o = attention(proj("q").reshape(T, H, D), proj("k").reshape(T, KV, D),
                  proj("v").reshape(T, KV, D), None, None, quant)
    o = o.reshape(T, H * D)
    if cfg["use_gqa_gate"]:
        o = o * jax.nn.sigmoid(proj("g"))
    return mm("td,dh->th", o, p[pre + "o_proj.weight"], quant)


def swiglu(h, w1, w3, w2, quant):
    a = mm("th,hm->tm", h, w1, quant)
    b = mm("th,hm->tm", h, w3, quant)
    return mm("tm,mh->th", jax.nn.silu(a) * b, w2, quant)


def routed(h, p, cfg, quant):
    """The held experts' share of the routed experts for h (T, hidden)."""
    s = jax.nn.sigmoid(mm("th,he->te", h, p["mlp.gate.weight"], quant))
    _, chosen = lax.top_k(s + p["mlp.gate.e_score_correction_bias"],
                          cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, chosen, -1)
    w = picked / jnp.sum(picked, -1, keepdims=True)          # (T, top)
    held = cfg["n_routed_experts"]
    # (T, held): the weight of held expert e for token t, 0 where not chosen
    w_held = jnp.sum(jnp.where(chosen[..., None] == jnp.arange(held),
                               w[..., None], F32(0)), axis=1)

    def one(y, e):
        w1, w3, w2, we = e
        return y + we[:, None] * swiglu(h, w1, w3, w2, quant), None

    y, _ = lax.scan(one, jnp.zeros_like(h), (
        p["mlp.experts.gate_proj"], p["mlp.experts.up_proj"],
        p["mlp.experts.down_proj"], w_held.T))
    return y


def moe(h, p, cfg, quant):
    y = routed(h, p, cfg, quant)
    if cfg["n_shared_experts"]:
        s = "mlp.shared_experts."
        y = y + swiglu(h, p[s + "gate_proj.weight"], p[s + "up_proj.weight"],
                       p[s + "down_proj.weight"], quant)
    return y


def block(p, x, cfg, i, quant):
    """Decoder layer ``i`` on x (T, hidden); ``p`` its leaves by short name."""
    eps = cfg["rms_norm_eps"]
    mixer = gqa_mixer if B.is_gqa(cfg, i) else kda_mixer
    h = x + mixer(p, rmsnorm(x, p["input_layernorm.weight"], eps), cfg, quant)
    return h + moe(rmsnorm(h, p["post_attention_layernorm.weight"], eps), p,
                   cfg, quant)


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
        make = B.is_gqa(cfg, i)
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

    def _hidden(self, tokens):
        h = self._embed(self.key, tokens)
        for i in range(self.cfg["num_hidden_layers"]):
            h = self._layer(i)(self.key, h,
                               jnp.int32(B.layer_base(self.cfg, i)))
        return h

    def logits(self, tokens):
        """All logits (T, vocab) of ONE sequence ``tokens`` (T,): for tests
        at a small size."""
        h = self._hidden(jnp.asarray(tokens, jnp.int32))
        n = len(self.specs)
        return head_logits(self._leaf(self.key, n - 2),
                           self._leaf(self.key, n - 1), h, self.cfg,
                           self.quant)

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
