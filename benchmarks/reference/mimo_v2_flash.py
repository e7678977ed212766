"""Plain reference of the MiMo-V2-Flash-shaped decoder, cut to one chip's share.

Written from the layer equations (ISSUE 32 / the configuration's ``assumed``)
in float32 ``jax.numpy`` with every matmul at ``Precision.HIGHEST``: no cache,
no kernel, no batching, nothing imported from the program. Per layer, with
``x`` [T, hidden] of ONE request:

- ``h = RMS(x)``; ``q = h Wq`` as heads x 192, ``k = h Wk`` as KV x 192,
  ``v = 0.707 (h Wv)`` as KV x 128; KV = 4 on a full layer, 8 on a window
  layer. Rotary (rotate-half) on the first ``int(0.334 * 192) = 64`` dims of q
  and k, base ``rope_theta`` (full) or ``swa_rope_theta`` (window).
  ``s_tj = q_t . k_j / sqrt(192)`` for ``j <= t`` and, on a window layer,
  ``j > t - sliding_window``. ``p_tj = exp(s_tj) / (exp(b_h) + sum_k
  exp(s_tk))`` with the sink logit ``b_h`` on window layers (no ``exp(b_h)``
  on full ones). ``x <- x + (sum_j p_tj v_j) Wo``.
- ``h = RMS(x)``; dense SwiGLU (layer 0) or the experts: ``g = sigmoid(h Wr)``
  over all 256; the 8 largest of ``g + c`` are chosen; ``w_e = g_e / (sum of
  the chosen g)``; ``y = sum over chosen AND HELD e of w_e (silu(h W1_e) *
  (h W3_e)) W2_e``: the share of the result that the experts held here give
  (the first ``n_routed_experts`` of ``published.n_routed_experts``); what the
  absent experts would add is left out, here as in the program.
- final RMS, untied head over the vocabulary slice held.

Attention is computed in query blocks and the sample is cropped to its longest
request, so that 8k positions fit; each held expert runs over every token and
is weighted by 0 where it was not chosen (plain, and 16 x the work).

``quant="fp8"`` is the control of "How correct is decided": both operands of
every matmul rounded to float8 e4m3 with a per-tensor scale, one precision
below the configuration's bfloat16. It has to come out as not correct.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

import weights as W
from builders import mimo_v2_flash as B
from reference.llama import F32, mm, rmsnorm

Q_BLOCK = 256           # query rows scored at a time
CROP_TO = 512           # a request is cropped to this times a power of two


def rope(x, theta, rotary_dim):
    """x (T, heads, D): rotate-half on the first ``rotary_dim`` dims at
    positions 0..T-1; the other dims pass through."""
    T = x.shape[0]
    inv = 1.0 / (F32(theta) ** (jnp.arange(0, rotary_dim, 2, dtype=F32)
                                / F32(rotary_dim)))
    freqs = jnp.outer(jnp.arange(T, dtype=F32), inv)
    emb = jnp.concatenate([freqs, freqs], -1)[:, None, :]    # (T, 1, R)
    r, rest = x[..., :rotary_dim], x[..., rotary_dim:]
    half = rotary_dim // 2
    rot = jnp.concatenate([-r[..., half:], r[..., :half]], -1)
    return jnp.concatenate([r * jnp.cos(emb) + rot * jnp.sin(emb), rest], -1)


def attention(q, k, v, window, sink, quant):
    """q (T, H, D), k (T, KV, D), v (T, KV, Dv); query head h reads KV head
    h // (H // KV). ``window`` None or the positions attended to; ``sink``
    None or (H,). Query blocks of Q_BLOCK rows against all keys."""
    T, H, D = q.shape
    KV = k.shape[1]
    G = H // KV
    pad = -T % Q_BLOCK
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        -1, Q_BLOCK, KV, G, D)
    j = jnp.arange(T)[None, :]

    def block(args):
        qi, t0 = args                                     # (Qb, KV, G, D)
        t = (t0 + jnp.arange(Q_BLOCK))[:, None]
        seen = j <= t
        if window is not None:
            seen = seen & (j > t - window)
        s = mm("qhgd,khd->hgqk", qi, k, quant) / F32(math.sqrt(D))
        s = jnp.where(seen[None, None], s, F32(-1e30))
        m = jnp.max(s, -1, keepdims=True)
        if sink is not None:
            b = sink.reshape(KV, G, 1, 1)
            m = jnp.maximum(m, b)
        e = jnp.exp(s - m)
        den = jnp.sum(e, -1, keepdims=True)
        if sink is not None:
            den = den + jnp.exp(b - m)
        return mm("hgqk,khd->qhgd", e / den, v, quant)    # (Qb, KV, G, Dv)

    out = lax.map(block, (qb, jnp.arange(qb.shape[0]) * Q_BLOCK))
    return out.reshape(-1, H, v.shape[-1])[:T]


def experts(h, p, cfg, quant):
    """The held experts' share of the expert MLP for h (T, hidden)."""
    g = jax.nn.sigmoid(mm("th,he->te", h, p["mlp.gate.weight"], quant))
    top = cfg["num_experts_per_tok"]
    _, chosen = lax.top_k(g + p["mlp.gate.e_score_correction_bias"], top)
    picked = jnp.take_along_axis(g, chosen, -1)
    w = picked / jnp.sum(picked, -1, keepdims=True)       # (T, top)
    held = cfg["n_routed_experts"]
    # (T, held): the weight of held expert e for token t, 0 where not chosen
    w_held = jnp.sum(jnp.where(chosen[..., None] == jnp.arange(held), w[..., None],
                               F32(0)), axis=1)

    def one(y, e):
        w1, w3, w2, we = e
        a = mm("th,hm->tm", h, w1, quant)
        b = mm("th,hm->tm", h, w3, quant)
        return y + we[:, None] * mm("tm,mh->th", jax.nn.silu(a) * b, w2,
                                    quant), None

    y, _ = lax.scan(one, jnp.zeros_like(h), (
        p["mlp.experts.gate_proj"], p["mlp.experts.up_proj"],
        p["mlp.experts.down_proj"], w_held.T))
    return y


def block(p, x, cfg, i, quant):
    """Decoder layer ``i`` on x (T, hidden); ``p`` its leaves by short name."""
    T = x.shape[0]
    window = bool(cfg["hybrid_layer_pattern"][i])
    pre = "swa_" if window else ""
    H = cfg["num_attention_heads"]
    KV = cfg[pre + "num_key_value_heads"]
    D, Dv = cfg[pre + "head_dim"], cfg[pre + "v_head_dim"]
    theta = cfg["swa_rope_theta" if window else "rope_theta"]
    rot = int(cfg["partial_rotary_factor"] * D)
    eps = cfg["layernorm_epsilon"]
    h = rmsnorm(x, p["input_layernorm.weight"], eps)
    q = mm("th,hd->td", h, p["self_attn.q_proj.weight"], quant).reshape(T, H, D)
    k = mm("th,hd->td", h, p["self_attn.k_proj.weight"], quant).reshape(T, KV, D)
    v = mm("th,hd->td", h, p["self_attn.v_proj.weight"], quant).reshape(T, KV, Dv)
    v = v * F32(cfg["attention_value_scale"])
    o = attention(rope(q, theta, rot), rope(k, theta, rot), v,
                  cfg["sliding_window"] if window else None,
                  p.get("self_attn.attention_sink_bias"), quant)
    x = x + mm("td,dh->th", o.reshape(T, H * Dv),
               p["self_attn.o_proj.weight"], quant)
    h = rmsnorm(x, p["post_attention_layernorm.weight"], eps)
    if cfg["moe_layer_freq"][i]:
        return x + experts(h, p, cfg, quant)
    a = mm("th,hm->tm", h, p["mlp.gate_proj.weight"], quant)
    b = mm("th,hm->tm", h, p["mlp.up_proj.weight"], quant)
    return x + mm("tm,mh->th", jax.nn.silu(a) * b, p["mlp.down_proj.weight"],
                  quant)


def head_logits(norm_w, head_w, rows, cfg, quant):
    return mm("rh,hv->rv", rmsnorm(rows, norm_w, cfg["layernorm_epsilon"]),
              head_w, quant)


class ServeReference:
    """Teacher-forced forward over prompt + served tokens, one request and one
    layer at a time, the layer's weights drawn again from the seed inside the
    program: one layer of float32 weights is the most the device ever holds."""

    def __init__(self, seed, cfg, quant=None):
        self.cfg, self.quant = dict(cfg), quant
        self.key = W.seed_key(seed)
        self.specs = B.leaf_specs(cfg)
        # layers of one make (window or not, experts or not) share a program
        self._layers = {}
        self._embed = jax.jit(lambda key, tokens: jnp.take(
            self._leaf(key, 0), tokens, axis=0))
        self._head = jax.jit(self._head_impl)

    def _leaf(self, key, index, like=None):
        # rounded to bfloat16 as served, then widened: the same values.
        # ``index`` (traced) picks the stream, ``like`` the leaf's shape
        spec = self.specs[index if like is None else like]
        return B.leaf(key, index, spec, jnp.bfloat16).astype(F32)

    # the seed's key is an ARGUMENT of the programs: closed over, it would be
    # a constant of each, and every new seed would compile them anew

    def _layer(self, i):
        cfg = self.cfg
        make = (bool(cfg["hybrid_layer_pattern"][i]), bool(cfg["moe_layer_freq"][i]))
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

    def gaps(self, tokens, positions, query):
        """``tokens`` (N, T) prompt + served tokens, padded at the end;
        ``positions`` (N, R) the positions whose next token was served (0
        where a row has fewer); ``query`` (N, R) the tokens to score there.
        Returns (gap, argmax), each (N, R): how far the queried token's logit
        lies below this forward's best, and this forward's own first choice.
        Each request is cropped to its own last position, rounded up to
        CROP_TO times a power of two."""
        tokens = jnp.asarray(tokens, jnp.int32)
        positions = jnp.asarray(positions, jnp.int32)
        query = jnp.asarray(query, jnp.int32)
        gaps, firsts = [], []
        for n in range(tokens.shape[0]):
            need, T = int(positions[n].max()) + 1, CROP_TO
            while T < need:                # few lengths, so few programs
                T *= 2
            T = min(T, tokens.shape[1])
            h = self._embed(self.key, tokens[n, :T])
            for i in range(self.cfg["num_hidden_layers"]):
                h = self._layer(i)(self.key, h,
                                   jnp.int32(B.layer_base(self.cfg, i)))
            gap, first = self._head(self.key, h, positions[n], query[n])
            gaps.append(gap)
            firsts.append(first)
        return jnp.stack(gaps), jnp.stack(firsts)
