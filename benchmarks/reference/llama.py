"""Plain reference of the Llama-shaped decoder: RMSNorm, rotary attention with
grouped KV heads, SwiGLU, untied LM head, token-mean cross entropy.

Written from the equations in float32 ``jax.numpy`` with every matmul at
``Precision.HIGHEST``; no kernel, no cache, no batching tricks, and nothing
imported from the program. Weights come from ``benchmarks/weights.py`` (the
seed), never from the program.

``quant`` turns the same code into the control of "How correct is decided": the
reference computed one precision below the configuration's bfloat16, i.e. with
both operands of every matmul rounded to float8 (e4m3, per-tensor absmax
scale). It stands in the program's place and has to come out as not correct.

Two users: ``ServeReference`` scores served tokens (teacher-forced forward over
prompt + served tokens, weights drawn again layer by layer so that only one
layer is ever on the device), ``TrainReference`` follows the first steps of a
training run (layer-by-layer backward, each leaf updated as soon as its
gradient exists, so no second copy of the gradients).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

import weights as W

HIGHEST = lax.Precision.HIGHEST
F32 = jnp.float32


# --------------------------------------------------------------------------- #
# the equations
# --------------------------------------------------------------------------- #
def fake_quant(x, quant):
    """Identity for the reference; float8 e4m3 with a per-tensor scale for the
    control (amax maps to the format's largest finite value, 448)."""
    if quant is None:
        return x
    if quant != "fp8":
        raise ValueError(f"unknown control precision {quant!r}")
    scale = jnp.maximum(jnp.max(jnp.abs(x)), F32(1e-30)) / F32(448.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _einsum(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST, preferred_element_type=F32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 3))
def _quantized_mm(spec, a, b, quant):
    return _einsum(spec, fake_quant(a, quant), fake_quant(b, quant))


def _quantized_mm_fwd(spec, a, b, quant):
    aq, bq = fake_quant(a, quant), fake_quant(b, quant)
    return _einsum(spec, aq, bq), (aq, bq)


def _quantized_mm_bwd(spec, quant, saved, g):
    # the backward's two matmuls take the rounded operands and the rounded
    # cotangent, as a low-precision training path does (a plain cast would
    # push the cotangent through float8 unscaled and flush it to nought)
    _, vjp = jax.vjp(lambda x, y: _einsum(spec, x, y), *saved)
    return vjp(fake_quant(g, quant))


_quantized_mm.defvjp(_quantized_mm_fwd, _quantized_mm_bwd)


def mm(spec, a, b, quant):
    """Every matmul of the model: float32 at HIGHEST for the reference; for
    the control both operands rounded first, in the backward too."""
    if quant is None:
        return _einsum(spec, a, b)
    return _quantized_mm(spec, a, b, quant)


def rmsnorm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + F32(eps)) * w


def rope_tables(length, head_dim, theta):
    inv = 1.0 / (F32(theta) ** (jnp.arange(0, head_dim, 2, dtype=F32)
                                / F32(head_dim)))
    freqs = jnp.outer(jnp.arange(length, dtype=F32), inv)
    emb = jnp.concatenate([freqs, freqs], -1)            # (T, D)
    return jnp.cos(emb), jnp.sin(emb)


def apply_rope(x, cos, sin):
    """x (B, T, heads, D); rotate-half pairing (HF Llama)."""
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos[None, :, None, :] + rot * sin[None, :, None, :]


def attention(q, k, v, quant):
    """Causal softmax attention. q (B, T, H, D), k and v (B, T, KV, D); query
    head h reads KV head h // (H // KV). One (row, KV head) at a time so the
    (T, T) scores of only one group are alive; recomputed in the backward."""
    B, T, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, T, KV, G, D).transpose(0, 2, 3, 1, 4).reshape(B * KV, G, T, D)
    kg = k.transpose(0, 2, 1, 3).reshape(B * KV, T, D)
    vg = v.transpose(0, 2, 1, 3).reshape(B * KV, T, D)
    causal = jnp.tril(jnp.ones((T, T), bool))

    @jax.checkpoint
    def one(args):
        qi, ki, vi = args                                  # (G,T,D) (T,D) (T,D)
        s = mm("gtd,sd->gts", qi, ki, quant) / F32(math.sqrt(D))
        s = jnp.where(causal[None], s, F32(-1e30))
        p = jax.nn.softmax(s, axis=-1)
        return mm("gts,sd->gtd", p, vi, quant)

    out = lax.map(one, (qg, kg, vg))                       # (B*KV, G, T, D)
    return out.reshape(B, KV, G, T, D).transpose(0, 3, 1, 2, 4).reshape(B, T, H * D)


def block(p, h, cfg, quant):
    """One decoder layer. ``p`` holds the layer's nine leaves by short name."""
    B, T, _ = h.shape
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or cfg["hidden_size"] // heads
    eps = cfg["rms_norm_eps"]
    x = rmsnorm(h, p["input_layernorm"], eps)
    q = mm("bth,hd->btd", x, p["q_proj"], quant).reshape(B, T, heads, hd)
    k = mm("bth,hd->btd", x, p["k_proj"], quant).reshape(B, T, kv, hd)
    v = mm("bth,hd->btd", x, p["v_proj"], quant).reshape(B, T, kv, hd)
    cos, sin = rope_tables(T, hd, cfg["rope_theta"])
    q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    h = h + mm("btd,dh->bth", attention(q, k, v, quant), p["o_proj"], quant)
    x = rmsnorm(h, p["post_attention_layernorm"], eps)
    gate = mm("bth,hi->bti", x, p["gate_proj"], quant)
    up = mm("bth,hi->bti", x, p["up_proj"], quant)
    return h + mm("bti,ih->bth", jax.nn.silu(gate) * up, p["down_proj"], quant)


def head_logits(norm_w, head_w, h, cfg, quant):
    return mm("...h,hv->...v", rmsnorm(h, norm_w, cfg["rms_norm_eps"]), head_w,
              quant)


def token_mean_loss(norm_w, head_w, h, labels, cfg, quant):
    logits = head_logits(norm_w, head_w, h, cfg, quant)
    logp = jax.nn.log_softmax(logits, -1)
    picked = jnp.take_along_axis(logp, labels[..., None].astype(jnp.int32), -1)
    return -jnp.mean(picked)


def _short(name):
    return name.rsplit(".", 2)[-2]


def _norm(x):
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(F32))))


# --------------------------------------------------------------------------- #
# serving: score served tokens
# --------------------------------------------------------------------------- #
class ServeReference:
    """Teacher-forced forward over prompt + served tokens, layer by layer with
    the layer's weights drawn again from the seed inside the program: one layer
    of float32 weights is the most the device ever holds."""

    def __init__(self, seed, cfg, quant=None):
        self.cfg, self.quant = dict(cfg), quant
        self.key = W.seed_key(seed)
        self.specs = W.leaf_specs(cfg)
        self.std = float(cfg.get("initializer_range", 0.02))
        self._layer = jax.jit(self._layer_impl)
        self._embed = jax.jit(self._embed_impl)
        self._head = jax.jit(self._head_impl)

    def _leaf(self, key, index, like):
        name, shape, kind = self.specs[like]
        # rounded to bfloat16 as served, then widened: the same values
        return W.leaf(key, index, shape, kind, self.std,
                      jnp.bfloat16).astype(F32)

    # the seed's key is an ARGUMENT of the three programs: closed over, it
    # would be a constant of each, and every new seed would compile them anew

    def _embed_impl(self, key, tokens):
        return jnp.take(self._leaf(key, 0, 0), tokens, axis=0)

    def _layer_impl(self, key, h, base):
        # every layer has layer 0's shapes; ``base`` (traced) picks its stream
        p = {_short(self.specs[1 + j][0]): self._leaf(key, base + j, 1 + j)
             for j in range(9)}
        return block(p, h, self.cfg, self.quant)

    def _head_impl(self, key, h, positions, query):
        n = len(self.specs)
        rows = jnp.take_along_axis(h, positions[..., None], axis=1)  # (N,R,H)
        logits = head_logits(self._leaf(key, n - 2, n - 2),
                             self._leaf(key, n - 1, n - 1),
                             rows, self.cfg, self.quant)
        best = jnp.max(logits, -1)
        at = jnp.take_along_axis(logits, query[..., None], -1)[..., 0]
        return best - at, jnp.argmax(logits, -1).astype(jnp.int32)

    def gaps(self, tokens, positions, query):
        """``tokens`` (N, T) prompt + served tokens, padded at the end;
        ``positions`` (N, R) the positions whose next token was served;
        ``query`` (N, R) the tokens to score there. Returns (gap, argmax), each
        (N, R): how far the queried token's logit lies below this forward's
        best, and this forward's own first choice."""
        tokens = jnp.asarray(tokens, jnp.int32)
        h = self._embed(self.key, tokens)
        for layer in range(self.cfg["num_hidden_layers"]):
            h = self._layer(self.key, h, jnp.int32(1 + 9 * layer))
        return self._head(self.key, h, jnp.asarray(positions, jnp.int32),
                          jnp.asarray(query, jnp.int32))


# --------------------------------------------------------------------------- #
# training: follow the first steps
# --------------------------------------------------------------------------- #
def update_rule(opt):
    """``(p, g, state, t) -> (p, state)`` in float32 for one leaf, from the
    optimizer's published equations. ``state`` is a tuple of arrays like p."""
    name = opt["name"]
    lr = F32(opt["learning_rate"])
    if name == "SGD":
        return 0, lambda p, g, s, t: (p - lr * g, s)
    if name == "Momentum":
        mu = F32(opt.get("momentum", 0.9))

        def rule(p, g, s, t):
            v = mu * s[0] + g
            return p - lr * v, (v,)
        return 1, rule
    if name == "AdamW":
        b1, b2 = F32(opt.get("beta1", 0.9)), F32(opt.get("beta2", 0.999))
        eps, wd = F32(opt.get("epsilon", 1e-8)), F32(opt.get("weight_decay", 0.01))

        def rule(p, g, s, t):
            p = p * (1 - lr * wd)
            m = b1 * s[0] + (1 - b1) * g
            v = b2 * s[1] + (1 - b2) * g * g
            mhat = m / (1 - b1 ** t)
            vhat = v / (1 - b2 ** t)
            return p - lr * mhat / (jnp.sqrt(vhat) + eps), (m, v)
        return 2, rule
    raise ValueError(f"no reference update rule for optimizer {name!r}")


class TrainReference:
    """Float32 parameters from the seed, the loss and its gradients layer by
    layer, and the optimizer's update applied leaf by leaf. ``rows`` keeps only
    the first rows of each batch (the fault "half of the batch left out")."""

    def __init__(self, seed, cfg, optimizer, quant=None, rows=None):
        self.cfg, self.quant, self.rows = dict(cfg), quant, rows
        self.seed = seed
        self.specs = W.leaf_specs(cfg)
        self.std = float(cfg.get("initializer_range", 0.02))
        n_state, rule = update_rule(optimizer)
        self.params = {k: v.astype(F32) for k, v in
                       W.make_all(seed, cfg, jnp.bfloat16).items()}
        self.state = {k: tuple(jnp.zeros_like(v) for _ in range(n_state))
                      for k, v in self.params.items()}
        self.t = 0
        self.grad_norms = None          # per leaf, of the first step
        cfg_, q = self.cfg, quant

        def layer_fwd(p, h):
            return block(p, h, cfg_, q)

        def layer_bwd(p, h, g_out):
            _, vjp = jax.vjp(lambda p_, h_: block(p_, h_, cfg_, q), p, h)
            g_p, g_h = vjp(g_out)
            return g_h, g_p

        def head_bwd(norm_w, head_w, h, labels):
            loss, grads = jax.value_and_grad(
                lambda n, w, x: token_mean_loss(n, w, x, labels, cfg_, q),
                (0, 1, 2))(norm_w, head_w, h)
            return loss, grads

        def embed_bwd(table, ids, g_h):
            return jnp.zeros_like(table).at[ids].add(g_h)

        def apply(p, g, s, t):
            new_p, new_s = rule(p, g, s, t)
            return new_p, new_s, _norm(g)

        self._layer_fwd = jax.jit(layer_fwd)
        self._layer_bwd = jax.jit(layer_bwd)
        self._head_bwd = jax.jit(head_bwd)
        self._embed_bwd = jax.jit(embed_bwd)
        self._apply = jax.jit(apply, donate_argnums=(0, 2))

    def _layer_params(self, layer):
        return {_short(self.specs[i][0]): self.params[self.specs[i][0]]
                for i in W.layer_indices(self.cfg, layer)}

    def _update(self, name, grad, norms):
        self.params[name], self.state[name], norms[name] = self._apply(
            self.params[name], grad, self.state[name], F32(self.t))

    def step(self, ids, labels):
        """One optimizer step on one batch; returns the loss (a float)."""
        ids = jnp.asarray(ids, jnp.int32)
        labels = jnp.asarray(labels, jnp.int32)
        if self.rows is not None:
            ids, labels = ids[:self.rows], labels[:self.rows]
        self.t += 1
        norms = {}
        L = self.cfg["num_hidden_layers"]
        h = jnp.take(self.params["llama.embed_tokens.weight"], ids, axis=0)
        inputs = []
        for layer in range(L):
            inputs.append(h)
            h = self._layer_fwd(self._layer_params(layer), h)
        loss, (g_norm, g_head, g_h) = self._head_bwd(
            self.params["llama.norm.weight"], self.params["lm_head.weight"],
            h, labels)
        self._update("llama.norm.weight", g_norm, norms)
        self._update("lm_head.weight", g_head, norms)
        for layer in reversed(range(L)):
            g_h, g_p = self._layer_bwd(self._layer_params(layer),
                                       inputs.pop(), g_h)
            for i in W.layer_indices(self.cfg, layer):
                name = self.specs[i][0]
                self._update(name, g_p[_short(name)], norms)
        g_table = self._embed_bwd(self.params["llama.embed_tokens.weight"],
                                  ids, g_h)
        self._update("llama.embed_tokens.weight", g_table, norms)
        if self.grad_norms is None:
            self.grad_norms = {k: float(v) for k, v in norms.items()}
        return float(loss)

    def change_norms(self):
        """Per leaf, the norm of (parameters now - parameters from the seed)."""
        return W.change_norms(self.seed, self.cfg, self.params)
