"""Seeded weights of a Llama-shaped decoder, as a pure function of the seed.

The benchmark makes the weights, not the program: ``leaf(seed, index, ...)``
gives the same array wherever it is called, so the driver loads them into the
program's model and the reference draws the very same values again, layer by
layer, after the program's state is gone. Names are the program's state-dict
names; a name the model does not know is an error in the driver.

Projection weights are N(0, std) rounded to ``dtype`` (bf16: what is served and
what the fp32 masters start from), norm weights are 1. Shapes follow the paddle
convention ``[in, out]``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def leaf_specs(cfg):
    """``[(name, shape, kind)]`` in a fixed order; the position is the leaf's
    index into the seed's stream. ``kind`` is ``normal`` or ``ones``."""
    h, inter = cfg["hidden_size"], cfg["intermediate_size"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or h // heads
    specs = [("llama.embed_tokens.weight", (cfg["vocab_size"], h), "normal")]
    for i in range(cfg["num_hidden_layers"]):
        p = f"llama.layers.{i}."
        specs += [
            (p + "self_attn.q_proj.weight", (h, heads * hd), "normal"),
            (p + "self_attn.k_proj.weight", (h, kv * hd), "normal"),
            (p + "self_attn.v_proj.weight", (h, kv * hd), "normal"),
            (p + "self_attn.o_proj.weight", (heads * hd, h), "normal"),
            (p + "mlp.gate_proj.weight", (h, inter), "normal"),
            (p + "mlp.up_proj.weight", (h, inter), "normal"),
            (p + "mlp.down_proj.weight", (inter, h), "normal"),
            (p + "input_layernorm.weight", (h,), "ones"),
            (p + "post_attention_layernorm.weight", (h,), "ones"),
        ]
    specs += [("llama.norm.weight", (h,), "ones"),
              ("lm_head.weight", (h, cfg["vocab_size"]), "normal")]
    return specs


def seed_key(seed):
    """A key from any whole number up to a little over 2**31: the low 31 bits
    seed it, the bits above are folded in."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, seed >> 31)


def leaf(key, index, shape, kind, std, dtype):
    """One leaf, traceable: ``key`` is ``seed_key(seed)``."""
    if kind == "ones":
        return jnp.ones(shape, dtype)
    draw = jax.random.normal(jax.random.fold_in(key, index), shape,
                             jnp.float32)
    return (draw * jnp.float32(std)).astype(dtype)


def make_all(seed, cfg, dtype=jnp.bfloat16):
    """Every leaf on the device in one jitted call: ``{name: array}``."""
    specs = leaf_specs(cfg)
    std = float(cfg.get("initializer_range", 0.02))

    def build(key):
        return {name: leaf(key, i, shape, kind, std, dtype)
                for i, (name, shape, kind) in enumerate(specs)}

    return jax.jit(build)(seed_key(seed))


def layer_indices(cfg, layer):
    """Indices of ``leaf_specs`` that belong to decoder layer ``layer``."""
    return list(range(1 + 9 * layer, 1 + 9 * (layer + 1)))


def matmul_param_count(cfg):
    """Parameters that take part in a matmul: the layers' projections and the
    LM head. The embedding table is a lookup and the norms are elementwise."""
    total = 0
    for name, shape, kind in leaf_specs(cfg):
        if kind == "normal" and name != "llama.embed_tokens.weight":
            total += shape[0] * shape[1]
    return total


def change_norms(seed, cfg, arrays):
    """Per leaf, ``|| arrays[name] - leaf drawn from the seed ||`` in float32:
    how far training has moved each parameter from where the seed put it,
    without keeping a second copy of the start. One small program per distinct
    leaf shape; the leaf's index is traced."""
    key = seed_key(seed)
    std = float(cfg.get("initializer_range", 0.02))

    # the key is an argument: closed over, every new seed would compile anew
    @functools.partial(jax.jit, static_argnums=(3, 4))
    def change(now, key, index, shape, kind):
        start = leaf(key, index, shape, kind, std, jnp.bfloat16)
        diff = now.astype(jnp.float32) - start.astype(jnp.float32)
        return jnp.sqrt(jnp.sum(jnp.square(diff)))

    out = {name: change(arrays[name], key, jnp.int32(i), tuple(shape), kind)
           for i, (name, shape, kind) in enumerate(leaf_specs(cfg))}
    return {k: float(v) for k, v in out.items()}


def norms(arrays):
    """Per leaf, the float32 Euclidean norm, read back in one transfer."""
    fn = jax.jit(lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))))
    out = {k: fn(v) for k, v in arrays.items()}
    return {k: float(v) for k, v in out.items()}
