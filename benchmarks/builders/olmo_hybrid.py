"""Builder ``olmo_hybrid``: the program's Olmo-Hybrid-shaped decoder
(``paddle_tpu.models.olmo_hybrid``) at a configuration's sizes, cut in depth
to the first ``num_hidden_layers`` of the published ``layer_types``, and its
weights from the seed.

``leaf_specs`` is the one list of leaves: the names are the model's state-dict
names, the position is the leaf's index into the seed's stream, so the
reference (``benchmarks/reference/olmo_hybrid.py``) draws the very same values
again, layer by layer, after the program's state is gone. Projections are
N(0, initializer_range). Every leaf that a program could forget without a
shape error is drawn where forgetting it changes the result:

- ``A_log = log(U(1, 16))`` and ``dt_bias`` the inverse softplus of a
  log-uniform step in [0.001, 0.1], as the layer's published initialisation
  (kind ``a_log`` / ``dt_bias``): a token's decay ``exp(g)`` then lies
  between 0.2 and 0.999, so a state neither dies in a few tokens nor never
  forgets;
- the convolutions' taps N(0, 1 / sqrt(kernel)) (kind ``normal`` at that
  spread): no tap is near 0 or near 1;
- every norm's weight 1 + N(0, 0.1) (kind ``norm``): the q / k norms, the gated
  norm on the recurrence's output, the two norms on the sublayers' outputs and
  the final one.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

import weights as W

LINEAR = "linear_attention"
NORM_STD = 0.1
A_RANGE = (1.0, 16.0)
DT_RANGE = (1e-3, 0.1)


def layer_types(cfg):
    """The kinds of the layers held: the first ``num_hidden_layers`` of the
    published list."""
    return list(cfg["layer_types"])[:cfg["num_hidden_layers"]]


def layer_specs(cfg, i):
    """``[(name, shape, kind, std)]`` of decoder layer ``i``."""
    h, m = cfg["hidden_size"], cfg["intermediate_size"]
    std = float(cfg.get("initializer_range", 0.02))
    p = f"model.layers.{i}."
    if layer_types(cfg)[i] == LINEAR:
        heads = cfg["linear_num_value_heads"]
        kw = heads * cfg["linear_key_head_dim"]
        vw = heads * cfg["linear_value_head_dim"]
        taps = cfg["linear_conv_kernel_dim"]
        tap_std = 1.0 / math.sqrt(taps)
        a = p + "linear_attn."
        specs = [
            (a + "q_proj.weight", (h, kw), "normal", std),
            (a + "k_proj.weight", (h, kw), "normal", std),
            (a + "v_proj.weight", (h, vw), "normal", std),
            (a + "g_proj.weight", (h, vw), "normal", std),
            (a + "a_proj.weight", (h, heads), "normal", std),
            (a + "b_proj.weight", (h, heads), "normal", std),
            (a + "o_proj.weight", (vw, h), "normal", std),
            (a + "q_conv1d.weight", (taps, kw), "normal", tap_std),
            (a + "k_conv1d.weight", (taps, kw), "normal", tap_std),
            (a + "v_conv1d.weight", (taps, vw), "normal", tap_std),
            (a + "A_log", (heads,), "a_log", 0.0),
            (a + "dt_bias", (heads,), "dt_bias", 0.0),
            (a + "o_norm.weight", (cfg["linear_value_head_dim"],), "norm",
             NORM_STD),
        ]
    else:
        kv = cfg["num_key_value_heads"] * (h // cfg["num_attention_heads"])
        a = p + "self_attn."
        specs = [
            (a + "q_proj.weight", (h, h), "normal", std),
            (a + "k_proj.weight", (h, kv), "normal", std),
            (a + "v_proj.weight", (h, kv), "normal", std),
            (a + "o_proj.weight", (h, h), "normal", std),
            (a + "q_norm.weight", (h,), "norm", NORM_STD),
            (a + "k_norm.weight", (kv,), "norm", NORM_STD),
        ]
    return specs + [
        (p + "post_attention_layernorm.weight", (h,), "norm", NORM_STD),
        (p + "mlp.gate_proj.weight", (h, m), "normal", std),
        (p + "mlp.up_proj.weight", (h, m), "normal", std),
        (p + "mlp.down_proj.weight", (m, h), "normal", std),
        (p + "post_feedforward_layernorm.weight", (h,), "norm", NORM_STD),
    ]


def leaf_specs(cfg):
    """Every leaf in a fixed order: embedding, the layers, final norm, head."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    std = float(cfg.get("initializer_range", 0.02))
    specs = [("model.embed_tokens.weight", (v, h), "normal", std)]
    for i in range(cfg["num_hidden_layers"]):
        specs += layer_specs(cfg, i)
    return specs + [("model.norm.weight", (h,), "norm", NORM_STD),
                    ("lm_head.weight", (h, v), "normal", std)]


def layer_base(cfg, i):
    """Index of layer ``i``'s first leaf in ``leaf_specs``."""
    return 1 + sum(len(layer_specs(cfg, j)) for j in range(i))


def parameter_count(cfg, matmul_only=False):
    """Parameters held here; with ``matmul_only`` those that multiply an
    activation in a matrix product (no embedding lookup, norm, convolution
    tap or gate constant)."""
    total = 0
    for name, shape, kind, _ in leaf_specs(cfg):
        if matmul_only and (kind != "normal" or "conv1d" in name
                            or name == "model.embed_tokens.weight"):
            continue
        total += math.prod(shape)
    return total


def construct(cfg):
    """The program's model at the configuration's sizes. Nothing is drawn
    (``initializer_range`` 0: zeros, made with the CPU as jax's default
    device): the driver replaces every value with ``weights``'."""
    from paddle_tpu.models.olmo_hybrid import (OlmoHybridConfig,
                                               OlmoHybridForCausalLM)

    dtype = cfg.get("model", {}).get("dtype", "bfloat16")
    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
            "layer_types", "linear_num_key_heads", "linear_num_value_heads",
            "linear_key_head_dim", "linear_value_head_dim",
            "linear_conv_kernel_dim", "linear_allow_neg_eigval",
            "rms_norm_eps", "max_position_embeddings")
    mcfg = OlmoHybridConfig(**{k: cfg[k] for k in keys},
                            initializer_range=0.0, dtype=dtype)
    with jax.default_device(jax.devices("cpu")[0]):
        return OlmoHybridForCausalLM(mcfg)


def leaf(key, index, spec, dtype):
    """One leaf of ``leaf_specs``, traceable: ``key`` is ``W.seed_key(seed)``."""
    _, shape, kind, std = spec
    if kind == "normal":
        return W.leaf(key, index, shape, kind, std, dtype)
    if kind == "norm":
        return (1.0 + W.leaf(key, index, shape, "normal", std, jnp.float32)
                ).astype(dtype)
    u = jax.random.uniform(jax.random.fold_in(key, index), shape, jnp.float32)
    if kind == "a_log":
        lo, hi = A_RANGE
        return jnp.log(lo + (hi - lo) * u).astype(dtype)
    if kind == "dt_bias":
        lo, hi = (math.log(x) for x in DT_RANGE)
        step = jnp.exp(lo + (hi - lo) * u)
        return (step + jnp.log(-jnp.expm1(-step))).astype(dtype)
    raise ValueError(f"unknown kind of leaf {kind!r}")


def weights(seed, cfg, dtype):
    """``{state-dict name: array}``, every leaf made on the device from the
    seed in one jitted call."""
    specs = leaf_specs(cfg)

    def build(key):
        return {spec[0]: leaf(key, i, spec, jnp.dtype(dtype))
                for i, spec in enumerate(specs)}

    return jax.jit(build)(W.seed_key(seed))
