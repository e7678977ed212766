"""Builder ``solar_open2``: the program's Solar-Open2-shaped decoder
(``paddle_tpu.models.solar_open2``) at a configuration's sizes, holding ONE
chip's share of the routed experts and of the vocabulary, cut in depth to the
first ``num_hidden_layers`` layers, and its weights from the seed.

The configuration's file gives the experts HELD under ``n_routed_experts`` and
the router's width under ``published.n_routed_experts``; the held ones are the
first ``n_routed_experts`` (``expert_offset`` 0: with seeded weights every
slice of the experts is like every other). Layer ``i`` is a GQA layer where
``i in gqa_layers``, a KDA layer elsewhere.

``leaf_specs`` is the one list of leaves: the names are the model's state-dict
names, the position is the leaf's index into the seed's stream, so the
reference (``benchmarks/reference/solar_open2.py``) draws the very same values
again, layer by layer, after the program's state is gone. Projections, expert
matrices and the router are N(0, initializer_range). Every leaf that a
program could forget without a shape error is drawn where forgetting it
changes the result:

- ``A_log = log(U(1, 16))`` a head and ``dt_bias`` the inverse softplus of a
  log-uniform step in [0.001, 0.1] a key channel, as the layer's published
  initialisation (kind ``a_log`` / ``dt_bias``): a token's decay then lies
  between ~0.1 and 0.999, channel by channel;
- the convolutions' taps N(0, 1 / sqrt(kernel)) (kind ``normal`` at that
  spread);
- every norm's weight 1 + N(0, 0.1) (kind ``norm``): the two norms before the
  sublayers, the KDA output norm and the final one;
- the router's selection bias N(0, 0.02), small as in
  ``builders/mimo_v2_flash.py``: large enough that a program that forgets it
  chooses other experts for many tokens, small enough that every held
  expert's load stays near its mean.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

import weights as W
from builders.olmo_hybrid import NORM_STD, leaf

SELECTION_BIAS_STD = 0.02


def routed_experts(cfg):
    """The router's width: the published count where the file holds a share."""
    return int(cfg.get("published", {}).get("n_routed_experts",
                                            cfg["n_routed_experts"]))


def is_gqa(cfg, i):
    return i in cfg["gqa_layers"]


def layer_specs(cfg, i):
    """``[(name, shape, kind, std)]`` of decoder layer ``i``."""
    h = cfg["hidden_size"]
    std = float(cfg.get("initializer_range", 0.02))
    p = f"model.layers.{i}."
    a = p + "self_attn."
    specs = [(p + "input_layernorm.weight", (h,), "norm", NORM_STD)]
    if is_gqa(cfg, i):
        q = cfg["num_attention_heads"] * cfg["head_dim"]
        kv = cfg["num_key_value_heads"] * cfg["head_dim"]
        specs += [
            (a + "q_proj.weight", (h, q), "normal", std),
            (a + "k_proj.weight", (h, kv), "normal", std),
            (a + "v_proj.weight", (h, kv), "normal", std),
            (a + "o_proj.weight", (q, h), "normal", std),
        ]
        if cfg["use_gqa_gate"]:
            specs.append((a + "g_proj.weight", (h, q), "normal", std))
    else:
        lin = cfg["linear_attn_config"]
        heads, d = lin["num_heads"], lin["head_dim"]
        width, taps = heads * d, lin["short_conv_kernel_size"]
        tap_std = 1.0 / math.sqrt(taps)
        specs += [
            (a + "q_proj.weight", (h, width), "normal", std),
            (a + "k_proj.weight", (h, width), "normal", std),
            (a + "v_proj.weight", (h, width), "normal", std),
            (a + "f_a_proj.weight", (h, d), "normal", std),
            (a + "f_b_proj.weight", (d, width), "normal", std),
            (a + "b_proj.weight", (h, heads), "normal", std),
            (a + "g_a_proj.weight", (h, d), "normal", std),
            (a + "g_b_proj.weight", (d, width), "normal", std),
            (a + "o_proj.weight", (width, h), "normal", std),
            (a + "q_conv1d.weight", (taps, width), "normal", tap_std),
            (a + "k_conv1d.weight", (taps, width), "normal", tap_std),
            (a + "v_conv1d.weight", (taps, width), "normal", tap_std),
            (a + "A_log", (heads,), "a_log", 0.0),
            (a + "dt_bias", (width,), "dt_bias", 0.0),
            (a + "o_norm.weight", (d,), "norm", NORM_STD),
        ]
    held, m = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    shared = cfg["n_shared_experts"] * m
    specs += [
        (p + "post_attention_layernorm.weight", (h,), "norm", NORM_STD),
        (p + "mlp.gate.weight", (h, routed_experts(cfg)), "normal", std),
        (p + "mlp.gate.e_score_correction_bias", (routed_experts(cfg),),
         "normal", SELECTION_BIAS_STD),
        (p + "mlp.experts.gate_proj", (held, h, m), "normal", std),
        (p + "mlp.experts.up_proj", (held, h, m), "normal", std),
        (p + "mlp.experts.down_proj", (held, m, h), "normal", std),
    ]
    if shared:
        s = p + "mlp.shared_experts."
        specs += [(s + "gate_proj.weight", (h, shared), "normal", std),
                  (s + "up_proj.weight", (h, shared), "normal", std),
                  (s + "down_proj.weight", (shared, h), "normal", std)]
    return specs


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
    tap, decay constant or selection bias; the held experts all)."""
    total = 0
    for name, shape, kind, _ in leaf_specs(cfg):
        if matmul_only and (kind != "normal" or len(shape) == 1
                            or "conv1d" in name
                            or name == "model.embed_tokens.weight"):
            continue
        total += math.prod(shape)
    return total


def construct(cfg):
    """The program's model at the configuration's sizes, holding the first
    ``n_routed_experts`` of ``published.n_routed_experts`` experts. Nothing is
    drawn (``initializer_range`` 0: zeros, made with the CPU as jax's default
    device): the driver replaces every value with ``weights``'."""
    from paddle_tpu.models.solar_open2 import (SolarOpen2Config,
                                               SolarOpen2ForCausalLM)

    dtype = cfg.get("model", {}).get("dtype", "bfloat16")
    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "linear_attn_config", "gqa_layers", "use_gqa_gate", "use_rope",
            "partial_rotary_factor", "rope_theta", "kda_use_full_proj",
            "kda_allow_neg_eigval", "n_shared_experts", "num_experts_per_tok",
            "norm_topk_prob", "routed_scaling_factor",
            "first_k_dense_replace", "rms_norm_eps",
            "max_position_embeddings")
    mcfg = SolarOpen2Config(
        **{k: cfg[k] for k in keys}, n_routed_experts=routed_experts(cfg),
        n_held_experts=cfg["n_routed_experts"], expert_offset=0,
        initializer_range=0.0, dtype=dtype)
    with jax.default_device(jax.devices("cpu")[0]):
        return SolarOpen2ForCausalLM(mcfg)


def weights(seed, cfg, dtype):
    """``{state-dict name: array}``, every leaf made on the device from the
    seed in one jitted call (``leaf`` is Olmo-Hybrid's builder's: the same
    kinds of leaf, drawn the same way)."""
    specs = leaf_specs(cfg)

    def build(key):
        return {spec[0]: leaf(key, i, spec, jnp.dtype(dtype))
                for i, spec in enumerate(specs)}

    return jax.jit(build)(W.seed_key(seed))
