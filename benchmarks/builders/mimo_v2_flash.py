"""Builder ``mimo_v2_flash``: the program's MiMo-V2-Flash-shaped decoder
(``paddle_tpu.models.mimo_v2``) at a configuration's sizes, holding ONE chip's
share of the routed experts, and its weights from the seed.

The configuration's file gives the experts HELD under ``n_routed_experts`` and
the router's width under ``published.n_routed_experts``; the held ones are the
first ``n_routed_experts`` (``expert_offset`` 0: with seeded weights every
slice of the experts is like every other).

``leaf_specs`` is the one list of leaves: the names are the model's state-dict
names, the position is the leaf's index into the seed's stream, so the
reference (``benchmarks/reference/mimo_v2_flash.py``) draws the very same
values again, layer by layer, after the program's state is gone. Projections
and expert matrices are N(0, initializer_range), norms 1, and the two leaves a
program could forget without a shape error are drawn NON-ZERO: the window
layers' sink logits N(0, 1) (scores have a spread of ~1.6) and the router's
selection bias N(0, 0.02). The bias is small on purpose: in the published model
it is what BALANCES the experts' load, and a random one unbalances it. At
N(0, 0.1) half of the 16 held experts got no pair in a step of 2,560 pairs and
the share of all pairs that fell on them went from 4.4% to 7.4% with the seed
(my chip runs, PR 32), so the step's time followed the seed; at N(0, 0.02)
every held expert is reached in every mixed step, the load's spread across
experts is 0.4 of its mean, and a program that forgets the bias still chooses
other experts for a large share of the tokens.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import weights as W

SINK_STD = 1.0
SELECTION_BIAS_STD = 0.02


def routed_experts(cfg):
    """The router's width: the published count where the file holds a share."""
    return int(cfg.get("published", {}).get("n_routed_experts",
                                            cfg["n_routed_experts"]))


def layer_specs(cfg, i):
    """``[(name, shape, kind, std)]`` of decoder layer ``i``."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    std = float(cfg.get("initializer_range", 0.02))
    window = bool(cfg["hybrid_layer_pattern"][i])
    kv = cfg["swa_num_key_value_heads" if window else "num_key_value_heads"]
    dk = cfg["swa_head_dim" if window else "head_dim"]
    dv = cfg["swa_v_head_dim" if window else "v_head_dim"]
    p = f"model.layers.{i}."
    specs = [
        (p + "input_layernorm.weight", (h,), "ones", 0.0),
        (p + "self_attn.q_proj.weight", (h, heads * dk), "normal", std),
        (p + "self_attn.k_proj.weight", (h, kv * dk), "normal", std),
        (p + "self_attn.v_proj.weight", (h, kv * dv), "normal", std),
        (p + "self_attn.o_proj.weight", (heads * dv, h), "normal", std),
    ]
    if cfg["add_swa_attention_sink_bias" if window
           else "add_full_attention_sink_bias"]:
        specs.append((p + "self_attn.attention_sink_bias", (heads,), "normal",
                      SINK_STD))
    specs.append((p + "post_attention_layernorm.weight", (h,), "ones", 0.0))
    if cfg["moe_layer_freq"][i]:
        held, m = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
        specs += [
            (p + "mlp.gate.weight", (h, routed_experts(cfg)), "normal", std),
            (p + "mlp.gate.e_score_correction_bias", (routed_experts(cfg),),
             "normal", SELECTION_BIAS_STD),
            (p + "mlp.experts.gate_proj", (held, h, m), "normal", std),
            (p + "mlp.experts.up_proj", (held, h, m), "normal", std),
            (p + "mlp.experts.down_proj", (held, m, h), "normal", std),
        ]
    else:
        m = cfg["intermediate_size"]
        specs += [
            (p + "mlp.gate_proj.weight", (h, m), "normal", std),
            (p + "mlp.up_proj.weight", (h, m), "normal", std),
            (p + "mlp.down_proj.weight", (m, h), "normal", std),
        ]
    return specs


def leaf_specs(cfg):
    """Every leaf in a fixed order: embedding, the layers, final norm, head."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    std = float(cfg.get("initializer_range", 0.02))
    specs = [("model.embed_tokens.weight", (v, h), "normal", std)]
    for i in range(cfg["num_hidden_layers"]):
        specs += layer_specs(cfg, i)
    return specs + [("model.norm.weight", (h,), "ones", 0.0),
                    ("lm_head.weight", (h, v), "normal", std)]


def layer_base(cfg, i):
    """Index of layer ``i``'s first leaf in ``leaf_specs``."""
    return 1 + sum(len(layer_specs(cfg, j)) for j in range(i))


def parameter_count(cfg, matmul_only=False):
    """Parameters held here; with ``matmul_only`` those that multiply an
    activation (no embedding lookup, norm, sink or selection bias)."""
    total = 0
    for name, shape, kind, _ in leaf_specs(cfg):
        n = 1
        for d in shape:
            n *= d
        if not matmul_only or (len(shape) > 1
                               and name != "model.embed_tokens.weight"):
            total += n
    return total


def construct(cfg):
    """The program's model at the configuration's sizes, holding the first
    ``n_routed_experts`` of ``published.n_routed_experts`` experts. Nothing is
    drawn (``initializer_range`` 0: zeros, made with the CPU as jax's default
    device): the driver replaces every value with ``weights``'."""
    from paddle_tpu.models.mimo_v2 import MiMoV2Config, MiMoV2ForCausalLM

    dtype = cfg.get("model", {}).get("dtype", "bfloat16")
    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
            "head_dim", "v_head_dim", "swa_num_key_value_heads",
            "swa_head_dim", "swa_v_head_dim", "sliding_window",
            "hybrid_layer_pattern", "moe_layer_freq", "rope_theta",
            "swa_rope_theta", "partial_rotary_factor", "attention_value_scale",
            "add_swa_attention_sink_bias", "add_full_attention_sink_bias",
            "moe_intermediate_size", "num_experts_per_tok",
            "layernorm_epsilon", "max_position_embeddings")
    mcfg = MiMoV2Config(
        **{k: cfg[k] for k in keys}, n_routed_experts=routed_experts(cfg),
        n_held_experts=cfg["n_routed_experts"], expert_offset=0,
        initializer_range=0.0, dtype=dtype)
    with jax.default_device(jax.devices("cpu")[0]):
        return MiMoV2ForCausalLM(mcfg)


def leaf(key, index, spec, dtype):
    """One leaf of ``leaf_specs``, traceable: ``key`` is ``W.seed_key(seed)``."""
    _, shape, kind, std = spec
    return W.leaf(key, index, shape, kind, std, dtype)


def weights(seed, cfg, dtype):
    """``{state-dict name: array}``, every leaf made on the device from the
    seed in one jitted call."""
    specs = leaf_specs(cfg)

    def build(key):
        return {spec[0]: leaf(key, i, spec, jnp.dtype(dtype))
                for i, spec in enumerate(specs)}

    return jax.jit(build)(W.seed_key(seed))
