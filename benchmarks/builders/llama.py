"""Builder ``llama``: the program's Llama-shaped decoder (``LlamaForCausalLM``
through one ``LlamaConfig``) at a configuration's sizes, and its weights from
the seed (``benchmarks/weights.py``, whose names are this model's state-dict
names). The configuration's ``model`` group holds the constructor's other
arguments (``dtype``, ``recompute``, ``use_flash_attention``)."""
from __future__ import annotations

import weights as W


def construct(cfg):
    """The program's model at the configuration's sizes.

    The constructor draws every weight on the host (``Normal.__call__``), in
    float32, whatever it is given; it runs with the CPU as jax's default
    device, so that the draw is never shipped to the chip. The driver then
    replaces every value with ``weights``'."""
    import jax

    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    opts = dict(cfg.get("model", {}))
    dtype = opts.pop("dtype", "bfloat16")
    heads = cfg["num_attention_heads"]
    if cfg.get("head_dim") and cfg["head_dim"] * heads != cfg["hidden_size"]:
        raise ValueError("LlamaConfig derives head_dim as hidden_size / heads")
    lcfg = LlamaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=heads,
        num_key_value_heads=cfg["num_key_value_heads"],
        max_position_embeddings=cfg["max_position_embeddings"],
        initializer_range=cfg.get("initializer_range", 0.02),
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        tie_word_embeddings=cfg.get("tie_word_embeddings", False),
        dtype=dtype, **opts)
    with jax.default_device(jax.devices("cpu")[0]):
        model = LlamaForCausalLM(lcfg)
        model.to(dtype=dtype)
    return model


def weights(seed, cfg, dtype):
    """``{state-dict name: array}``, every leaf made on the device from the
    seed in one jitted call."""
    return W.make_all(seed, cfg, dtype)
