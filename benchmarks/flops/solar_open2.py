"""Operations and bytes of the Solar-Open2-shaped decoder cut to one chip's
share, from its shapes alone.

Model FLOPs of what THIS chip computes: per token 2 x the matmul parameters it
multiplies (the mixers' projections and gates, the router over all published
experts, the shared expert, the head over the vocabulary slice, and of the
held experts the share a token reaches in expectation: experts per token x
held / routed of them, 8 x 40 / 320 = one expert a layer; the embedding is a
lookup, the convolutions' taps and the norms elementwise); per position
attended to, in each GQA layer, QK^T and PV (4 x heads x head_dim); per token
in each KDA layer the recurrence's own products: S^T k, the rank-one update
and S^T q (2 x heads x head_dim x head_dim each; the decay's multiply is not
counted), whatever form computes them.
"""
from __future__ import annotations

from builders import solar_open2 as B

LINEAR_RUNS = "paddle_tpu_serving_linear_runs_total"
LINEAR_TOKENS = "paddle_tpu_serving_linear_tokens_total"
EXPERT_PAIRS = "paddle_tpu_serving_expert_pairs_total"


def _layers(cfg):
    """(KDA layers, GQA layers) held."""
    n = cfg["num_hidden_layers"]
    gqa = sum(B.is_gqa(cfg, i) for i in range(n))
    return n - gqa, gqa


def _state_elements(cfg):
    lin = cfg["linear_attn_config"]
    return lin["num_heads"] * lin["head_dim"] ** 2


def recurrence_flops_per_token(cfg):
    """One KDA layer's recurrence for one token."""
    return 6 * _state_elements(cfg)


def matmul_params_per_token(cfg):
    """Matmul parameters one token is multiplied by on this chip, the held
    experts counted by the share of them a token reaches in expectation."""
    held, m = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    expert = 3 * cfg["hidden_size"] * m
    reached = cfg["num_experts_per_tok"] * held / B.routed_experts(cfg)
    return B.parameter_count(cfg, matmul_only=True) \
        - cfg["num_hidden_layers"] * (held - reached) * expert


def forward_flops(cfg, tokens, sum_context):
    """Forward of ``tokens`` tokens whose context lengths (positions attended
    to, itself included) add up to ``sum_context``."""
    kda, gqa = _layers(cfg)
    attn = 4 * cfg["num_attention_heads"] * cfg["head_dim"] * sum_context \
        * gqa
    return (2 * matmul_params_per_token(cfg)
            + recurrence_flops_per_token(cfg) * kda) * tokens + attn


def request_forward_flops(cfg, prompt_len, output_len):
    """A served request feeds prompt + output - 1 tokens through the model (the
    last token served is not fed back), token i attending to i + 1 positions."""
    fed = prompt_len + output_len - 1
    return forward_flops(cfg, fed, fed * (fed + 1) // 2)


def kda_costs(cfg, engine, counters):
    """``(flops, bytes)`` that ALL calls of the KDA layers' recurrence in a
    traced slice need at the least, from what the scheduler counted over that
    slice (a layer once), whatever implements the recurrence; None where
    nothing was counted. Every RUN (one slot's consecutive tokens of one
    step: a prefill chunk, a decode lane, an iteration of a burst) reads its
    slot's float32 state once and writes it once; every TOKEN reads its q and
    k rows (heads x head_dim each), its v row and writes its output row
    (heads x head_dim each) at the activations' item size, and reads its
    decay (heads x head_dim) and beta (heads) in float32; times the KDA
    layers. FLOPs: the recurrence's products a token
    (``recurrence_flops_per_token``)."""
    import jax.numpy as jnp

    runs = sum(int(v) for v in counters.get(LINEAR_RUNS, {}).values())
    tokens = sum(int(v) for v in counters.get(LINEAR_TOKENS, {}).values())
    if not tokens:
        return None
    kda, _ = _layers(cfg)
    lin = cfg["linear_attn_config"]
    heads, d = lin["num_heads"], lin["head_dim"]
    el = jnp.dtype(cfg["torch_dtype"]).itemsize
    row = 4 * heads * d * el + heads * d * 4 + heads * 4
    return (tokens * kda * recurrence_flops_per_token(cfg),
            kda * (runs * 2 * _state_elements(cfg) * 4 + tokens * row))


def held_experts_costs(cfg, engine, counters):
    """``(flops, bytes)`` that ALL grouped products of the expert layers in a
    traced slice need at the least, from what the steps' programs counted over
    that slice: every held expert that got a pair (a layer and forward pass
    each) reads its three matrices once; every pair on a held expert reads a
    row of hidden size and writes one, and costs three products of hidden x
    expert width. None where nothing was counted. (The shared expert is a
    plain product, not one of these.)"""
    import jax.numpy as jnp

    series = counters.get(EXPERT_PAIRS, {})
    pairs = int(series.get("where=held", 0))
    hit = int(series.get("where=experts_hit", 0))
    if not pairs:
        return None
    el = jnp.dtype(cfg["torch_dtype"]).itemsize
    h, m = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return (pairs * 3 * 2 * h * m,
            (hit * 3 * h * m + pairs * 2 * h) * el)
