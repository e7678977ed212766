"""Operations and bytes of the Olmo-Hybrid-shaped decoder cut in depth, from
its shapes alone.

Model FLOPs: per token 2 x the matmul parameters it is multiplied by (the
layers' projections, the MLPs and the head over the whole vocabulary; the
embedding is a lookup, the convolutions' taps and the norms elementwise); per
position attended to, in each FULL layer, QK^T and PV (4 x heads x head_dim);
per token in each LINEAR layer the recurrence's own products: S^T k, the
rank-one update and S^T q (2 x heads x key_dim x value_dim each; the decay's
multiply is not counted), whatever form computes them.
"""
from __future__ import annotations

from builders import olmo_hybrid as B

ATTN_KIND_BLOCKS = "paddle_tpu_serving_attn_kind_blocks_total"
LINEAR_RUNS = "paddle_tpu_serving_linear_runs_total"
LINEAR_TOKENS = "paddle_tpu_serving_linear_tokens_total"


def _layers(cfg):
    """(linear layers, full layers) held."""
    kinds = B.layer_types(cfg)
    linear = sum(k == B.LINEAR for k in kinds)
    return linear, len(kinds) - linear


def _head_dim(cfg):
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def _state_elements(cfg):
    return cfg["linear_num_value_heads"] * cfg["linear_key_head_dim"] \
        * cfg["linear_value_head_dim"]


def recurrence_flops_per_token(cfg):
    """One linear layer's recurrence for one token."""
    return 6 * _state_elements(cfg)


def forward_flops(cfg, tokens, sum_context):
    """Forward of ``tokens`` tokens whose context lengths (positions attended
    to, itself included) add up to ``sum_context``."""
    linear, full = _layers(cfg)
    attn = 4 * cfg["num_attention_heads"] * _head_dim(cfg) * sum_context * full
    return (2 * B.parameter_count(cfg, matmul_only=True)
            + recurrence_flops_per_token(cfg) * linear) * tokens + attn


def request_forward_flops(cfg, prompt_len, output_len):
    """A served request feeds prompt + output - 1 tokens through the model (the
    last token served is not fed back), token i attending to i + 1 positions."""
    fed = prompt_len + output_len - 1
    return forward_flops(cfg, fed, fed * (fed + 1) // 2)


def paged_attention_costs(cfg, engine, counters):
    """``(flops, bytes)`` that ALL calls of the full layers' paged attention
    in a traced slice need at the least, from what the program counted over
    that slice; None where nothing was counted. The program counts the KV
    blocks the full kind's attention has to read, a layer of the kind once
    (a query tile's blocks once, a lone lane's own); every full layer reads
    them from its own pool: K and V once each at the pool's item size, plus a
    query row read and an output row written per layer for the FEWEST lanes
    that can have read that many blocks. FLOPs: QK^T and PV for every position
    of a block read."""
    import jax.numpy as jnp

    read = int(counters.get(ATTN_KIND_BLOCKS, {}).get("kind=full", 0))
    if not read:
        return None
    _, full = _layers(cfg)
    heads, hd = cfg["num_attention_heads"], _head_dim(cfg)
    block = int(engine["block_size"])
    row_blocks = -(-int(engine["max_len"]) // block)
    el = jnp.dtype(cfg["torch_dtype"]).itemsize
    kv_bytes = read * full * block * cfg["num_key_value_heads"] * hd * 2 * el
    lanes = -(-read // row_blocks)
    return (4 * heads * hd * read * block * full,
            kv_bytes + lanes * full * heads * hd * 2 * el)


def gated_delta_costs(cfg, engine, counters):
    """``(flops, bytes)`` that ALL calls of the linear layers' recurrence in a
    traced slice need at the least, from what the scheduler counted over that
    slice (a layer once), whatever implements the recurrence; None where
    nothing was counted. Every RUN (one slot's consecutive tokens of one
    step: a prefill chunk, a decode lane, an iteration of a burst) reads its
    slot's float32 state once and writes it once; every TOKEN reads its q and
    k rows (heads x key_dim each), its v row, and writes its output row (heads
    x value_dim each) at the activations' item size, and its decay and beta
    (heads each, float32); times the linear layers. FLOPs: the recurrence's
    products a token (``recurrence_flops_per_token``)."""
    import jax.numpy as jnp

    runs = sum(int(v) for v in counters.get(LINEAR_RUNS, {}).values())
    tokens = sum(int(v) for v in counters.get(LINEAR_TOKENS, {}).values())
    if not tokens:
        return None
    linear, _ = _layers(cfg)
    heads = cfg["linear_num_value_heads"]
    el = jnp.dtype(cfg["torch_dtype"]).itemsize
    row = 2 * heads * (cfg["linear_key_head_dim"]
                       + cfg["linear_value_head_dim"]) * el + 2 * heads * 4
    return (tokens * linear * recurrence_flops_per_token(cfg),
            linear * (runs * 2 * _state_elements(cfg) * 4 + tokens * row))
