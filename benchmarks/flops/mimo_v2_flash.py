"""Operations and bytes of the MiMo-V2-Flash-shaped decoder cut to one chip's
share, from its shapes alone.

Model FLOPs of what THIS chip computes: per token 2 x the matmul parameters it
multiplies (attention projections, the dense MLP of the leading layer, the
router over all published experts, the head over the vocabulary slice, and of
the held experts the share a token reaches in expectation: experts per token x
held / routed of them, 8 x 16 / 256 = half an expert a layer), and per position
attended to QK^T at the QK head dim and PV at the V head dim, a window layer's
context capped at the window.
"""
from __future__ import annotations

from builders import mimo_v2_flash as B

ATTN_KIND_BLOCKS = "paddle_tpu_serving_attn_kind_blocks_total"


def _kind(cfg, window):
    pre = "swa_" if window else ""
    return (cfg[pre + "num_key_value_heads"], cfg[pre + "head_dim"],
            cfg[pre + "v_head_dim"])


def _layers(cfg):
    """(window?, experts?) of each layer held."""
    n = cfg["num_hidden_layers"]
    return list(zip((bool(w) for w in cfg["hybrid_layer_pattern"][:n]),
                    (bool(m) for m in cfg["moe_layer_freq"][:n])))


def matmul_params_per_token(cfg):
    """Matmul parameters one token is multiplied by on this chip, the held
    experts counted by the share of them a token reaches in expectation."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    routed = B.routed_experts(cfg)
    total = h * cfg["vocab_size"]                            # the head
    for window, experts in _layers(cfg):
        kv, dk, dv = _kind(cfg, window)
        total += h * heads * dk + h * kv * dk + h * kv * dv + heads * dv * h
        if experts:
            reached = cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / routed
            total += h * routed + reached * 3 * h * cfg["moe_intermediate_size"]
        else:
            total += 3 * h * cfg["intermediate_size"]
    return total


def _attn_flops_per_position(cfg, window):
    _, dk, dv = _kind(cfg, window)
    return 2 * cfg["num_attention_heads"] * (dk + dv)


def _capped(tokens, start, window):
    """Sum over positions start .. start + tokens - 1 of min(pos + 1, window)."""
    total = 0
    first = max(start, 0)
    ramp_end = min(start + tokens, window)           # positions below window - 1
    if ramp_end > first:
        total += (first + 1 + ramp_end) * (ramp_end - first) // 2
    return total + max(start + tokens - max(first, window), 0) * window


def forward_flops(cfg, tokens, sum_context, start=0):
    """Forward of ``tokens`` tokens at positions ``start ..`` whose context
    lengths (positions attended to, itself included, uncapped) add up to
    ``sum_context``: a full layer attends to all of them, a window layer to
    ``sliding_window`` at most."""
    attn = 0
    for window, _ in _layers(cfg):
        ctx = _capped(tokens, start, cfg["sliding_window"]) if window \
            else sum_context
        attn += _attn_flops_per_position(cfg, window) * ctx
    return 2 * matmul_params_per_token(cfg) * tokens + attn


def request_forward_flops(cfg, prompt_len, output_len):
    """A served request feeds prompt + output - 1 tokens through the model (the
    last token served is not fed back), token i attending to i + 1 positions."""
    fed = prompt_len + output_len - 1
    return forward_flops(cfg, fed, fed * (fed + 1) // 2)


def paged_attention_costs(cfg, engine, counters):
    """``(flops, bytes)`` that ALL calls of the grouped-query paged attention
    in a traced slice need at the least, from what the program counted over
    that slice; None where nothing was counted.

    The program counts, a cache kind, the KV blocks its attention has to read
    (a layer of the kind once; a window lane from its window's first block).
    Bytes: each such block of K and of V once in every layer of the kind, at
    the unpadded widths KV heads x (QK head dim + V head dim) x the pool's
    item size; plus a query row read and an output row written per layer for
    the FEWEST lanes that can have read the full kind's blocks (a lane reads
    at most its whole table row). FLOPs: QK^T and PV for every position of a
    block read."""
    import jax.numpy as jnp

    series = counters.get(ATTN_KIND_BLOCKS, {})
    read = {False: int(series.get("kind=full", 0)),
            True: int(series.get("kind=window", 0))}
    if not any(read.values()):
        return None
    block = int(engine["block_size"])
    row_blocks = -(-int(engine["max_len"]) // block)
    el = jnp.dtype(cfg["torch_dtype"]).itemsize
    heads = cfg["num_attention_heads"]
    lanes = max(-(-read[False] // row_blocks), 1)
    flops = nbytes = 0
    for window, _ in _layers(cfg):
        kv, dk, dv = _kind(cfg, window)
        nbytes += read[window] * block * kv * (dk + dv) * el
        nbytes += lanes * heads * (dk + dv) * el
        flops += _attn_flops_per_position(cfg, window) * read[window] * block
    return flops, nbytes


EXPERT_PAIRS = "paddle_tpu_serving_expert_pairs_total"


def held_experts_costs(cfg, engine, counters):
    """``(flops, bytes)`` that ALL grouped products of the expert layers in a
    traced slice need at the least, from what the steps' programs counted over
    that slice: every held expert that got a pair (a layer and forward pass
    each) reads its three matrices once; every pair on a held expert reads a
    row of hidden size and writes one, and costs three products of hidden x
    expert width. None where nothing was counted."""
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
