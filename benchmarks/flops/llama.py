"""Operations and bytes of the Llama-shaped decoder, from its shapes alone.

Model FLOPs, not hardware FLOPs: matmul parameters only (the layers'
projections and the LM head; the embedding table is a lookup), causal attention
at half the square, and what recomputation runs again is not counted.
"""
from __future__ import annotations

import weights as W


def _attn_width(cfg):
    hd = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    return cfg["num_attention_heads"] * hd


def train_flops_per_token(cfg, seq_len):
    """Forward + backward of one token in a sequence of ``seq_len``: 6 per
    matmul parameter, and per layer 3 x (QK^T + PV) over the causal half:
    3 x 2 x 2 x (seq_len / 2) x heads x head_dim."""
    attn = 6 * seq_len * _attn_width(cfg) * cfg["num_hidden_layers"]
    return 6 * W.matmul_param_count(cfg) + attn


def forward_flops(cfg, tokens, sum_context):
    """Forward of ``tokens`` tokens whose context lengths (positions attended
    to, itself included) add up to ``sum_context``: 2 per matmul parameter and
    token, and per layer QK^T + PV = 4 x context x heads x head_dim."""
    attn = 4 * sum_context * _attn_width(cfg) * cfg["num_hidden_layers"]
    return 2 * W.matmul_param_count(cfg) * tokens + attn


def request_forward_flops(cfg, prompt_len, output_len):
    """A served request feeds prompt + output - 1 tokens through the model (the
    last token served is not fed back), token i attending to i + 1 positions."""
    fed = prompt_len + output_len - 1
    return forward_flops(cfg, fed, fed * (fed + 1) // 2)


def flash_attention_costs(cfg, batch, seq_len, bytes_per_el=2):
    """``{call: (flops, bytes)}`` of the three flash-attention kernels for one
    layer at (batch, seq_len), causal: forward (QK^T, PV), dQ (QK^T, dO V^T,
    dS K) and dK/dV (QK^T, P^T dO, dO V^T, dS^T Q), each matmul
    2 x batch x heads x seq^2 x head_dim / 2. Bytes: every operand read and
    every result written once, K/V at the KV heads' width."""
    hd = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    one = 2 * batch * h * seq_len * seq_len * hd // 2
    q = batch * seq_len * h * hd * bytes_per_el
    k = batch * seq_len * kv * hd * bytes_per_el
    stat = batch * seq_len * h * 4                    # float32 row statistic
    return {
        "fwd": (2 * one, 2 * q + 2 * k + stat),               # q,k,v -> o,lse
        "dq": (3 * one, 3 * q + 2 * k + 2 * stat),            # q,k,v,do,lse,d -> dq
        "dkv": (4 * one, 2 * q + 4 * k + 2 * stat),           # q,k,v,do,lse,d -> dk,dv
    }


ATTN_BLOCKS = "paddle_tpu_serving_attn_blocks_total"


def paged_attention_costs(cfg, engine, counters):
    """``(flops, bytes)`` that ALL calls of the serving programs' paged
    attention in a traced slice need at the least, from what the program
    counted over that slice (``counters``: ``{counter: {"label=value": n}}``)
    and the configuration's ``engine`` group; None where nothing was counted.

    The program counts the KV blocks its attention has to read, a layer's
    once (``extent=read``: ``position // block_size + 1`` per valid lane and
    burst iteration), and every layer reads them from its own pool. Bytes:
    each such block of K and of V once, at the pool's item size; plus one
    query row read and one output row written per lane that read, for the
    FEWEST lanes that can have read that many blocks (a lane reads at most
    its whole table row; this count takes no lanes from the program). The
    program counts what the kernels' DMAs bring in: a lane of its own, its
    blocks; the lanes of a prefill chunk, which share a table row and form a
    query tile since PR 33, each block from the tile's first to its last
    ONCE (until then every one of the 128 lanes counted them again). What the
    kernel moves above that is its loss, not this count's. FLOPs: QK^T and PV, 4 x query heads x head_dim for every
    position of a block read (a lane's last block counts whole, up to
    block_size - 1 positions more than it attends to: at one FLOP a byte
    against the chip's 240 this side never bounds the kernel)."""
    import jax.numpy as jnp

    read = int(counters.get(ATTN_BLOCKS, {}).get("extent=read", 0))
    if not read:
        return None
    layers, heads = cfg["num_hidden_layers"], cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or cfg["hidden_size"] // heads
    block = int(engine["block_size"])
    row_blocks = -(-int(engine["max_len"]) // block)
    pool_el = jnp.dtype(engine.get("kv_cache_dtype") or cfg["torch_dtype"]).itemsize
    q_el = jnp.dtype(cfg["torch_dtype"]).itemsize
    kv_bytes = read * layers * block * cfg["num_key_value_heads"] * hd * 2 * pool_el
    lanes = -(-read // row_blocks)
    qo_bytes = lanes * layers * heads * hd * 2 * q_el
    return 4 * heads * hd * read * block * layers, kv_bytes + qo_bytes
