"""Olmo-Hybrid-shaped decoder: three of every four layers are gated delta-rule
(linear-attention) layers whose per-sequence cache is a recurrent state, the
fourth is softmax attention over keys and values; every sublayer's OUTPUT is
normalised (the OLMo 2 / 3 order) and no layer has a rotary embedding: position
reaches the attention layers through the recurrent ones.

Reference analog: none in the reference framework; the published
``config.json`` of allenai/Olmo-Hybrid-7B gives the shapes and this file's
config keeps its key names. Layer ``i`` is linear where ``layer_types[i] ==
"linear_attention"``. With ``x`` [T, hidden] and ``RMS`` at ``rms_norm_eps``:

- both kinds: ``h = x + RMS_post_attn(mixer(x))``;
  ``y = h + RMS_post_ff(W_down(silu(W_gate h) * W_up h))``; no bias anywhere;
  a final ``RMS`` before the untied head.
- full layer: ``q = RMS_q(x Wq)``, ``k = RMS_k(x Wk)`` over the whole
  projections, ``num_attention_heads`` heads of ``hidden / heads``,
  ``v = x Wv``; causal softmax attention at ``head_dim ** -0.5``; ``Wo``.
- linear layer: ``models/linear_attention.py`` (projections, a causal depthwise
  convolution and SiLU on q, k and v, l2-normalised q and k, ``beta = 2
  sigmoid(x Wb)`` with ``linear_allow_neg_eigval``, ``g = -exp(A_log)
  softplus(x Wa + dt_bias)``, the gated delta rule on a float32 state
  ``[heads, key_dim, value_dim]``, a gated RMS norm per head, ``Wo``).

The model is a parameter holder with a plain inference ``forward`` (no cache
kept); serving goes through ``ContinuousBatchingEngine``, which finds
``OlmoHybridDecodeEngine`` by ``decode_engine_class``: the same serving block
as every other model, told each layer's kind.
"""
from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from ..framework.core import Tensor
from ..nn.initializer import Assign, Constant, Normal
from ..nn.layer.container import LayerList
from ..nn.layer.layers import Layer
from .llama_decode import (CacheKind, LlamaDecodeEngine, StateKind, _tables)
from .mimo_v2 import _Weight

LINEAR = "linear_attention"


class OlmoHybridConfig:
    """The published ``config.json`` keys that shape the model."""

    def __init__(self, vocab_size=100352, hidden_size=3840,
                 intermediate_size=11008, num_hidden_layers=32,
                 num_attention_heads=30, num_key_value_heads=30,
                 layer_types=None, linear_num_key_heads=30,
                 linear_num_value_heads=30, linear_key_head_dim=96,
                 linear_value_head_dim=192, linear_conv_kernel_dim=4,
                 linear_allow_neg_eigval=True, rms_norm_eps=1e-6,
                 max_position_embeddings=65536, initializer_range=0.02,
                 dtype="float32", **kwargs):
        n = num_hidden_layers
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.num_hidden_layers = n
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = hidden_size // num_attention_heads
        # published default: every fourth layer is a full one; a model cut in
        # depth reads the first ``num_hidden_layers`` of the list
        self.layer_types = list(
            layer_types if layer_types is not None
            else ["full_attention" if i % 4 == 3 else LINEAR
                  for i in range(n)])[:n]
        if linear_num_key_heads != linear_num_value_heads:
            raise ValueError("key and value heads of the linear layers are "
                             "one number here")
        self.linear_num_key_heads = linear_num_key_heads
        self.linear_num_value_heads = linear_num_value_heads
        self.linear_key_head_dim = linear_key_head_dim
        self.linear_value_head_dim = linear_value_head_dim
        self.linear_conv_kernel_dim = linear_conv_kernel_dim
        self.linear_allow_neg_eigval = bool(linear_allow_neg_eigval)
        self.rms_norm_eps = rms_norm_eps
        self.max_position_embeddings = max_position_embeddings
        # 0: nothing is drawn (zeros), for a model whose weights are loaded
        self.initializer_range = initializer_range
        self.dtype = dtype
        for k, v in kwargs.items():
            setattr(self, k, v)

    def kinds(self):
        """``(full, linear)``: the layers' two cache kinds."""
        # flat pools: a block's row is all KV heads side by side (30 x 128
        # lanes), which the grouped-query kernel reads whole; a [.., 30,
        # 128] pool's head dim would sit on sublanes it cannot slice
        return (CacheKind("full", self.num_key_value_heads, self.head_dim,
                          self.head_dim, 0.0, 0, flat=True),
                StateKind("linear", self.linear_num_value_heads,
                          self.linear_key_head_dim,
                          self.linear_value_head_dim,
                          self.linear_conv_kernel_dim,
                          self.linear_allow_neg_eigval))


class OlmoHybridAttention(Layer):
    def __init__(self, cfg, init):
        super().__init__()
        h, dt = cfg.hidden_size, cfg.dtype
        kv = cfg.num_key_value_heads * cfg.head_dim
        self.q_proj = _Weight((h, h), init, dt)
        self.k_proj = _Weight((h, kv), init, dt)
        self.v_proj = _Weight((h, kv), init, dt)
        self.o_proj = _Weight((h, h), init, dt)
        self.q_norm = _Weight((h,), Constant(1.0), dt)
        self.k_norm = _Weight((kv,), Constant(1.0), dt)


class OlmoHybridLinearAttention(Layer):
    """The gated delta-rule layer's parameters. ``A_log`` and ``dt_bias`` as
    the layer's published initialisation draws them: ``A = U(1, 16)``, ``dt``
    log-uniform in [0.001, 0.1] through the inverse softplus."""

    def __init__(self, cfg, init, index):
        super().__init__()
        h, dt = cfg.hidden_size, cfg.dtype
        heads = cfg.linear_num_value_heads
        kw, vw = heads * cfg.linear_key_head_dim, \
            heads * cfg.linear_value_head_dim
        taps = cfg.linear_conv_kernel_dim
        self.q_proj = _Weight((h, kw), init, dt)
        self.k_proj = _Weight((h, kw), init, dt)
        self.v_proj = _Weight((h, vw), init, dt)
        self.g_proj = _Weight((h, vw), init, dt)
        self.a_proj = _Weight((h, heads), init, dt)
        self.b_proj = _Weight((h, heads), init, dt)
        self.o_proj = _Weight((vw, h), init, dt)
        conv = Normal(std=taps ** -0.5) if cfg.initializer_range \
            else Constant(0.0)
        self.q_conv1d = _Weight((taps, kw), conv, dt)
        self.k_conv1d = _Weight((taps, kw), conv, dt)
        self.v_conv1d = _Weight((taps, vw), conv, dt)
        rng = np.random.RandomState(index)
        a = rng.uniform(1.0, 16.0, heads)
        step = np.exp(rng.uniform(np.log(1e-3), np.log(0.1), heads))
        drawn = bool(cfg.initializer_range)
        self.A_log = self.create_parameter(
            [heads], dtype=dt, default_initializer=Assign(
                np.log(a) if drawn else np.zeros(heads)))
        self.dt_bias = self.create_parameter(
            [heads], dtype=dt, default_initializer=Assign(
                step + np.log(-np.expm1(-step)) if drawn
                else np.zeros(heads)))
        self.o_norm = _Weight((cfg.linear_value_head_dim,), Constant(1.0), dt)


class OlmoHybridMLP(Layer):
    def __init__(self, cfg, init):
        super().__init__()
        h, m, dt = cfg.hidden_size, cfg.intermediate_size, cfg.dtype
        self.gate_proj = _Weight((h, m), init, dt)
        self.up_proj = _Weight((h, m), init, dt)
        self.down_proj = _Weight((m, h), init, dt)


class OlmoHybridDecoderLayer(Layer):
    def __init__(self, cfg, index, init):
        super().__init__()
        self.linear = cfg.layer_types[index] == LINEAR
        if self.linear:
            self.linear_attn = OlmoHybridLinearAttention(cfg, init, index)
        else:
            self.self_attn = OlmoHybridAttention(cfg, init)
        ones = Constant(1.0)
        self.post_attention_layernorm = _Weight((cfg.hidden_size,), ones,
                                                cfg.dtype)
        self.mlp = OlmoHybridMLP(cfg, init)
        self.post_feedforward_layernorm = _Weight((cfg.hidden_size,), ones,
                                                  cfg.dtype)


class OlmoHybridModel(Layer):
    def __init__(self, cfg, init):
        super().__init__()
        self.embed_tokens = _Weight((cfg.vocab_size, cfg.hidden_size), init,
                                    cfg.dtype)
        self.layers = LayerList([OlmoHybridDecoderLayer(cfg, i, init)
                                 for i in range(cfg.num_hidden_layers)])
        self.norm = _Weight((cfg.hidden_size,), Constant(1.0), cfg.dtype)


class OlmoHybridDecodeEngine(LlamaDecodeEngine):
    """The serving block's description of an Olmo-Hybrid model: a paged kind
    for the full layers (first: its pager is the block tables') and a
    recurrent kind for the linear ones; each layer's kind and weights."""

    def _extract(self, model):
        cfg = model.config
        self.eps = cfg.rms_norm_eps
        self.kinds = cfg.kinds()
        self.layer_kind = tuple(int(t == LINEAR) for t in cfg.layer_types)
        self.layers = []
        for lyr in model.model.layers:
            m = lyr.mlp
            p = dict(post_attn_norm=lyr.post_attention_layernorm.weight.value,
                     post_ff_norm=lyr.post_feedforward_layernorm.weight.value,
                     gate=m.gate_proj.weight.value, up=m.up_proj.weight.value,
                     down=m.down_proj.weight.value)
            if lyr.linear:
                a = lyr.linear_attn
                p.update(wq=a.q_proj.weight.value, wk=a.k_proj.weight.value,
                         wv=a.v_proj.weight.value, wz=a.g_proj.weight.value,
                         wa=a.a_proj.weight.value, wb=a.b_proj.weight.value,
                         wo=a.o_proj.weight.value,
                         conv=jnp.concatenate(
                             [a.q_conv1d.weight.value, a.k_conv1d.weight.value,
                              a.v_conv1d.weight.value], axis=1),
                         A_log=a.A_log.value, dt_bias=a.dt_bias.value,
                         o_norm=a.o_norm.weight.value)
            else:
                a = lyr.self_attn
                p.update(wq=a.q_proj.weight.value, wk=a.k_proj.weight.value,
                         wv=a.v_proj.weight.value, wo=a.o_proj.weight.value,
                         q_norm=a.q_norm.weight.value,
                         k_norm=a.k_norm.weight.value)
            self.layers.append(p)
        self.emb = model.model.embed_tokens.weight.value
        self.norm_w = model.model.norm.weight.value
        self.head_w = model.lm_head.weight.value


class OlmoHybridForCausalLM(Layer):
    decode_engine_class = OlmoHybridDecodeEngine

    def __init__(self, config: OlmoHybridConfig):
        super().__init__()
        self.config = config
        std = config.initializer_range
        init = Normal(std=std) if std else Constant(0.0)
        self.model = OlmoHybridModel(config, init)
        self.lm_head = _Weight((config.hidden_size, config.vocab_size), init,
                               config.dtype)

    def forward(self, input_ids):
        """Logits [B, S, vocab] of whole sequences: inference only, and no
        cache is kept. It IS the serving block's lockstep prefill, over pools
        made for this call."""
        ids = jnp.asarray(getattr(input_ids, "value", input_ids), jnp.int32)
        B, S = ids.shape
        eng = self.decode_engine_class(self, max_len=S,
                                       kv_cache_layout="paged")
        pager, pools = eng._init_paged(B)
        pager.ensure_capacity([S] * B)
        logits, _ = eng._prefill_paged_jit(
            ids, pools, _tables(pager), jnp.full((B,), S, jnp.int32),
            eng.weights)
        return Tensor(logits)


__all__ = ["OlmoHybridConfig", "OlmoHybridForCausalLM",
           "OlmoHybridDecodeEngine"]
