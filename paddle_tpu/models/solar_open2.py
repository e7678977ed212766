"""Solar-Open2-shaped decoder: three of every four layers are Kimi Delta
Attention (KDA) layers, gated delta-rule linear attention with a decay a KEY
CHANNEL, whose per-sequence cache is a recurrent state; the fourth is softmax
grouped-query attention with no rotary (NoPE) and a sigmoid gate on its
output; every layer's MLP is a sparse expert layer (sigmoid scores, a
selection bias, top-k without dropping) with a shared expert beside it.

Reference analog: none in the reference framework; the published
``config.json`` of upstage/Solar-Open2-250B gives the shapes and this file's
config keeps its key names. Pre-norm (the DeepSeek and Kimi order). Layer
``i`` is softmax attention where ``i in gqa_layers``, KDA elsewhere. With
``x`` [T, hidden] and ``RMS`` at ``rms_norm_eps``:

- both kinds: ``h = x + mixer(RMS(x))``; ``y = h + MoE(RMS(h))``; no bias
  anywhere; a final ``RMS`` before the untied head. Every layer is an expert
  layer (``first_k_dense_replace`` 0), so ``intermediate_size`` is unused.
- GQA layer: ``q = x Wq`` (heads x head_dim), ``k = x Wk``, ``v = x Wv``
  (KV heads x head_dim); causal softmax at ``head_dim ** -0.5``, no rotary
  (``use_rope`` false); ``o = attn * sigmoid(x Wg)`` elementwise
  (``use_gqa_gate``); ``Wo``.
- KDA layer: ``models/linear_attention.py``: q, k, v projections, each
  through its causal depthwise convolution and SiLU, l2-normalised q and k,
  ``beta = 2 sigmoid(x Wb)`` (``kda_allow_neg_eigval``), ``g = -exp(A_log)
  softplus(x Wfa Wfb + dt_bias)`` a key channel, the recurrence on a float32
  state ``[heads, head_dim, head_dim]``, ``o = RMS(o) * w * sigmoid(x Wga
  Wgb)`` per head, ``Wo``. The gates' low rank is the head dim
  (``kda_use_full_proj`` false).
- MoE: ``s = sigmoid(x Wr)`` over all ``n_routed_experts``; the
  ``num_experts_per_tok`` largest of ``s + bias`` are chosen, weighted by
  their ``s`` over the chosen sum (``norm_topk_prob``) times
  ``routed_scaling_factor`` (1); ``y = sum_e w_e SwiGLU_e(x) +
  SwiGLU_shared(x)``, the shared expert ``n_shared_experts`` experts wide.
  The model HOLDS the experts ``expert_offset .. expert_offset +
  n_held_experts - 1`` (all of them by default): it routes over all and adds
  up what its own experts give (``held_experts.py``); every chip computes
  the shared expert alike.

The model is a parameter holder with a plain inference ``forward`` (no cache
kept); serving goes through ``ContinuousBatchingEngine``, which finds
``SolarOpen2DecodeEngine`` by ``decode_engine_class``: the same serving block
as every other model, told each layer's kind.
"""
from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from ..framework.core import Tensor
from ..nn.initializer import Assign, Constant, Normal
from ..nn.layer.container import LayerList
from ..nn.layer.layers import Layer
from .llama_decode import (CacheKind, LlamaDecodeEngine, StateKind,
                           prefill_logits)
from .mimo_v2 import MiMoV2Experts, MiMoV2Router, _Weight


class SolarOpen2Config:
    """The published ``config.json`` keys that shape the model, plus which
    experts this instance holds."""

    def __init__(self, vocab_size=196608, hidden_size=4096,
                 intermediate_size=10240, moe_intermediate_size=1280,
                 num_hidden_layers=48, num_attention_heads=64,
                 num_key_value_heads=8, head_dim=128, linear_attn_config=None,
                 gqa_layers=None, use_gqa_gate=True, use_rope=False,
                 partial_rotary_factor=1.0, rope_theta=10000.0,
                 kda_use_full_proj=False, kda_allow_neg_eigval=True,
                 n_routed_experts=320, n_shared_experts=1,
                 num_experts_per_tok=8, norm_topk_prob=True,
                 routed_scaling_factor=1.0, first_k_dense_replace=0,
                 rms_norm_eps=1e-5, max_position_embeddings=1048576,
                 initializer_range=0.02, expert_offset=0, n_held_experts=None,
                 dtype="float32", **kwargs):
        n = num_hidden_layers
        lin = dict(linear_attn_config or {"short_conv_kernel_size": 4,
                                          "head_dim": 128, "num_heads": 64})
        if kda_use_full_proj or use_rope or first_k_dense_replace:
            raise ValueError("not implemented: the KDA layers' full-rank "
                             "gates, rotary on the GQA layers, leading dense "
                             "layers (kda_use_full_proj, use_rope and "
                             "first_k_dense_replace are false / 0 here)")
        if not norm_topk_prob or routed_scaling_factor != 1:
            raise ValueError("the routed weights are the chosen scores over "
                             "their sum, unscaled (norm_topk_prob true, "
                             "routed_scaling_factor 1)")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_hidden_layers = n
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.linear_attn_config = lin
        # published: every fourth layer from layer 0 is a GQA layer; a model
        # cut in depth holds the first ``num_hidden_layers``
        self.gqa_layers = sorted(set(gqa_layers if gqa_layers is not None
                                     else range(0, n, 4)))
        self.use_gqa_gate = bool(use_gqa_gate)
        self.kda_allow_neg_eigval = bool(kda_allow_neg_eigval)
        self.n_routed_experts = n_routed_experts
        self.n_shared_experts = int(n_shared_experts or 0)
        self.num_experts_per_tok = num_experts_per_tok
        self.rms_norm_eps = rms_norm_eps
        self.max_position_embeddings = max_position_embeddings
        # 0: nothing is drawn (zeros), for a model whose weights are loaded
        self.initializer_range = initializer_range
        self.expert_offset = int(expert_offset)
        self.n_held_experts = int(n_routed_experts if n_held_experts is None
                                  else n_held_experts)
        if not 0 <= self.expert_offset \
                <= n_routed_experts - self.n_held_experts:
            raise ValueError("the held experts lie outside the routed ones")
        self.dtype = dtype
        for k, v in kwargs.items():
            setattr(self, k, v)

    def is_gqa(self, i):
        return i in self.gqa_layers

    def kinds(self):
        """``(full, kda)``: the layers' two cache kinds."""
        lin = self.linear_attn_config
        d = lin["head_dim"]
        return (CacheKind("full", self.num_key_value_heads, self.head_dim,
                          self.head_dim, 0.0, 0, flat=True),
                StateKind("kda", lin["num_heads"], d, d,
                          lin["short_conv_kernel_size"],
                          self.kda_allow_neg_eigval))


class SolarOpen2Attention(Layer):
    """The GQA layer's projections and its output gate."""

    def __init__(self, cfg, init):
        super().__init__()
        h, dt = cfg.hidden_size, cfg.dtype
        q = cfg.num_attention_heads * cfg.head_dim
        kv = cfg.num_key_value_heads * cfg.head_dim
        self.q_proj = _Weight((h, q), init, dt)
        self.k_proj = _Weight((h, kv), init, dt)
        self.v_proj = _Weight((h, kv), init, dt)
        self.o_proj = _Weight((q, h), init, dt)
        if cfg.use_gqa_gate:
            self.g_proj = _Weight((h, q), init, dt)


class SolarOpen2DeltaAttention(Layer):
    """The KDA layer's parameters. ``A_log`` (a head) and ``dt_bias`` (a key
    channel) as the layer's published initialisation draws them: ``A =
    U(1, 16)``, ``dt`` log-uniform in [0.001, 0.1] through the inverse
    softplus."""

    def __init__(self, cfg, init, index):
        super().__init__()
        lin = cfg.linear_attn_config
        h, dt = cfg.hidden_size, cfg.dtype
        heads, d = lin["num_heads"], lin["head_dim"]
        width, taps = heads * d, lin["short_conv_kernel_size"]
        self.q_proj = _Weight((h, width), init, dt)
        self.k_proj = _Weight((h, width), init, dt)
        self.v_proj = _Weight((h, width), init, dt)
        self.f_a_proj = _Weight((h, d), init, dt)
        self.f_b_proj = _Weight((d, width), init, dt)
        self.b_proj = _Weight((h, heads), init, dt)
        self.g_a_proj = _Weight((h, d), init, dt)
        self.g_b_proj = _Weight((d, width), init, dt)
        self.o_proj = _Weight((width, h), init, dt)
        conv = Normal(std=taps ** -0.5) if cfg.initializer_range \
            else Constant(0.0)
        self.q_conv1d = _Weight((taps, width), conv, dt)
        self.k_conv1d = _Weight((taps, width), conv, dt)
        self.v_conv1d = _Weight((taps, width), conv, dt)
        rng = np.random.RandomState(index)
        a = rng.uniform(1.0, 16.0, heads)
        step = np.exp(rng.uniform(np.log(1e-3), np.log(0.1), width))
        drawn = bool(cfg.initializer_range)
        self.A_log = self.create_parameter(
            [heads], dtype=dt, default_initializer=Assign(
                np.log(a) if drawn else np.zeros(heads)))
        self.dt_bias = self.create_parameter(
            [width], dtype=dt, default_initializer=Assign(
                step + np.log(-np.expm1(-step)) if drawn
                else np.zeros(width)))
        self.o_norm = _Weight((d,), Constant(1.0), dt)


class _SwiGLU(Layer):
    def __init__(self, h, m, init, dt):
        super().__init__()
        self.gate_proj = _Weight((h, m), init, dt)
        self.up_proj = _Weight((h, m), init, dt)
        self.down_proj = _Weight((m, h), init, dt)


class SolarOpen2MoE(Layer):
    def __init__(self, cfg, init):
        super().__init__()
        self.gate = MiMoV2Router(cfg, init)
        self.experts = MiMoV2Experts(cfg, init)
        if cfg.n_shared_experts:
            self.shared_experts = _SwiGLU(
                cfg.hidden_size,
                cfg.n_shared_experts * cfg.moe_intermediate_size, init,
                cfg.dtype)


class SolarOpen2DecoderLayer(Layer):
    def __init__(self, cfg, index, init):
        super().__init__()
        self.gqa = cfg.is_gqa(index)
        ones, h, dt = Constant(1.0), cfg.hidden_size, cfg.dtype
        self.input_layernorm = _Weight((h,), ones, dt)
        self.self_attn = SolarOpen2Attention(cfg, init) if self.gqa \
            else SolarOpen2DeltaAttention(cfg, init, index)
        self.post_attention_layernorm = _Weight((h,), ones, dt)
        self.mlp = SolarOpen2MoE(cfg, init)


class SolarOpen2Model(Layer):
    def __init__(self, cfg, init):
        super().__init__()
        self.embed_tokens = _Weight((cfg.vocab_size, cfg.hidden_size), init,
                                    cfg.dtype)
        self.layers = LayerList([SolarOpen2DecoderLayer(cfg, i, init)
                                 for i in range(cfg.num_hidden_layers)])
        self.norm = _Weight((cfg.hidden_size,), Constant(1.0), cfg.dtype)


class SolarOpen2DecodeEngine(LlamaDecodeEngine):
    """The serving block's description of a Solar-Open2 model: a paged kind
    for the GQA layers (first: its pager is the block tables') and a
    recurrent kind for the KDA ones; each layer's kind and weights, which say
    the rest (``ln1`` a norm before the mixer, ``attn_gate`` the output gate,
    ``wfa`` / ``wgb`` the KDA decay and gate, ``router`` the experts and
    ``shared_gate`` the shared expert)."""

    def _extract(self, model):
        cfg = model.config
        self.eps = cfg.rms_norm_eps
        self.held_lo = cfg.expert_offset
        self.held_experts = cfg.n_held_experts
        self.top_k = cfg.num_experts_per_tok
        self.kinds = cfg.kinds()
        self.layer_kind = tuple(int(not cfg.is_gqa(i))
                                for i in range(cfg.num_hidden_layers))
        self.layers = []
        for lyr in model.model.layers:
            a, m = lyr.self_attn, lyr.mlp
            p = dict(ln1=lyr.input_layernorm.weight.value,
                     ln2=lyr.post_attention_layernorm.weight.value,
                     wq=a.q_proj.weight.value, wk=a.k_proj.weight.value,
                     wv=a.v_proj.weight.value, wo=a.o_proj.weight.value)
            if lyr.gqa:
                if hasattr(a, "g_proj"):
                    p["attn_gate"] = a.g_proj.weight.value
            else:
                p.update(conv=jnp.concatenate(
                             [a.q_conv1d.weight.value, a.k_conv1d.weight.value,
                              a.v_conv1d.weight.value], axis=1),
                         A_log=a.A_log.value, dt_bias=a.dt_bias.value,
                         wfa=a.f_a_proj.weight.value,
                         wfb=a.f_b_proj.weight.value,
                         wb=a.b_proj.weight.value,
                         wga=a.g_a_proj.weight.value,
                         wgb=a.g_b_proj.weight.value,
                         o_norm=a.o_norm.weight.value)
            p.update(router=m.gate.weight.value,
                     router_bias=m.gate.e_score_correction_bias.value,
                     w1=m.experts.gate_proj.value, w3=m.experts.up_proj.value,
                     w2=m.experts.down_proj.value)
            if hasattr(m, "shared_experts"):
                s = m.shared_experts
                p.update(shared_gate=s.gate_proj.weight.value,
                         shared_up=s.up_proj.weight.value,
                         shared_down=s.down_proj.weight.value)
            self.layers.append(p)
        self.emb = model.model.embed_tokens.weight.value
        self.norm_w = model.model.norm.weight.value
        self.head_w = model.lm_head.weight.value


class SolarOpen2ForCausalLM(Layer):
    decode_engine_class = SolarOpen2DecodeEngine

    def __init__(self, config: SolarOpen2Config):
        super().__init__()
        self.config = config
        std = config.initializer_range
        init = Normal(std=std) if std else Constant(0.0)
        self.model = SolarOpen2Model(config, init)
        self.lm_head = _Weight((config.hidden_size, config.vocab_size), init,
                               config.dtype)

    def forward(self, input_ids):
        """Logits [B, S, vocab] of whole sequences: inference only, and no
        cache is kept. It IS the serving block's lockstep prefill, over pools
        made for this call."""
        return Tensor(prefill_logits(self, input_ids))


__all__ = ["SolarOpen2Config", "SolarOpen2ForCausalLM",
           "SolarOpen2DecodeEngine"]
