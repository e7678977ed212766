"""Ouro-shaped looped decoder (a "LoopLM"): ONE stack of dense decoder layers
that every token runs ``total_ut_steps`` times with the same weights, each
pass with its own keys and values.

Reference analog: none in the reference framework; the published
``config.json`` of ByteDance/Ouro-2.6B gives the shapes and this file's config
keeps its key names. With ``x`` [T, hidden] and ``RMS`` at ``rms_norm_eps``:

- layer ``l`` (a norm before AND after each sublayer: the family's
  ``input_layernorm``, ``input_layernorm_2``, ``post_attention_layernorm``,
  ``post_attention_layernorm_2``):
  ``h = x + RMS_a2(Attn(RMS_a1(x)))``;
  ``y = h + RMS_m2(W_down(silu(W_gate RMS_m1(h)) * W_up RMS_m1(h)))``;
  no bias anywhere;
- ``Attn``: multi-head attention, ``num_attention_heads`` heads of
  ``head_dim`` for q, k and v alike, rotary on the whole head at
  ``rope_theta`` at the token's absolute position, causal softmax at
  ``head_dim ** -0.5``, ``W_o``;
- the loop: ``x_0 = E[ids]``; for ``t = 1 .. total_ut_steps``:
  ``x_t = RMS_f(Layer_{L-1}(... Layer_0(x_{t-1})))`` with the ONE final norm
  after every pass; ``logits = x_R W_head`` (untied). In pass ``t`` layer
  ``l`` attends over the keys and values pass ``t`` of layer ``l`` wrote.
- the exit gate (``early_exit_gate``, ``Linear(hidden, 1)``) decides nothing
  at ``early_exit_threshold`` 1: every token runs every pass. It is held, so
  that a checkpoint loads whole, and never computed.

The model is a parameter holder with a plain inference ``forward`` (no cache
kept); serving goes through ``ContinuousBatchingEngine``, which finds
``OuroDecodeEngine`` by ``decode_engine_class``: the serving block every other
model runs, told that the stack runs ``total_ut_steps`` times.
"""
from __future__ import annotations

from ..framework.core import Tensor
from ..nn.initializer import Constant, Normal
from ..nn.layer.container import LayerList
from ..nn.layer.layers import Layer
from .llama_decode import CacheKind, LlamaDecodeEngine, prefill_logits
from .mimo_v2 import _Weight


class OuroConfig:
    """The published ``config.json`` keys that shape the model."""

    def __init__(self, vocab_size=49152, hidden_size=2048,
                 intermediate_size=5632, num_hidden_layers=48,
                 num_attention_heads=16, num_key_value_heads=16, head_dim=128,
                 rope_theta=1e6, rms_norm_eps=1e-6, total_ut_steps=4,
                 early_exit_threshold=1.0, max_position_embeddings=65536,
                 initializer_range=0.02, dtype="float32", **kwargs):
        if early_exit_threshold < 1:
            raise ValueError("early exit below threshold 1 (a token leaving "
                             "the loop early) is not served")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.rope_theta = rope_theta
        self.rms_norm_eps = rms_norm_eps
        self.total_ut_steps = int(total_ut_steps)
        self.early_exit_threshold = early_exit_threshold
        self.max_position_embeddings = max_position_embeddings
        # 0: nothing is drawn (zeros), for a model whose weights are loaded
        self.initializer_range = initializer_range
        self.dtype = dtype
        for k, v in kwargs.items():
            setattr(self, k, v)

    def kind(self):
        """The layers' one cache kind: whole-head rotary, every pass's keys
        and values in the pool."""
        return CacheKind("full", self.num_key_value_heads, self.head_dim,
                         self.head_dim, self.rope_theta, self.head_dim,
                         passes=self.total_ut_steps)


class OuroAttention(Layer):
    def __init__(self, cfg, init):
        super().__init__()
        h, dt = cfg.hidden_size, cfg.dtype
        q = cfg.num_attention_heads * cfg.head_dim
        kv = cfg.num_key_value_heads * cfg.head_dim
        self.q_proj = _Weight((h, q), init, dt)
        self.k_proj = _Weight((h, kv), init, dt)
        self.v_proj = _Weight((h, kv), init, dt)
        self.o_proj = _Weight((q, h), init, dt)


class OuroMLP(Layer):
    def __init__(self, cfg, init):
        super().__init__()
        h, m, dt = cfg.hidden_size, cfg.intermediate_size, cfg.dtype
        self.gate_proj = _Weight((h, m), init, dt)
        self.up_proj = _Weight((h, m), init, dt)
        self.down_proj = _Weight((m, h), init, dt)


class OuroDecoderLayer(Layer):
    def __init__(self, cfg, init):
        super().__init__()
        h, dt, ones = cfg.hidden_size, cfg.dtype, Constant(1.0)
        self.self_attn = OuroAttention(cfg, init)
        self.mlp = OuroMLP(cfg, init)
        self.input_layernorm = _Weight((h,), ones, dt)
        self.input_layernorm_2 = _Weight((h,), ones, dt)
        self.post_attention_layernorm = _Weight((h,), ones, dt)
        self.post_attention_layernorm_2 = _Weight((h,), ones, dt)


class OuroExitGate(Layer):
    """``Linear(hidden, 1)`` with its bias: held, never computed."""

    def __init__(self, cfg, init):
        super().__init__()
        self.weight = self.create_parameter(
            [cfg.hidden_size, 1], dtype=cfg.dtype, default_initializer=init)
        self.bias = self.create_parameter(
            [1], dtype=cfg.dtype, default_initializer=Constant(0.0))


class OuroModel(Layer):
    def __init__(self, cfg, init):
        super().__init__()
        self.embed_tokens = _Weight((cfg.vocab_size, cfg.hidden_size), init,
                                    cfg.dtype)
        self.layers = LayerList([OuroDecoderLayer(cfg, init)
                                 for _ in range(cfg.num_hidden_layers)])
        self.norm = _Weight((cfg.hidden_size,), Constant(1.0), cfg.dtype)
        self.early_exit_gate = OuroExitGate(cfg, init)


class OuroDecodeEngine(LlamaDecodeEngine):
    """The serving block's description of an Ouro model: one paged kind that
    holds every pass, and each layer's weights, whose keys say it has a norm
    before and after each sublayer."""

    def _extract(self, model):
        cfg = model.config
        self.eps = cfg.rms_norm_eps
        self.kinds = (cfg.kind(),)
        self.layer_kind = (0,) * cfg.num_hidden_layers
        self.layers = []
        for lyr in model.model.layers:
            a, m = lyr.self_attn, lyr.mlp
            self.layers.append(dict(
                ln1=lyr.input_layernorm.weight.value,
                post_attn_norm=lyr.input_layernorm_2.weight.value,
                ln2=lyr.post_attention_layernorm.weight.value,
                post_ff_norm=lyr.post_attention_layernorm_2.weight.value,
                wq=a.q_proj.weight.value, wk=a.k_proj.weight.value,
                wv=a.v_proj.weight.value, wo=a.o_proj.weight.value,
                gate=m.gate_proj.weight.value, up=m.up_proj.weight.value,
                down=m.down_proj.weight.value))
        self.emb = model.model.embed_tokens.weight.value
        self.norm_w = model.model.norm.weight.value
        self.head_w = model.lm_head.weight.value


class OuroForCausalLM(Layer):
    decode_engine_class = OuroDecodeEngine

    def __init__(self, config: OuroConfig):
        super().__init__()
        self.config = config
        std = config.initializer_range
        init = Normal(std=std) if std else Constant(0.0)
        self.model = OuroModel(config, init)
        self.lm_head = _Weight((config.hidden_size, config.vocab_size), init,
                               config.dtype)

    def forward(self, input_ids):
        """Logits [B, S, vocab] of whole sequences: inference only, and no
        cache is kept. It IS the serving block's lockstep prefill, over pools
        made for this call."""
        return Tensor(prefill_logits(self, input_ids))


__all__ = ["OuroConfig", "OuroForCausalLM", "OuroDecodeEngine"]
