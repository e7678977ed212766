"""MiMo-V2-Flash-shaped decoder: full and sliding-window attention layers with
their own KV-head counts, a QK head wider than the V head, rotary on part of a
head, a learnable sink logit per head on window layers, and a sparse expert MLP
(sigmoid scores, a selection bias, top-k without dropping) in every layer but
the leading dense ones.

Reference analog: none in the reference framework (its model zoo is dense);
the published ``config.json`` of XiaomiMiMo/MiMo-V2-Flash gives the shapes and
this file's config keeps its key names. Per layer ``i``, with ``x`` [T, hidden]:

- ``h = RMS(x)``; ``q = h Wq`` as heads x head_dim; ``k = h Wk`` as KV x head_dim;
  ``v = attention_value_scale * (h Wv)`` as KV x v_head_dim; KV is
  ``num_key_value_heads`` on a full layer, ``swa_num_key_value_heads`` on a
  window layer (``hybrid_layer_pattern[i] == 1``). Rotary (rotate-half) on the
  first ``int(partial_rotary_factor * head_dim)`` dims of q and k, base
  ``rope_theta`` / ``swa_rope_theta``. Scores ``q . k / sqrt(head_dim)``,
  causal, on a window layer over the last ``sliding_window`` positions (the
  current one included); there the softmax's denominator also holds
  ``exp(sink_h)``, which takes probability and adds no value.
  ``x <- x + o Wo``.
- ``h = RMS(x)``; dense SwiGLU of ``intermediate_size`` where
  ``moe_layer_freq[i] == 0``, else the experts: see
  ``incubate/distributed/models/moe/held_experts.py``. The model HOLDS the
  experts ``expert_offset .. expert_offset + n_held_experts - 1`` of
  ``n_routed_experts`` (all of them by default): it routes over all, at the
  published width, and adds up what its own experts give.

The model is a parameter holder with a plain inference ``forward`` (no cache);
serving goes through ``ContinuousBatchingEngine``, which finds
``MiMoV2DecodeEngine`` by ``decode_engine_class``: the same serving block as
every Llama-shaped model, told each layer's kind.
"""
from __future__ import annotations

import jax.numpy as jnp

from ..framework.core import Tensor
from ..nn.initializer import Constant, Normal
from ..nn.layer.container import LayerList
from ..nn.layer.layers import Layer
from .llama_decode import CacheKind, LlamaDecodeEngine, _rms


class MiMoV2Config:
    """The published ``config.json`` keys that shape the model, plus which
    experts this instance holds."""

    def __init__(self, vocab_size=152576, hidden_size=4096,
                 intermediate_size=16384, num_hidden_layers=48,
                 num_attention_heads=64, num_key_value_heads=4, head_dim=192,
                 v_head_dim=128, swa_num_key_value_heads=8, swa_head_dim=192,
                 swa_v_head_dim=128, sliding_window=128,
                 hybrid_layer_pattern=None, moe_layer_freq=None,
                 rope_theta=5e6, swa_rope_theta=1e4,
                 partial_rotary_factor=0.334, attention_value_scale=0.707,
                 add_swa_attention_sink_bias=True,
                 add_full_attention_sink_bias=False,
                 moe_intermediate_size=2048, n_routed_experts=256,
                 num_experts_per_tok=8, layernorm_epsilon=1e-5,
                 max_position_embeddings=262144, initializer_range=0.02,
                 expert_offset=0, n_held_experts=None, dtype="float32",
                 **kwargs):
        n = num_hidden_layers
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.num_hidden_layers = n
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.v_head_dim = v_head_dim
        self.swa_num_key_value_heads = swa_num_key_value_heads
        self.swa_head_dim = swa_head_dim
        self.swa_v_head_dim = swa_v_head_dim
        self.sliding_window = sliding_window
        # published default: layer 0 and every sixth layer from layer 5 on
        # are full, the five between are window layers
        self.hybrid_layer_pattern = list(
            hybrid_layer_pattern if hybrid_layer_pattern is not None
            else [0 if i == 0 or i % 6 == 5 else 1 for i in range(n)])[:n]
        self.moe_layer_freq = list(
            moe_layer_freq if moe_layer_freq is not None
            else [0] + [1] * (n - 1))[:n]
        self.rope_theta = rope_theta
        self.swa_rope_theta = swa_rope_theta
        self.partial_rotary_factor = partial_rotary_factor
        self.attention_value_scale = attention_value_scale
        self.add_swa_attention_sink_bias = add_swa_attention_sink_bias
        self.add_full_attention_sink_bias = add_full_attention_sink_bias
        self.moe_intermediate_size = moe_intermediate_size
        self.n_routed_experts = n_routed_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.layernorm_epsilon = layernorm_epsilon
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

    def kind(self, window):
        """The cache kind of a window (True) or a full (False) layer."""
        if window:
            return CacheKind(
                "window", self.swa_num_key_value_heads, self.swa_head_dim,
                self.swa_v_head_dim, float(self.swa_rope_theta),
                int(self.partial_rotary_factor * self.swa_head_dim),
                window=int(self.sliding_window), flat=True)
        return CacheKind(
            "full", self.num_key_value_heads, self.head_dim, self.v_head_dim,
            float(self.rope_theta),
            int(self.partial_rotary_factor * self.head_dim), flat=True)


class _Weight(Layer):
    """One ``weight`` of a given shape (the ``[in, out]`` of a projection, a
    norm's scale)."""

    def __init__(self, shape, init, dtype):
        super().__init__()
        self.weight = self.create_parameter(list(shape), dtype=dtype,
                                            default_initializer=init)


class MiMoV2Attention(Layer):
    def __init__(self, cfg, window, init):
        super().__init__()
        kind = cfg.kind(window)
        h, nh, dt = cfg.hidden_size, cfg.num_attention_heads, cfg.dtype
        self.q_proj = _Weight((h, nh * kind.head_dim), init, dt)
        self.k_proj = _Weight((h, kind.num_kv * kind.head_dim), init, dt)
        self.v_proj = _Weight((h, kind.num_kv * kind.v_head_dim), init, dt)
        self.o_proj = _Weight((nh * kind.v_head_dim, h), init, dt)
        if cfg.add_swa_attention_sink_bias if window \
                else cfg.add_full_attention_sink_bias:
            self.attention_sink_bias = self.create_parameter(
                [nh], dtype=dt, default_initializer=Constant(0.0))


class MiMoV2MLP(Layer):
    def __init__(self, cfg, init):
        super().__init__()
        h, m, dt = cfg.hidden_size, cfg.intermediate_size, cfg.dtype
        self.gate_proj = _Weight((h, m), init, dt)
        self.up_proj = _Weight((h, m), init, dt)
        self.down_proj = _Weight((m, h), init, dt)


class MiMoV2Router(Layer):
    """Scores over ALL routed experts, and the ``noaux_tc`` selection bias."""

    def __init__(self, cfg, init):
        super().__init__()
        self.weight = self.create_parameter(
            [cfg.hidden_size, cfg.n_routed_experts], dtype=cfg.dtype,
            default_initializer=init)
        self.e_score_correction_bias = self.create_parameter(
            [cfg.n_routed_experts], dtype=cfg.dtype,
            default_initializer=Constant(0.0))


class MiMoV2Experts(Layer):
    """The held experts' SwiGLU matrices, stacked on a leading expert axis."""

    def __init__(self, cfg, init):
        super().__init__()
        e, h, m = cfg.n_held_experts, cfg.hidden_size, cfg.moe_intermediate_size
        mk = lambda shape: self.create_parameter(        # noqa: E731
            list(shape), dtype=cfg.dtype, default_initializer=init)
        self.gate_proj = mk((e, h, m))
        self.up_proj = mk((e, h, m))
        self.down_proj = mk((e, m, h))


class MiMoV2MoE(Layer):
    def __init__(self, cfg, init):
        super().__init__()
        self.gate = MiMoV2Router(cfg, init)
        self.experts = MiMoV2Experts(cfg, init)


class MiMoV2DecoderLayer(Layer):
    def __init__(self, cfg, index, init):
        super().__init__()
        self.window = bool(cfg.hybrid_layer_pattern[index])
        ones = Constant(1.0)
        self.input_layernorm = _Weight((cfg.hidden_size,), ones, cfg.dtype)
        self.self_attn = MiMoV2Attention(cfg, self.window, init)
        self.post_attention_layernorm = _Weight((cfg.hidden_size,), ones,
                                                cfg.dtype)
        self.mlp = MiMoV2MoE(cfg, init) if cfg.moe_layer_freq[index] \
            else MiMoV2MLP(cfg, init)


class MiMoV2Model(Layer):
    def __init__(self, cfg, init):
        super().__init__()
        self.embed_tokens = _Weight((cfg.vocab_size, cfg.hidden_size), init,
                                    cfg.dtype)
        self.layers = LayerList([MiMoV2DecoderLayer(cfg, i, init)
                                 for i in range(cfg.num_hidden_layers)])
        self.norm = _Weight((cfg.hidden_size,), Constant(1.0), cfg.dtype)


class MiMoV2DecodeEngine(LlamaDecodeEngine):
    """The serving block's description of a MiMo-V2 model: two cache kinds
    (full first), each layer's kind, sink, and dense or expert MLP."""

    def _extract(self, model):
        cfg = model.config
        self.eps = cfg.layernorm_epsilon
        self.v_scale = float(cfg.attention_value_scale)
        self.held_lo = cfg.expert_offset
        self.held_experts = cfg.n_held_experts
        self.top_k = cfg.num_experts_per_tok
        present = sorted(set(bool(w) for w in cfg.hybrid_layer_pattern))
        self.kinds = tuple(cfg.kind(w) for w in present)
        self.layer_kind = tuple(present.index(bool(w))
                                for w in cfg.hybrid_layer_pattern)
        self.layers = []
        for lyr in model.model.layers:
            a, m = lyr.self_attn, lyr.mlp
            p = dict(ln1=lyr.input_layernorm.weight.value,
                     ln2=lyr.post_attention_layernorm.weight.value,
                     wq=a.q_proj.weight.value, wk=a.k_proj.weight.value,
                     wv=a.v_proj.weight.value, wo=a.o_proj.weight.value)
            if hasattr(a, "attention_sink_bias"):
                p["sink"] = a.attention_sink_bias.value
            if isinstance(m, MiMoV2MoE):
                p.update(router=m.gate.weight.value,
                         router_bias=m.gate.e_score_correction_bias.value,
                         w1=m.experts.gate_proj.value,
                         w3=m.experts.up_proj.value,
                         w2=m.experts.down_proj.value)
            else:
                p.update(gate=m.gate_proj.weight.value,
                         up=m.up_proj.weight.value,
                         down=m.down_proj.weight.value)
            self.layers.append(p)
        self.emb = model.model.embed_tokens.weight.value
        self.norm_w = model.model.norm.weight.value
        self.head_w = model.lm_head.weight.value


class MiMoV2ForCausalLM(Layer):
    decode_engine_class = MiMoV2DecodeEngine

    def __init__(self, config: MiMoV2Config):
        super().__init__()
        self.config = config
        std = config.initializer_range
        init = Normal(std=std) if std else Constant(0.0)
        self.model = MiMoV2Model(config, init)
        self.lm_head = _Weight((config.hidden_size, config.vocab_size), init,
                               config.dtype)

    def forward(self, input_ids):
        """Logits [B, S, vocab] of whole sequences, no cache: inference only
        (nothing is recorded for a backward pass). The layers are the serving
        block's own functions, the attention its masked prompt form."""
        ids = jnp.asarray(getattr(input_ids, "value", input_ids), jnp.int32)
        eng = self.decode_engine_class(self, max_len=ids.shape[1],
                                       kv_cache_layout="paged")
        B, S = ids.shape
        t = jnp.arange(S)
        x = eng.emb[ids]
        for li, p in enumerate(eng.layers):
            kind = eng.kinds[eng.layer_kind[li]]
            q, k, v = eng._qkv_rope(p, x, t, kind)
            seen = t[None, :] <= t[:, None]
            if kind.window is not None:
                seen = seen & (t[None, :] > t[:, None] - kind.window)
            attn = eng._attend(q, k, v, jnp.broadcast_to(seen, (B, S, S)),
                               p.get("sink"))
            x, _ = eng._post_attn(p, x, attn)
        return Tensor(_rms(x, eng.norm_w, eng.eps) @ eng.head_w)


__all__ = ["MiMoV2Config", "MiMoV2ForCausalLM", "MiMoV2DecodeEngine"]
