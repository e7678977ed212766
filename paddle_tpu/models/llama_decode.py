"""KV-cache decode engine for LLaMA serving.

Reference analog: the inference engine's decode path
(fluid/inference/api/analysis_predictor.cc execution role +
paddle/fluid/operators fused attention decode kernels; the reference's
generation stack caches K/V per layer and attends each new token against it).

TPU-first design: the cache is a STATIC-shape ring of (B, max_len, Hkv, D)
arrays per layer; each step writes the new K/V at position `pos` via
lax.dynamic_update_slice and attends against the full buffer under a
position mask — no dynamic shapes, so the whole decode step is ONE compiled
XLA program reused for every token (the AOT executable the Predictor caches).
Weights are pulled from the trained model once; a parity test pins this
functional path against the model's own forward.
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax


def _rms(x, w, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * lax.rsqrt(var + eps)).astype(x.dtype) * w


def _rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def _partial(rope):
    """``rope(x, positions, theta)`` on the first ``rotary_dim`` dims of a
    head (rotate-half within them), the rest passing through; the whole
    head where ``rotary_dim`` is None or the head's width."""
    def apply(x, positions, theta, rotary_dim=None):
        if rotary_dim is None or rotary_dim == x.shape[-1]:
            return rope(x, positions, theta)
        return jnp.concatenate(
            [rope(x[..., :rotary_dim], positions, theta),
             x[..., rotary_dim:]], axis=-1)
    return apply


@_partial
def _rope_at(x, positions, theta):
    """x: (B, S, H, D) rotated at absolute 1-D `positions` (S,) — the same
    rotate-half pairing as models/llama.py apply_rotary_pos_emb."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    freqs = jnp.asarray(positions, jnp.float32)[:, None] * inv   # (S, D/2)
    emb = jnp.concatenate([freqs, freqs], -1)                    # (S, D)
    cos = jnp.cos(emb).astype(x.dtype)[None, :, None, :]
    sin = jnp.sin(emb).astype(x.dtype)[None, :, None, :]
    return x * cos + _rotate_half(x) * sin


@_partial
def _rope_at_rows(x, positions, theta):
    """x: (B, 1, H, D) rotated at PER-ROW absolute `positions` (B,) — the
    ragged-batch form (continuous batching decodes every slot at its own
    position in one step)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    freqs = jnp.asarray(positions, jnp.float32)[:, None] * inv   # (B, D/2)
    emb = jnp.concatenate([freqs, freqs], -1)                    # (B, D)
    cos = jnp.cos(emb).astype(x.dtype)[:, None, None, :]
    sin = jnp.sin(emb).astype(x.dtype)[:, None, None, :]
    return x * cos + _rotate_half(x) * sin


@dataclasses.dataclass(frozen=True)
class CacheKind:
    """One kind of attention layer, as the serving block and the cache
    manager see it: layers of one kind share their pools' shapes, one block
    table per sequence and one rotary base. A Llama-shaped model has one
    kind; a model that mixes full and sliding-window layers has two."""

    name: str                 # "full" | "window": the counters' label
    num_kv: int
    head_dim: int             # of Q and K
    v_head_dim: int
    theta: float
    rotary_dim: int           # leading dims of a head that are rotated
    window: int | None = None   # positions attended to, the current included
    flat: bool = False        # pools stored [blocks, block_size, kv * dim]

    # what the cache manager may do with a sequence's cache of this kind:
    # position-addressed blocks can be mapped into another sequence (a radix
    # hit), spilled and brought back, and a rejected draft's write is
    # overwritten before it is read; behind a window the blocks are released,
    # so neither a hit nor a spill finds them
    paged = True
    rollback = True
    why_not = {
        "reuse": "a radix hit would have to bring the sliding-window "
                 "layers' last blocks too",
        "spill": "a preempted request's sliding-window blocks are not "
                 "spilled"}

    @property
    def reuse(self):
        return self.window is None

    @property
    def spill(self):
        return self.window is None


@dataclasses.dataclass(frozen=True)
class StateKind:
    """One kind of RECURRENT layer (a gated delta-rule linear-attention
    layer), as the serving block and the cache manager see it: a sequence's
    cache of this kind is not addressed by position. It is one float32 state
    ``[num_heads, key_dim, value_dim]`` and the last ``conv_kernel - 1``
    inputs of the layer's convolutions, in the SLOT of the sequence
    (``paged_kv.StateSlots``). The state at an earlier position is gone, so
    nothing of it can be reused by another sequence, spilled in part or
    rolled back."""

    name: str                 # "linear": the counters' label
    num_heads: int
    key_dim: int
    value_dim: int
    conv_kernel: int
    neg_eigval: bool = True   # beta in (0, 2)

    paged = False
    reuse = False
    spill = False
    rollback = False
    window = None
    why_not = {
        "reuse": "a radix hit would need a recurrent layer's state AT the "
                 "hit's last position, and a slot keeps only the newest",
        "spill": "a preempted request's recurrent state is not spilled",
        "rollback": "a rejected draft cannot be rolled back out of a "
                    "recurrent state"}

    @property
    def conv_width(self):
        return self.num_heads * (2 * self.key_dim + self.value_dim)


class _PagedCache:
    """Cache value of the paged engine: the block pools (device) plus THEIR
    pager (host allocator + tables). The pager travels with the cache, not
    the engine, so interleaved prefills cannot cross-wire block tables."""

    __slots__ = ("pager", "pools")

    def __init__(self, pager, pools):
        self.pager = pager
        self.pools = pools


class _Pagers:
    """The pagers of a model with several cache kinds, for lockstep
    decoding: rows advance together in every kind, nothing is shared (no
    beam fork), and a window kind keeps its whole history."""

    def __init__(self, pagers):
        self.pagers = pagers
        self.batch = pagers[0].batch

    def ensure_capacity(self, seq_lens_next):
        for pg in self.pagers:
            pg.ensure_capacity(seq_lens_next)

    def make_tail_exclusive(self, pos, pools):
        return pools                     # no fork, so no shared block

    def fork_rows(self, parent_rows):
        raise NotImplementedError(
            "beam search over several cache kinds is not supported")


def _tables(pager):
    """The block tables a paged program takes: one a cache kind."""
    if isinstance(pager, _Pagers):
        return tuple(pg.block_tables for pg in pager.pagers)
    return (pager.block_tables,)


class LlamaDecodeEngine:
    """Greedy/temperature decoding with a per-layer KV cache."""

    # a factor on V after its projection (1: none); a model that has one
    # sets it in _extract
    v_scale = 1.0

    def __init__(self, model, max_len=None, kv_cache_dtype=None,
                 kv_cache_layout=None, block_size=64):
        """``kv_cache_dtype="int8"`` stores K/V quantized per (token, head)
        with fp32 absmax scales: half the KV-cache HBM footprint and read
        bandwidth — decode attention is KV-bandwidth-bound, so this is the
        serving lever (the reference's cache-KV int8 capability in
        quantized inference); dequantization happens after the int8 loads,
        inside the compiled step.

        ``kv_cache_layout="paged"`` stores K/V in a block pool indexed by
        per-sequence block tables (models/paged_kv.py; the reference's
        block_multihead_attention serving mode): blocks are granted lazily
        on the host as decoding advances, so cache memory scales with
        actual tokens, not batch * max_len."""
        cfg = model.config
        self.config = cfg
        if kv_cache_dtype not in (None, "int8"):
            raise ValueError(f"unsupported kv_cache_dtype {kv_cache_dtype!r}")
        self.kv_int8 = kv_cache_dtype == "int8"
        if kv_cache_layout not in (None, "dense", "paged"):
            raise ValueError(
                f"unsupported kv_cache_layout {kv_cache_layout!r}")
        self.paged = kv_cache_layout == "paged"
        self.block_size = int(block_size)
        self._pager = None   # built at prefill (batch known then)
        self.max_len = int(max_len or cfg.max_position_embeddings)
        self.num_heads = cfg.num_attention_heads
        self._extract(model)
        # the first kind's sizes under their old names: the dense cache and
        # the int8 pools know one kind only
        kind = self.kinds[0]
        if not kind.paged:
            raise ValueError("a model's first cache kind is a paged one (its "
                             "pager is the one the block tables, the radix "
                             "cache and the spill layer work on)")
        self.num_kv, self.head_dim = kind.num_kv, kind.head_dim
        self.theta = kind.theta
        if len(self.kinds) > 1 or kind.flat or kind.window is not None \
                or kind.v_head_dim != kind.head_dim:
            if not self.paged or self.kv_int8:
                raise ValueError(
                    "a model whose layers differ in kind, or in "
                    "their K and V widths, is served from the paged "
                    "bfloat16/float32 cache only (kv_cache_layout='paged', "
                    "no kv_cache_dtype)")
        # every weight the compiled programs read, as ONE pytree, passed to
        # them as an argument (the last one): an array a jitted function
        # merely closes over is embedded in the program as a literal, which
        # at real widths makes a multi-GB module that cannot be serialized
        # and a second copy of the weights on the device
        self.weights = {"layers": self.layers, "emb": self.emb,
                        "norm_w": self.norm_w, "head_w": self.head_w}

    def _extract(self, model):
        """Read the model into what the serving block runs on: ``kinds``
        (the cache kinds), ``layer_kind`` (each layer's index into them),
        ``layers`` (one dict of weights a layer; its keys tell the block
        what the layer is: ``sink`` a per-head sink logit, ``router`` an
        expert MLP in place of ``gate`` / ``up`` / ``down``), ``emb``,
        ``norm_w``, ``head_w``, ``eps``. A Llama-shaped decoder is the
        special case of one kind, whole-head rotary and dense MLPs."""
        cfg = model.config
        self.eps = cfg.rms_norm_eps
        self.kinds = (CacheKind(
            "full", cfg.num_key_value_heads, cfg.head_dim, cfg.head_dim,
            cfg.rope_theta, cfg.head_dim),)
        self.layer_kind = (0,) * len(model.llama.layers)

        def _w(layer):
            """Dense weight of a Linear OR a WeightOnlyLinear (dequantized
            once at engine build; the per-step bandwidth saving of the int8
            form belongs to the weight_only_linear op path)."""
            if hasattr(layer, "weight"):
                return layer.weight.value
            from ..quantization.weight_only import weight_dequantize

            return weight_dequantize(layer.quant_weight, layer.weight_scale,
                                     algo=layer.algo,
                                     k=layer.in_features).value

        self.layers = []
        for lyr in model.llama.layers:
            a, m = lyr.self_attn, lyr.mlp
            self.layers.append(dict(
                ln1=lyr.input_layernorm.weight.value,
                ln2=lyr.post_attention_layernorm.weight.value,
                wq=_w(a.q_proj), wk=_w(a.k_proj),
                wv=_w(a.v_proj), wo=_w(a.o_proj),
                gate=_w(m.gate_proj), up=_w(m.up_proj),
                down=_w(m.down_proj)))
        self.emb = model.llama.embed_tokens.weight.value
        self.norm_w = model.llama.norm.weight.value
        head = model.lm_head
        self.head_w = (jnp.swapaxes(self.emb, 0, 1) if head._tied
                       else head.weight.value)

    # -- cache ---------------------------------------------------------------
    def init_cache(self, batch):
        shape = (batch, self.max_len, self.num_kv, self.head_dim)
        if self.kv_int8:
            sshape = shape[:-1]  # one absmax scale per (token, kv head)
            return [(jnp.zeros(shape, jnp.int8), jnp.zeros(sshape, jnp.float32),
                     jnp.zeros(shape, jnp.int8), jnp.zeros(sshape, jnp.float32))
                    for _ in self.layers]
        dt = self.emb.dtype
        return [(jnp.zeros(shape, dt), jnp.zeros(shape, dt))
                for _ in self.layers]

    @staticmethod
    def _quantize_kv(x):
        """(B, S, H, D) -> int8 values + per-(token, head) fp32 scales."""
        scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0
        scale = jnp.maximum(scale, 1e-8)
        q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                     -127, 127).astype(jnp.int8)
        return q, scale

    def _attend_int8(self, q, ck_q, ck_s, cv_q, cv_s, pos_mask):
        """Attention over the int8 cache WITHOUT materializing a
        dequantized copy (that would re-create the full-precision HBM
        traffic the int8 cache exists to remove): the per-(token, head)
        scales fold into the score and value einsums —
        logits[b,h,s,t] = (q . k_q) * ck_s[b,t,h];
        out = (probs * cv_s)[b,h,s,t] @ v_q[b,t,h,d]."""
        rep = self.num_heads // self.num_kv
        if rep > 1:
            ck_q = jnp.repeat(ck_q, rep, axis=2)
            cv_q = jnp.repeat(cv_q, rep, axis=2)
            ck_s = jnp.repeat(ck_s, rep, axis=2)
            cv_s = jnp.repeat(cv_s, rep, axis=2)
        logits = jnp.einsum("bshd,bthd->bhst", q, ck_q.astype(q.dtype))
        logits = (logits.astype(jnp.float32)
                  * jnp.transpose(ck_s, (0, 2, 1))[:, :, None, :]
                  / np.sqrt(self.head_dim))
        logits = jnp.where(pos_mask[:, None, :, :], logits,
                           jnp.asarray(-1e30, logits.dtype))
        probs = jax.nn.softmax(logits, -1)
        pv = probs * jnp.transpose(cv_s, (0, 2, 1))[:, :, None, :]
        out = jnp.einsum("bhst,bthd->bshd", pv.astype(q.dtype),
                         cv_q.astype(q.dtype))
        return out

    # -- functional blocks ---------------------------------------------------
    def _attend(self, q, ck, cv, pos_mask, sink=None):
        """q: (B, S, Hq, D) vs full cache (B, max_len, Hkv, D); V may have
        its own width. ``sink`` (Hq,) is one more logit a head in the
        softmax's denominator, with no value row."""
        rep = self.num_heads // ck.shape[2]
        if rep > 1:
            ck = jnp.repeat(ck, rep, axis=2)
            cv = jnp.repeat(cv, rep, axis=2)
        logits = jnp.einsum("bshd,bthd->bhst", q, ck) / np.sqrt(q.shape[-1])
        logits = jnp.where(pos_mask[:, None, :, :], logits,
                           jnp.asarray(-1e30, logits.dtype))
        # promote, don't demote: f64 parity runs must stay f64
        ct = jnp.promote_types(q.dtype, jnp.float32)
        logits = logits.astype(ct)
        if sink is not None:
            col = jnp.broadcast_to(sink.astype(ct)[None, :, None, None],
                                   logits.shape[:3] + (1,))
            logits = jnp.concatenate([logits, col], axis=-1)
        probs = jax.nn.softmax(logits, -1).astype(q.dtype)
        if sink is not None:
            probs = probs[..., :-1]
        return jnp.einsum("bhst,bthd->bshd", probs, cv)

    def _block(self, p, x, cache_kv, positions, pos_mask):
        B, S, _ = x.shape
        q, k, v = self._qkv_rope(p, x, positions)
        start = positions[0]
        if self.kv_int8:
            ck_q, ck_s, cv_q, cv_s = cache_kv
            kq, ks = self._quantize_kv(k)
            vq, vs = self._quantize_kv(v)
            ck_q = lax.dynamic_update_slice(ck_q, kq, (0, start, 0, 0))
            ck_s = lax.dynamic_update_slice(ck_s, ks, (0, start, 0))
            cv_q = lax.dynamic_update_slice(cv_q, vq, (0, start, 0, 0))
            cv_s = lax.dynamic_update_slice(cv_s, vs, (0, start, 0))
            new_cache = (ck_q, ck_s, cv_q, cv_s)
            attn = self._attend_int8(q, ck_q, ck_s, cv_q, cv_s, pos_mask)
        else:
            ck, cv = cache_kv
            ck = lax.dynamic_update_slice(ck, k, (0, start, 0, 0))
            cv = lax.dynamic_update_slice(cv, v, (0, start, 0, 0))
            new_cache = (ck, cv)
            attn = self._attend(q, ck, cv, pos_mask)
        return self._post_attn(p, x, attn)[0], new_cache

    def _forward(self, ids, cache, start_pos, w):
        """ids: (B, S) absolute positions start_pos..start_pos+S-1."""
        B, S = ids.shape
        x = w["emb"][ids]
        positions = start_pos + jnp.arange(S)
        t = jnp.arange(self.max_len)[None, None, :]          # cache slots
        s = positions[None, :, None]                          # query slots
        pos_mask = jnp.broadcast_to(t <= s, (B, S, self.max_len))
        new_cache = []
        for p, ckv in zip(w["layers"], cache):
            x, ckv = self._block(p, x, ckv, positions, pos_mask)
            new_cache.append(ckv)
        x = _rms(x, w["norm_w"], self.eps)
        return x @ w["head_w"], new_cache

    # -- paged forward paths (models/paged_kv.py pool + tables) --------------
    def _qkv_rope(self, p, x, positions, kind=None, rows=False):
        """Shared pre-attention: rms -> q/k/v projections -> RoPE on the
        kind's rotary dims, at ``positions`` (S,) shared by the batch, or
        with ``rows`` at one position a row (S == 1). V is scaled where the
        layer says so (``v_scale``: a static number among its weights'
        keys)."""
        kind = self.kinds[0] if kind is None else kind
        B, S, _ = x.shape
        # the layer's weights' keys say what it has: a norm before the
        # projections (``ln1``), norms over the whole q and k projections
        # (``q_norm``, ``k_norm``)
        h = _rms(x, p["ln1"], self.eps) if "ln1" in p else x
        q = h @ p["wq"]
        if "q_norm" in p:
            q = _rms(q, p["q_norm"], self.eps)
        q = q.reshape(B, S, self.num_heads, kind.head_dim)
        k = h @ p["wk"]
        if "k_norm" in p:
            k = _rms(k, p["k_norm"], self.eps)
        k = k.reshape(B, S, kind.num_kv, kind.head_dim)
        v = (h @ p["wv"]).reshape(B, S, kind.num_kv, kind.v_head_dim)
        if self.v_scale != 1.0:
            v = v * jnp.asarray(self.v_scale, v.dtype)
        if not kind.rotary_dim:         # no rotary embedding on this kind
            return q, k, v
        rope = _rope_at_rows if rows else _rope_at
        return (rope(q, positions, kind.theta, kind.rotary_dim),
                rope(k, positions, kind.theta, kind.rotary_dim), v)

    def _post_attn(self, p, x, attn, valid=None):
        """Shared epilogue: output proj + residual + rms + the layer's MLP,
        dense SwiGLU or (``router`` among the layer's weights) the experts
        this engine holds. Returns ``(x, pairs)``: ``pairs`` [4] int32 counts
        the (token, expert) pairs on held experts and in all, and the held
        experts that got one, over ``valid`` tokens, and the rows of the
        grouped product's row tiles; None for a dense layer."""
        B, S = x.shape[0], x.shape[1]
        out = attn.reshape(B, S, -1) @ p["wo"]
        if "post_attn_norm" in p:       # the norm on the sublayer's OUTPUT
            out = _rms(out, p["post_attn_norm"], self.eps)
        x = x + out
        h2 = _rms(x, p["ln2"], self.eps) if "ln2" in p else x
        if "router" not in p:
            mlp = (jax.nn.silu(h2 @ p["gate"]) * (h2 @ p["up"])) @ p["down"]
            if "post_ff_norm" in p:
                mlp = _rms(mlp, p["post_ff_norm"], self.eps)
            return x + mlp, None
        from ..incubate.distributed.models.moe.held_experts import (
            held_experts_mlp)

        y, pairs = held_experts_mlp(
            h2.reshape(B * S, -1), p["router"], p["router_bias"], p["w1"],
            p["w3"], p["w2"], self.held_lo, self.top_k,
            None if valid is None else valid.reshape(B * S))
        return x + y.reshape(x.shape), pairs

    def _block_paged(self, li, p, x, pool, tables, positions, valid=None,
                     prompt=False, counted=None, rows=None, runs=None):
        """THE serving block of layer ``li``, for every paged program.

        One token per LANE (``x`` (T, 1, hidden)) at a per-lane position
        against a per-lane block-table row: the continuous-batching MIXED
        step (decode lanes, draft-verify lanes and the consecutive prompt
        tokens of a chunk share one program; ``valid`` marks the lanes that
        are real, padding lanes' writes are dropped), the decode BURST and
        lockstep decoding (``valid`` None: every row writes, an inactive
        row into the null block its zero table row points at; ``counted``
        then marks the rows whose expert pairs count). Writes land
        before the attention reads the pool (kernel or gather: paged_kv
        picks), so lanes of one chunk see each other through it, causal by
        absolute position. ``rows`` (the mixed step's slot ids) tells the
        attention which lanes sit on one table row: a chunk's lanes then
        form query tiles that walk the row once.

        With ``prompt`` the lockstep PREFILL: ``x`` (B, S, hidden), causal
        self-attention within the prompt (the history IS the prompt),
        ``positions`` the prompts' lengths, k/v written into the
        sequences' blocks.

        The layer's kind gives the pool's shapes, the rotary base and dims,
        and the window; its weights' keys give the sink and the MLP.

        A RECURRENT kind (``StateKind``) has no blocks: its pool entry is
        the slots' ``(state, conv)`` and ``runs`` the plan of the step's
        lanes (``_plan_runs``; None where lane ``i`` is slot ``i``'s next
        token: the burst, lockstep decoding): a chunk's lanes go through the
        recurrence in order from their slot's state, padding lanes through
        the null slot."""
        from . import paged_kv as _pk

        kind = self.kinds[self.layer_kind[li]]
        if not kind.paged:
            from .linear_attention import mixer

            B, S, _ = x.shape
            mix, pool = mixer(kind, p, x.reshape(B * S, -1), pool,
                              positions, runs, self.eps)
            x, pairs = self._post_attn(
                p, x, mix.reshape(B, S, -1),
                valid if counted is None else counted)
            return x, pool, pairs
        sink = p.get("sink")
        if prompt:
            B, S, _ = x.shape
            q, k, v = self._qkv_rope(p, x, jnp.arange(S), kind)
            t_idx = jnp.arange(S)
            seen = t_idx[None, None, :] <= t_idx[None, :, None]
            if kind.window is not None:
                seen = seen & (t_idx[None, None, :]
                               > t_idx[None, :, None] - kind.window)
            pos_mask = jnp.broadcast_to(seen, (B, S, S))
            if self.kv_int8:
                kq, kscale = self._quantize_kv(k)
                vq, vscale = self._quantize_kv(v)
                pool = _pk.paged_write_prefill_int8(
                    *pool, tables, positions, kq, kscale, vq, vscale)
                # attend the QUANTIZED prompt, exactly like the dense int8
                # engine's prefill (_block -> _attend_int8 over the written
                # cache) — full-precision prompt attention here would give
                # the paged engine different logits than dense int8
                attn = self._attend_int8(q, kq, kscale, vq, vscale, pos_mask)
            else:
                pool = _pk.paged_write_prefill(*pool, tables, positions, k, v)
                attn = self._attend(q, k, v, pos_mask, sink)
            x, _pairs = self._post_attn(p, x, attn)
            return x, pool, None
        q, k, v = self._qkv_rope(p, x, positions, kind, rows=True)
        if self.kv_int8:
            kq, kscale = self._quantize_kv(k)      # (T, 1, kv, D)
            vq, vscale = self._quantize_kv(v)
            new = (kq[:, 0], kscale[:, 0], vq[:, 0], vscale[:, 0])
            if valid is None:
                pool = _pk.paged_write_decode_int8(*pool, tables, positions,
                                                   *new)
            else:
                pool = _pk.paged_write_mixed_int8(*pool, tables, positions,
                                                  valid, *new)
            attn = _pk.paged_attention_decode_int8(
                q[:, 0], *pool, tables, positions)[:, None]
        else:
            if valid is None:
                pool = _pk.paged_write_decode(*pool, tables, positions,
                                              k[:, 0], v[:, 0])
            else:
                pool = _pk.paged_write_mixed(*pool, tables, positions, valid,
                                             k[:, 0], v[:, 0])
            attn = _pk.paged_attention_decode(
                q[:, 0], *pool, tables, positions, window=kind.window,
                sink=sink, rows=rows)[:, None]
        x, pairs = self._post_attn(p, x, attn,
                                   valid if counted is None else counted)
        return x, pool, pairs

    def _layers_paged(self, w, x, pools, tables, positions, valid=None,
                      prompt=False, counted=None, rows=None):
        """Every layer's ``_block_paged`` in turn. ``tables`` holds one
        block table a cache kind (already the lanes' rows, for a mixed
        step). Returns ``(x, pools, pairs)``: ``pairs`` [4] int32 summed
        over the expert layers, None for a model without any."""
        new_pools, total = [], None
        runs = self._plan_runs(x, pools, positions, valid, prompt, rows)
        for li, (p, pool) in enumerate(zip(w["layers"], pools)):
            x, pool, pairs = self._block_paged(
                li, p, x, pool, tables[self.layer_kind[li]], positions,
                valid, prompt, counted, rows, runs)
            new_pools.append(pool)
            if pairs is not None:
                total = pairs if total is None else total + pairs
        return x, new_pools, total

    def _plan_runs(self, x, pools, positions, valid, prompt, rows):
        """The runs of a step's lanes, planned ONCE for all recurrent layers
        (None for a model without any, and where lane ``i`` is slot ``i``'s
        next token). A mixed step's lanes are its pack (``rows`` the slot
        ids); a lockstep prefill's are its ``B`` prompts one behind the
        other, row ``b``'s at positions 0 .. S - 1."""
        li = next((i for i, ki in enumerate(self.layer_kind)
                   if not self.kinds[ki].paged), None)
        if li is None or (rows is None and not prompt):
            return None
        from ..ops.pallas.gated_delta_rule import plan_runs

        slots = pools[li][0].shape[0]
        if prompt:
            B, S, _ = x.shape
            rows = jnp.repeat(jnp.arange(B, dtype=jnp.int32), S)
            positions = jnp.tile(jnp.arange(S, dtype=jnp.int32), B)
            valid = jnp.ones((B * S,), bool)
        return plan_runs(rows, positions, valid, slots)

    def build_mixed_step(self):
        """The continuous-batching mixed step as a pure function for the
        serving engine to jit (donated pools): a ``(token_ids, slot_ids,
        positions)`` pack of ``T`` lanes — decode slots, draft-verify
        lanes and prefill chunks interleaved — runs ONE forward, writes
        every lane's K/V into its slot's paged blocks, and returns the
        per-lane greedy token (read only for lanes the scheduler marked
        as emitting). Shapes are fixed by the token budget ``T``, so XLA
        compiles this exactly once. ``tables`` is a tuple: one block table
        a cache kind.

        Verify mode (self-speculative decoding) rides the SAME program:
        ``chain[i]`` marks lane ``i`` as carrying a DRAFT token that
        continues lane ``i-1``'s sequence. The program scores every lane
        as usual (each lane's attention masks to its own position, so a
        draft lane is arithmetically identical to the single decode step
        it speculates) and additionally computes, device-side, the
        longest-agreeing-prefix accept flags: draft lane ``i`` is
        accepted iff every draft before it in its chain was accepted AND
        lane ``i-1``'s greedy token equals the draft lane ``i`` carries.
        Rejected lanes wrote KV at positions past the accept fence — the
        scheduler rolls them back by simply not advancing ``seq_lens``
        (paged writes are position-addressed; the stale positions are
        overwritten before any mask can read them). With ``chain`` all
        False (speculation off) the flags are all zero and row 0 is the
        plain mixed step — one program serves both modes, so greedy
        outputs are bit-identical with speculation on or off.

        A model with expert layers gets a third row in the same array (no
        second download): ``[pairs on held experts, pairs routed, held
        experts that got a pair]`` of the valid lanes and the rows of the
        grouped product's row tiles, summed over its expert layers, then
        zeros."""
        def serving_mixed_step(pack, pools, tables, slot_ids, valid, chain,
                               w):
            # (the function's name is the compiled program's: a device
            # trace lists it as jit_serving_mixed_step)
            # pack (2, T) int32: row 0 = token ids, row 1 = positions
            # (one fused upload per step — these are the only per-step
            # transfers; slot_ids/valid/chain are cached per composition)
            token_ids, positions = pack[0], pack[1]
            x = w["emb"][token_ids][:, None]        # (T, 1, hidden)
            # (T, max_blocks) a paged kind; a recurrent kind has no table
            row_tables = tuple(None if t is None else t[slot_ids]
                               for t in tables)
            x, new_pools, pairs = self._layers_paged(
                w, x, pools, row_tables, positions, valid, rows=slot_ids)
            x = _rms(x, w["norm_w"], self.eps)
            logits = (x @ w["head_w"])[:, -1]
            # argmax INSIDE the program: the scheduler transfers one
            # (2, T) int32 lane matrix per step, never a vocab logits row
            nt = jnp.argmax(logits, -1).astype(jnp.int32)
            # segmented running-AND along draft chains (accept = my draft
            # token equals the previous lane's greedy token, and every
            # draft before me agreed): a (value, segment-start) monoid so
            # the scan is O(log T) on device
            prev = jnp.roll(nt, 1)
            agree = jnp.where(chain, prev == token_ids, True)
            start = ~chain

            def comb(a, b):
                av, as_ = a
                bv, bs_ = b
                return jnp.where(bs_, bv, av & bv), as_ | bs_

            acc, _ = lax.associative_scan(comb, (agree, start))
            accept = acc & chain
            rows = [nt, accept.astype(jnp.int32)]
            if pairs is not None:
                rows.append(jnp.zeros_like(nt).at[:pairs.shape[0]].set(pairs))
            return jnp.stack(rows), new_pools

        return serving_mixed_step

    def build_decode_burst(self, k):
        """``k`` ragged decode iterations fused into ONE program via
        lax.scan — the serving engine's steady-state path when no prefill
        or admission work is pending: one dispatch + one host round-trip
        emits ``k`` tokens per slot instead of one. Inactive rows write
        into the reserved null block (their table rows are zero), exactly
        like the single-step path. Returns (B, k) tokens; a model with
        expert layers appends four rows: each iteration's pairs on held
        experts, pairs routed and held experts that got a pair, over the
        rows whose position is not 0, and the rows of the grouped product's
        row tiles."""
        def serving_decode_burst(pack, pools, tables, w):
            # (jit_serving_decode_burst in a device trace)
            # pack (2, B) int32: row 0 = current tokens, row 1 = per-row
            # positions (one fused upload per burst)
            tokens, lens = pack[0][:, None], pack[1]
            counts = self.has_experts

            def body(carry, _):
                toks, pools_c, lens_c = carry
                x = w["emb"][toks]
                # every row writes (valid None), an idle one into the null
                # block; pairs are counted over the rows that run
                x, new_pools, pairs = self._layers_paged(
                    w, x, pools_c, tables, lens_c,
                    counted=lens_c > 0 if counts else None)
                x = _rms(x, w["norm_w"], self.eps)
                logits = (x @ w["head_w"])[:, -1]
                nxt = jnp.argmax(logits, -1).astype(jnp.int32)
                out = nxt if pairs is None else jnp.concatenate([nxt, pairs])
                return (nxt[:, None], new_pools, lens_c + 1), out

            (toks, pools, lens), outs = lax.scan(
                body, (tokens, pools, lens), None, length=k)
            return jnp.swapaxes(outs, 0, 1), pools    # (B [+ 4], k)

        return serving_decode_burst

    @property
    def has_experts(self):
        return any("router" in p for p in self.layers)

    @functools.cached_property
    def _prefill_paged_jit(self):
        def run(ids, pools, tables, lens, w):
            x, new_pools, _ = self._layers_paged(w, w["emb"][ids], pools,
                                                 tables, lens, prompt=True)
            x = _rms(x, w["norm_w"], self.eps)
            return x @ w["head_w"], new_pools

        return jax.jit(run, donate_argnums=(1,))

    @functools.cached_property
    def _step_paged_jit(self):
        def run(token, pools, tables, pos, w):
            # lens derives from pos INSIDE the trace: the engine decodes in
            # lockstep, so no per-token host-built array is needed
            lens = jnp.full((token.shape[0],), pos, jnp.int32)
            x, new_pools, _ = self._layers_paged(w, w["emb"][token], pools,
                                                 tables, lens)
            x = _rms(x, w["norm_w"], self.eps)
            return (x @ w["head_w"])[:, -1], new_pools

        return jax.jit(run, donate_argnums=(1,))

    def make_pagers(self, batch, num_blocks=None, window_blocks=None):
        """One ``PagedKVCache`` a paged cache kind, one ``StateSlots`` a
        recurrent kind (the first is the whole-length
        kind's, whose pool ``num_blocks`` sizes: default, the worst case of
        ``batch`` rows + the null block; ``window_blocks`` sizes a window
        kind's, default the same: a caller that releases blocks behind the
        window passes less) and the per-layer pool entries, each layer's
        from its kind's pager."""
        from .paged_kv import PagedKVCache, StateSlots

        max_blocks = -(-self.max_len // self.block_size)
        pagers = []
        for ki, kind in enumerate(self.kinds):
            if not kind.paged:          # a slot a row, and the null slot
                pagers.append(StateSlots(self.layer_kind.count(ki), batch,
                                         kind, self.emb.dtype))
                continue
            n = num_blocks if kind.window is None else window_blocks
            pagers.append(PagedKVCache(
                num_layers=self.layer_kind.count(ki),
                num_blocks=batch * max_blocks + 1 if n is None else n,
                block_size=self.block_size, kv_heads=kind.num_kv,
                head_dim=kind.head_dim, batch=batch,
                max_blocks_per_seq=max_blocks, dtype=self.emb.dtype,
                quantized=self.kv_int8, v_head_dim=kind.v_head_dim,
                flat=kind.flat, window=kind.window))
        nth = [0] * len(self.kinds)
        pools = []
        for ki in self.layer_kind:
            pg, i = pagers[ki], nth[ki]
            nth[ki] += 1
            if not self.kinds[ki].paged:
                pools.append((pg.state[i], pg.conv[i]))
                continue
            pools.append((pg.k[i], pg.k_scale[i], pg.v[i], pg.v_scale[i])
                         if self.kv_int8 else (pg.k[i], pg.v[i]))
        return pagers, pools

    def _init_paged(self, batch):
        # pools sized for the worst case + the reserved null block; blocks
        # are still GRANTED lazily, so a short-lived batch touches few.
        # Lockstep decoding keeps a window layer's whole history (its mask
        # holds the window); only the continuous-batching engine releases
        pagers, pools = self.make_pagers(batch)
        return (pagers[0] if len(pagers) == 1 else _Pagers(pagers)), pools

    # -- public API ----------------------------------------------------------
    @functools.cached_property
    def _prefill_jit(self):
        return jax.jit(lambda ids, cache, w: self._forward(ids, cache, 0, w))

    @functools.cached_property
    def _step_jit(self):
        @functools.partial(jax.jit, donate_argnums=(1,))
        def step(token, cache, pos, w):
            logits, cache = self._forward(token, cache, pos, w)
            return logits[:, -1], cache

        return step

    def prefill(self, input_ids):
        ids = jnp.asarray(getattr(input_ids, "value", input_ids), jnp.int32)
        B, S = ids.shape
        if self.paged:
            pager, pools = self._init_paged(B)
            self._pager = pager   # introspection only; the CACHE owns it
            pager.ensure_capacity([S] * B)
            lens = jnp.full((B,), S, jnp.int32)
            logits, pools = self._prefill_paged_jit(
                ids, pools, _tables(pager), lens, self.weights)
            return logits[:, -1], _PagedCache(pager, pools), S
        cache = self.init_cache(B)
        logits, cache = self._prefill_jit(ids, cache, self.weights)
        return logits[:, -1], cache, S

    def decode_step(self, token, cache, pos):
        """token (B, 1) int32 -> (next-token logits (B, V), cache')."""
        if int(pos) >= self.max_len:
            # dynamic_update_slice would silently CLAMP the write position,
            # overwriting the last slot while RoPE keeps advancing
            raise ValueError(
                f"decode position {int(pos)} exceeds the cache "
                f"(max_len={self.max_len}); build the engine with a larger "
                "max_len")
        if self.paged:
            if not isinstance(cache, _PagedCache):
                raise TypeError(
                    "paged decode_step needs the cache returned by "
                    "prefill() (each prefill owns its own block tables; "
                    "engine-level state would cross-wire interleaved "
                    "sequences)")
            pager = cache.pager
            # host-side block grant for position pos (writes land AT pos),
            # then copy-on-write for any SHARED tail block (beam forks;
            # cheap no-op when nothing is shared)
            pager.ensure_capacity([int(pos) + 1] * pager.batch)
            from .paged_kv import CowPoolExhausted

            try:
                pools = pager.make_tail_exclusive(int(pos), cache.pools)
            except CowPoolExhausted as e:
                # the CoW donated the cache's pools before running dry:
                # adopt the replacement so a caller that frees rows and
                # retries holds live buffers, not consumed ones
                cache.pools = e.pools
                raise
            logits, pools = self._step_paged_jit(
                jnp.asarray(token, jnp.int32), pools,
                _tables(pager), jnp.asarray(pos, jnp.int32),
                self.weights)
            return logits, _PagedCache(pager, pools)
        return self._step_jit(jnp.asarray(token, jnp.int32), cache,
                              jnp.asarray(pos, jnp.int32), self.weights)

    def _select(self, logits, temperature, top_k, top_p, key):
        """Greedy (temperature 0) or temperature/top-k/top-p sampling —
        the generation config surface of the reference's generate stack."""
        if not temperature:
            return jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        logits = logits.astype(jnp.float32) / float(temperature)
        if top_k:
            kth = jax.lax.top_k(logits, int(top_k))[0][:, -1:]
            logits = jnp.where(logits < kth, -1e30, logits)
        if top_p is not None and top_p < 1.0:
            sort = jnp.sort(logits, axis=-1)[:, ::-1]
            probs = jax.nn.softmax(sort, axis=-1)
            cum = jnp.cumsum(probs, axis=-1)
            # smallest set whose mass >= top_p: cutoff at the first crossing
            mask_sorted = cum - probs < top_p
            kth = jnp.where(mask_sorted, sort, jnp.inf).min(
                axis=-1, keepdims=True)
            logits = jnp.where(logits < kth, -1e30, logits)
        tok = jax.random.categorical(key, logits, axis=-1)
        return tok.astype(jnp.int32)[:, None]

    def generate(self, input_ids, max_new_tokens=32, temperature=0.0,
                 top_k=0, top_p=1.0, seed=0, eos_token_id=None):
        """Decode with the cache: O(S + T) attention work per token instead of
        generate()'s O((S+T)^2) prefix recompute. temperature=0 is greedy;
        otherwise temperature/top-k/top-p sampling. With ``eos_token_id``, a
        finished row keeps emitting EOS (shapes stay static for the compiled
        step; the host loop exits early once EVERY row has finished)."""
        ids = getattr(input_ids, "value", input_ids)
        need = int(ids.shape[1]) + int(max_new_tokens)
        if need > self.max_len:
            raise ValueError(
                f"prompt ({ids.shape[1]}) + max_new_tokens ({max_new_tokens})"
                f" = {need} exceeds the cache (max_len={self.max_len})")
        if max_new_tokens <= 0:
            ids2 = jnp.asarray(ids, jnp.int32)
            return ids2[:, :0]
        key = jax.random.PRNGKey(seed)
        logits, cache, pos = self.prefill(input_ids)
        key, sub = jax.random.split(key)
        tok = self._select(logits, temperature, top_k, top_p, sub)
        finished = None
        if eos_token_id is not None:
            finished = tok[:, 0] == eos_token_id
        out = [tok]
        for i in range(max_new_tokens - 1):
            # poll for all-finished only every few steps: the .all() read is
            # a host-device sync that would otherwise serialize the async
            # dispatch pipeline on every token (frozen rows are already
            # masked to EOS, so a late exit is correct, just not early)
            if (finished is not None and i % 8 == 7
                    and bool(finished.all())):
                # pad the remainder with EOS without running the model
                pad = jnp.full_like(out[-1], eos_token_id)
                out.extend([pad] * (max_new_tokens - len(out)))
                break
            logits, cache = self.decode_step(out[-1], cache, pos)
            pos += 1
            key, sub = jax.random.split(key)
            tok = self._select(logits, temperature, top_k, top_p, sub)
            if finished is not None:
                tok = jnp.where(finished[:, None], eos_token_id, tok)
                finished = finished | (tok[:, 0] == eos_token_id)
            out.append(tok)
        return jnp.concatenate(out, axis=1)

    # -- beam search ---------------------------------------------------------
    @functools.cached_property
    def _reorder_jit(self):
        @jax.jit
        def reorder(cache, flat_parent):
            # each layer's cache entry is a tuple of batch-major arrays
            # ((k, v) or the int8 form (k_q, k_s, v_q, v_s))
            return [tuple(jnp.take(a, flat_parent, axis=0) for a in entry)
                    for entry in cache]

        return reorder

    def beam_search(self, input_ids, beam_size=4, max_new_tokens=32,
                    length_penalty=0.0, eos_token_id=None):
        """Beam-search decoding over the KV cache (the reference's
        beam_search op family / BeamSearchDecoder capability, KV-cache form:
        beams ride the batch axis, so every step is the same compiled
        decode_step at batch B*K plus one compiled cache reorder).

        Returns (tokens (B, K, T) int32, scores (B, K) fp32), beams sorted
        best-first per batch row. ``length_penalty`` alpha normalizes final
        scores by len**alpha (0 = raw log-prob sum). EOS-finished beams are
        frozen (their score stops accumulating and the tail pads with EOS).
        """
        ids = jnp.asarray(getattr(input_ids, "value", input_ids), jnp.int32)
        B, S = ids.shape
        K, V = int(beam_size), self.head_w.shape[-1]
        if S + max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt ({S}) + max_new_tokens ({max_new_tokens}) exceeds "
                f"the cache (max_len={self.max_len})")
        if max_new_tokens <= 0:  # mirror generate(): nothing requested
            return (jnp.zeros((B, K, 0), jnp.int32),
                    jnp.zeros((B, K), jnp.float32))

        if self.paged:
            # prefill the B prompts into rows b*K of a B*K-row pager; beams
            # then FORK the prompt blocks (refcounted sharing, CoW on
            # write) instead of copying the prompt KV K times
            pager, pools = self._init_paged(B * K)
            self._pager = pager
            need = np.zeros(B * K, np.int64)
            need[::K] = S
            pager.ensure_capacity(need)
            logits, pools = self._prefill_paged_jit(
                ids, pools, (pager.block_tables[::K],),
                jnp.full((B,), S, jnp.int32), self.weights)
            logits = logits[:, -1]
            cache = _PagedCache(pager, pools)
            pos = S
        else:
            logits, cache, pos = self.prefill(ids)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)  # (B, V)
        scores, first = jax.lax.top_k(logp, K)                     # (B, K)
        # expand the cache to B*K rows: beam k of row b lives at b*K + k
        if self.paged:
            # paged prompts were prefilled into rows b*K of the B*K-row
            # pager — fork from THOSE rows (the dense base indexes the
            # B-row cache instead)
            cache.pager.fork_rows(np.repeat(np.arange(B) * K, K))
        else:
            base = (jnp.arange(B)[:, None] * jnp.ones((1, K), jnp.int32)
                    ).reshape(-1).astype(jnp.int32)
            cache = self._reorder_jit(cache, base)
        tokens = first.reshape(B, K, 1).astype(jnp.int32)
        finished = (jnp.zeros((B, K), bool) if eos_token_id is None
                    else first == eos_token_id)

        for _ in range(int(max_new_tokens) - 1):
            flat_tok = tokens[:, :, -1].reshape(B * K, 1)
            logits, cache = self.decode_step(flat_tok, cache, pos)
            pos += 1
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
            logp = logp.reshape(B, K, V)
            if eos_token_id is not None:
                # frozen beams may only extend with EOS at zero cost
                frozen = jnp.full((V,), -jnp.inf).at[eos_token_id].set(0.0)
                logp = jnp.where(finished[:, :, None], frozen[None, None],
                                 logp)
            total = scores[:, :, None] + logp                      # (B, K, V)
            scores, idx = jax.lax.top_k(total.reshape(B, K * V), K)
            parent = (idx // V).astype(jnp.int32)                  # (B, K)
            tok = (idx % V).astype(jnp.int32)
            # reorder histories + caches to the surviving parents
            tokens = jnp.take_along_axis(tokens, parent[:, :, None], axis=1)
            tokens = jnp.concatenate([tokens, tok[:, :, None]], axis=-1)
            flat_parent = (jnp.arange(B)[:, None] * K + parent).reshape(-1)
            if self.paged:
                # adopt the surviving parents' block tables (shared blocks,
                # CoW at the next write in decode_step)
                cache.pager.fork_rows(np.asarray(flat_parent))
            else:
                cache = self._reorder_jit(cache, flat_parent.astype(jnp.int32))
            if eos_token_id is not None:
                finished = jnp.take_along_axis(finished, parent, axis=1)
                finished = finished | (tok == eos_token_id)

        if length_penalty:
            if eos_token_id is None:
                lens = jnp.full((B, K), tokens.shape[-1], jnp.float32)
            else:
                lens = (tokens != eos_token_id).sum(-1).astype(jnp.float32)
                lens = jnp.maximum(lens, 1.0)
            final = scores / (lens ** float(length_penalty))
        else:
            final = scores
        order = jnp.argsort(-final, axis=-1)
        tokens = jnp.take_along_axis(tokens, order[:, :, None], axis=1)
        final = jnp.take_along_axis(final, order, axis=1)
        return tokens, final
