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


class _PagedCache:
    """Cache value of the paged engine: the block pools (device) plus THEIR
    pager (host allocator + tables). The pager travels with the cache, not
    the engine, so interleaved prefills cannot cross-wire block tables."""

    __slots__ = ("pager", "pools")

    def __init__(self, pager, pools):
        self.pager = pager
        self.pools = pools


class LlamaDecodeEngine:
    """Greedy/temperature decoding with a per-layer KV cache."""

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
        self.num_kv = cfg.num_key_value_heads
        self.head_dim = cfg.head_dim
        self.eps = cfg.rms_norm_eps
        self.theta = cfg.rope_theta

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
        # every weight the compiled programs read, as ONE pytree, passed to
        # them as an argument (the last one): an array a jitted function
        # merely closes over is embedded in the program as a literal, which
        # at real widths makes a multi-GB module that cannot be serialized
        # and a second copy of the weights on the device
        self.weights = {"layers": self.layers, "emb": self.emb,
                        "norm_w": self.norm_w, "head_w": self.head_w}

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
    def _attend(self, q, ck, cv, pos_mask):
        """q: (B, S, Hq, D) vs full cache (B, max_len, Hkv, D)."""
        rep = self.num_heads // self.num_kv
        if rep > 1:
            ck = jnp.repeat(ck, rep, axis=2)
            cv = jnp.repeat(cv, rep, axis=2)
        logits = jnp.einsum("bshd,bthd->bhst", q, ck) / np.sqrt(self.head_dim)
        logits = jnp.where(pos_mask[:, None, :, :], logits,
                           jnp.asarray(-1e30, logits.dtype))
        # promote, don't demote: f64 parity runs must stay f64
        ct = jnp.promote_types(q.dtype, jnp.float32)
        probs = jax.nn.softmax(logits.astype(ct), -1).astype(q.dtype)
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
        return self._post_attn(p, x, attn), new_cache

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
    def _qkv_rope(self, p, x, positions):
        """Shared pre-attention: rms -> q/k/v projections -> RoPE."""
        B, S, _ = x.shape
        h = _rms(x, p["ln1"], self.eps)
        q = (h @ p["wq"]).reshape(B, S, self.num_heads, self.head_dim)
        k = (h @ p["wk"]).reshape(B, S, self.num_kv, self.head_dim)
        v = (h @ p["wv"]).reshape(B, S, self.num_kv, self.head_dim)
        return (_rope_at(q, positions, self.theta),
                _rope_at(k, positions, self.theta), v)

    def _post_attn(self, p, x, attn):
        """Shared epilogue: output proj + residual + rms + SwiGLU MLP."""
        B, S = x.shape[0], x.shape[1]
        x = x + attn.reshape(B, S, -1) @ p["wo"]
        h2 = _rms(x, p["ln2"], self.eps)
        mlp = (jax.nn.silu(h2 @ p["gate"]) * (h2 @ p["up"])) @ p["down"]
        return x + mlp

    def _block_paged_prefill(self, p, x, pool, tables, lens):
        """Prompt pass: causal self-attention within the prompt (the history
        IS the prompt), k/v written into the sequence's blocks."""
        from . import paged_kv as _pk

        B, S, _ = x.shape
        q, k, v = self._qkv_rope(p, x, jnp.arange(S))
        t_idx = jnp.arange(S)
        pos_mask = jnp.broadcast_to(
            t_idx[None, None, :] <= t_idx[None, :, None], (B, S, S))
        if self.kv_int8:
            kq, kscale = self._quantize_kv(k)
            vq, vscale = self._quantize_kv(v)
            pool = _pk.paged_write_prefill_int8(*pool, tables, lens,
                                                kq, kscale, vq, vscale)
            # attend the QUANTIZED prompt, exactly like the dense int8
            # engine's prefill (_block -> _attend_int8 over the written
            # cache) — full-precision prompt attention here would give the
            # paged engine different logits than dense int8
            attn = self._attend_int8(q, kq, kscale, vq, vscale, pos_mask)
        else:
            pool = _pk.paged_write_prefill(*pool, tables, lens, k, v)
            attn = self._attend(q, k, v, pos_mask)
        return self._post_attn(p, x, attn), pool

    def _block_paged_decode(self, p, x, pool, tables, lens):
        """One decode token per row at PER-ROW position lens[b] (write and
        RoPE both happen at that position) — the same block serves lockstep
        decoding (lens = broadcast pos) and continuous batching (ragged)."""
        from . import paged_kv as _pk

        B = x.shape[0]
        h = _rms(x, p["ln1"], self.eps)
        q = (h @ p["wq"]).reshape(B, 1, self.num_heads, self.head_dim)
        k = (h @ p["wk"]).reshape(B, 1, self.num_kv, self.head_dim)
        v = (h @ p["wv"]).reshape(B, 1, self.num_kv, self.head_dim)
        q = _rope_at_rows(q, lens, self.theta)
        k = _rope_at_rows(k, lens, self.theta)
        if self.kv_int8:
            kq, kscale = self._quantize_kv(k)      # (B, 1, kv, D) already
            vq, vscale = self._quantize_kv(v)
            pool = _pk.paged_write_decode_int8(
                *pool, tables, lens, kq[:, 0], kscale[:, 0], vq[:, 0],
                vscale[:, 0])
            attn = _pk.paged_attention_decode_int8(
                q[:, 0], *pool, tables, lens)[:, None]
        else:
            pool = _pk.paged_write_decode(*pool, tables, lens,
                                          k[:, 0], v[:, 0])
            attn = _pk.paged_attention_decode(q[:, 0], *pool, tables,
                                              lens)[:, None]
        return self._post_attn(p, x, attn), pool

    def _block_paged_mixed(self, p, x, pool, row_tables, positions, valid):
        """One token per LANE at a per-lane position against a per-lane
        block-table row — the transformer block of the continuous-batching
        MIXED step, where decode lanes (one token per running request) and
        chunked-prefill lanes (consecutive prompt tokens of an admitted
        request) share one compiled program. Writes land before the
        attention reads the pool (kernel or gather: paged_kv picks), so
        prefill lanes of the same chunk see each other through it (causal
        by absolute position)."""
        from . import paged_kv as _pk

        B = x.shape[0]
        h = _rms(x, p["ln1"], self.eps)
        q = (h @ p["wq"]).reshape(B, 1, self.num_heads, self.head_dim)
        k = (h @ p["wk"]).reshape(B, 1, self.num_kv, self.head_dim)
        v = (h @ p["wv"]).reshape(B, 1, self.num_kv, self.head_dim)
        q = _rope_at_rows(q, positions, self.theta)
        k = _rope_at_rows(k, positions, self.theta)
        if self.kv_int8:
            kq, kscale = self._quantize_kv(k)      # (B, 1, kv, D)
            vq, vscale = self._quantize_kv(v)
            pool = _pk.paged_write_mixed_int8(
                *pool, row_tables, positions, valid, kq[:, 0], kscale[:, 0],
                vq[:, 0], vscale[:, 0])
            attn = _pk.paged_attention_decode_int8(
                q[:, 0], *pool, row_tables, positions)[:, None]
        else:
            pool = _pk.paged_write_mixed(*pool, row_tables, positions, valid,
                                         k[:, 0], v[:, 0])
            attn = _pk.paged_attention_decode(q[:, 0], *pool, row_tables,
                                              positions)[:, None]
        return self._post_attn(p, x, attn), pool

    def build_mixed_step(self):
        """The continuous-batching mixed step as a pure function for the
        serving engine to jit (donated pools): a ``(token_ids, slot_ids,
        positions)`` pack of ``T`` lanes — decode slots, draft-verify
        lanes and prefill chunks interleaved — runs ONE forward, writes
        every lane's K/V into its slot's paged blocks, and returns the
        per-lane greedy token (read only for lanes the scheduler marked
        as emitting). Shapes are fixed by the token budget ``T``, so XLA
        compiles this exactly once.

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
        outputs are bit-identical with speculation on or off."""
        def serving_mixed_step(pack, pools, tables, slot_ids, valid, chain,
                               w):
            # (the function's name is the compiled program's: a device
            # trace lists it as jit_serving_mixed_step)
            # pack (2, T) int32: row 0 = token ids, row 1 = positions
            # (one fused upload per step — these are the only per-step
            # transfers; slot_ids/valid/chain are cached per composition)
            token_ids, positions = pack[0], pack[1]
            x = w["emb"][token_ids][:, None]        # (T, 1, hidden)
            row_tables = tables[slot_ids]           # (T, max_blocks)
            new_pools = []
            for p, pool in zip(w["layers"], pools):
                x, pool = self._block_paged_mixed(p, x, pool, row_tables,
                                                  positions, valid)
                new_pools.append(pool)
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
            return jnp.stack([nt, accept.astype(jnp.int32)]), new_pools

        return serving_mixed_step

    def build_decode_burst(self, k):
        """``k`` ragged decode iterations fused into ONE program via
        lax.scan — the serving engine's steady-state path when no prefill
        or admission work is pending: one dispatch + one host round-trip
        emits ``k`` tokens per slot instead of one. Inactive rows write
        into the reserved null block (their table rows are zero), exactly
        like the single-step path."""
        def serving_decode_burst(pack, pools, tables, w):
            # (jit_serving_decode_burst in a device trace)
            # pack (2, B) int32: row 0 = current tokens, row 1 = per-row
            # positions (one fused upload per burst)
            tokens, lens = pack[0][:, None], pack[1]

            def body(carry, _):
                toks, pools_c, lens_c = carry
                x = w["emb"][toks]
                new_pools = []
                for p, pool in zip(w["layers"], pools_c):
                    x, pool = self._block_paged_decode(p, x, pool, tables,
                                                       lens_c)
                    new_pools.append(pool)
                x = _rms(x, w["norm_w"], self.eps)
                logits = (x @ w["head_w"])[:, -1]
                nxt = jnp.argmax(logits, -1).astype(jnp.int32)
                return (nxt[:, None], new_pools, lens_c + 1), nxt

            (toks, pools, lens), outs = lax.scan(
                body, (tokens, pools, lens), None, length=k)
            return jnp.swapaxes(outs, 0, 1), pools    # (B, k)

        return serving_decode_burst

    @functools.cached_property
    def _prefill_paged_jit(self):
        def run(ids, pools, tables, lens, w):
            x = w["emb"][ids]
            new_pools = []
            for p, pool in zip(w["layers"], pools):
                x, pool = self._block_paged_prefill(p, x, pool, tables, lens)
                new_pools.append(pool)
            x = _rms(x, w["norm_w"], self.eps)
            return x @ w["head_w"], new_pools

        return jax.jit(run, donate_argnums=(1,))

    @functools.cached_property
    def _step_paged_jit(self):
        def run(token, pools, tables, pos, w):
            # lens derives from pos INSIDE the trace: the engine decodes in
            # lockstep, so no per-token host-built array is needed
            lens = jnp.full((token.shape[0],), pos, jnp.int32)
            x = w["emb"][token]
            new_pools = []
            for p, pool in zip(w["layers"], pools):
                x, pool = self._block_paged_decode(p, x, pool, tables, lens)
                new_pools.append(pool)
            x = _rms(x, w["norm_w"], self.eps)
            return (x @ w["head_w"])[:, -1], new_pools

        return jax.jit(run, donate_argnums=(1,))

    def _init_paged(self, batch):
        from .paged_kv import PagedKVCache

        max_blocks = -(-self.max_len // self.block_size)
        # pool sized for the worst case + the reserved null block; blocks
        # are still GRANTED lazily, so a short-lived batch touches few
        pager = PagedKVCache(
            num_layers=len(self.layers), num_blocks=batch * max_blocks + 1,
            block_size=self.block_size, kv_heads=self.num_kv,
            head_dim=self.head_dim, batch=batch,
            max_blocks_per_seq=max_blocks, dtype=self.emb.dtype,
            quantized=self.kv_int8)
        if self.kv_int8:
            return pager, list(zip(pager.k, pager.k_scale,
                                   pager.v, pager.v_scale))
        return pager, list(zip(pager.k, pager.v))

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
                ids, pools, pager.block_tables, lens, self.weights)
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
                pager.block_tables, jnp.asarray(pos, jnp.int32),
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
                ids, pools, pager.block_tables[::K],
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
