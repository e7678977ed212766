"""Continuous-batching serving engine: chunked prefill + prefix-shared
paged KV over one fixed-shape compiled step.

Reference analog: the block_multihead_attention serving stack
(incubate/nn/functional/block_multihead_attention.py) exists exactly to
serve BATCHES OF SEQUENCES AT DIFFERENT POSITIONS — seq_lens_encoder /
seq_lens_decoder / block tables are its admission contract. This module is
the engine on top of that capability, TPU-first, rebuilt around three
ideas (the design of modern continuous-batching servers — Orca's
iteration-level scheduling, vLLM's paged prefix reuse — expressed as ONE
XLA program):

1. **Token-budget mixed step.** Every step packs up to ``max_step_tokens``
   lanes from a mix of decode slots (1 token each) and admitted-but-
   unprefilled requests (prefill chunks of up to ``chunk_size`` tokens)
   into a ``(token_ids, slot_ids, positions)`` pack consumed by one
   jitted, donated program (models/llama_decode.py ``build_mixed_step``).
   New requests join the running batch WITHOUT draining it, prompts never
   pad to buckets, and the pack shape is fixed by the budget — XLA
   compiles exactly once, so the recompile sentinel stays silent.
2. **Radix prefix cache.** Full KV blocks are content-hashed at prefill
   time (models/radix_cache.py); admission walks the new prompt down the
   digest chain and maps every shared block read-only into the request's
   block table (refcounts), so identical prompt prefixes neither recompute
   nor re-store their KV. A block-aligned full hit re-runs only the last
   prompt token — its write copy-on-writes the shared tail block
   (the PR 1 CoW counters fire on exactly that path).
3. **Scheduler policy + backpressure.** Prefill order is FCFS or
   shortest-prefill-first; ``decode_priority`` bounds the prefill share of
   each pack (the inter-token-latency lever of chunked prefill);
   ``submit()`` blocks on a bounded admission queue and raises a typed
   :class:`AdmissionTimeout` instead of waiting unboundedly.

Instrumentation: the paddle_tpu.monitor serving metrics (queue depth,
occupancy, pack fill, prefix-cache hits/misses/blocks-shared,
chunked-prefill depth, TTFT — docs/observability.md) plus, with span
tracing on, a per-request trace tree (ONE trace id from admission to
eviction: queue_wait / prefill_chunk / prefill / decode_step / evict,
and a per-step serving.pack_tokens span; docs/tracing.md).
"""
from __future__ import annotations

import collections
import itertools
import os
import threading
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental.layout import Format, Layout

from . import paged_kv as _pk
from ..analysis import faultinject as _fi
from ..analysis import sanitizers as _sanitizers
from .llama_decode import LlamaDecodeEngine
from .radix_cache import PrefixCache

__all__ = ["ContinuousBatchingEngine", "AdmissionTimeout", "RequestShed",
           "RequestAborted"]

_ENGINE_SEQ = itertools.count()


class AdmissionTimeout(RuntimeError):
    """submit() could not enqueue within the caller's timeout: the
    admission queue stayed full (backpressure — shed load upstream)."""


class RequestShed(AdmissionTimeout):
    """Typed load-shedding rejection: under sustained overload the engine
    sheds the LOWEST-priority work — this request (or a queued victim,
    surfaced via :meth:`ContinuousBatchingEngine.pop_shed`) was it.
    Subclasses :class:`AdmissionTimeout` so existing backpressure
    handlers keep working; ``tenant`` names who was shed."""

    def __init__(self, message, tenant="", rid=None):
        super().__init__(message)
        self.tenant = tenant
        self.rid = rid


class RequestAborted(RuntimeError):
    """An in-flight request was aborted by engine recovery (driving-
    thread death or hang): ``tokens`` carries the partial output so the
    caller can resume/retry instead of hanging silently, and ``stats``
    carries the request's partial pop_stats record (ttft_ns if the
    first token had already landed, prefill chunks, shared prefix
    tokens) so a router re-routing the work can merge them into the
    replacement request's final stats — fleet TTFT percentiles stay
    honest across a failover instead of restarting the clock."""

    def __init__(self, message, rid=None, tokens=(), tenant="",
                 stats=None):
        super().__init__(message)
        self.rid = rid
        self.tokens = list(tokens)
        self.tenant = tenant
        self.stats = stats


class _Mon:
    """Lazily-bound monitor handles (one attribute load per metric on the
    serving hot path; nothing is touched while the monitor is off)."""

    __slots__ = ("mod", "state", "trace", "tstate", "queue_depth",
                 "occupancy", "prefill", "decode", "tokens", "evictions",
                 "ttft", "admitted", "rejected", "adm_rejected",
                 "pack", "chunk_depth", "pc_hits", "pc_misses", "pc_shared",
                 "pc_blocks", "pc_evictions",
                 "shed", "tenant_depth", "aborted", "recoveries",
                 "preemptions", "cancelled",
                 "spec_drafted", "spec_accepted", "spec_rate", "pool_bytes",
                 "jit_compiles", "jit_hits", "jit_sigs",
                 "phase_ns", "steps", "token_gap", "attn_blocks",
                 "kind_blocks", "attn_lanes", "block_steps", "expert_pairs",
                 "window_released", "linear_tokens", "linear_runs",
                 "byte_steps", "state_bytes", "state_resets", "dispatched",
                 "forwards", "passes", "lane_steps", "relayout",
                 "shared_rows")


_MON = None

# the `phase` label values of paddle_tpu_serving_step_phase_ns_total, in
# the order a step runs them (spans: serving.pack_tokens / dispatch /
# wait / route)
_STEP_PHASES = ("schedule", "dispatch", "wait", "route")
_PHASE_LABEL = dict(zip(("serving.pack_tokens", "serving.dispatch",
                         "serving.wait", "serving.route"), _STEP_PHASES))


def _mon():
    global _MON
    if _MON is None:
        from .. import monitor as m

        o = _Mon()
        o.mod = m
        o.state = m._state
        o.trace = m.trace
        o.tstate = m.trace._state
        o.queue_depth = m.gauge("paddle_tpu_serving_queue_depth")
        o.occupancy = m.gauge("paddle_tpu_serving_batch_occupancy")
        o.prefill = m.histogram("paddle_tpu_serving_prefill_latency_ns")
        o.decode = m.histogram("paddle_tpu_serving_decode_step_latency_ns")
        o.tokens = m.counter("paddle_tpu_serving_generated_tokens_total")
        o.evictions = m.counter("paddle_tpu_serving_evictions_total")
        o.ttft = m.histogram("paddle_tpu_serving_ttft_ns")
        o.admitted = m.counter("paddle_tpu_serving_admitted_total")
        o.rejected = m.counter("paddle_tpu_serving_rejected_total")
        o.adm_rejected = m.counter(
            "paddle_tpu_serving_admission_rejected_total")
        o.pack = m.histogram("paddle_tpu_serving_pack_tokens")
        o.chunk_depth = m.histogram(
            "paddle_tpu_serving_chunked_prefill_depth")
        o.pc_hits = m.counter("paddle_tpu_serving_prefix_cache_hits_total")
        o.pc_misses = m.counter(
            "paddle_tpu_serving_prefix_cache_misses_total")
        o.pc_shared = m.counter(
            "paddle_tpu_serving_prefix_blocks_shared_total")
        o.pc_blocks = m.gauge("paddle_tpu_kv_prefix_cache_blocks")
        o.pc_evictions = m.counter(
            "paddle_tpu_kv_prefix_cache_evictions_total")
        o.shed = m.counter("paddle_tpu_serving_shed_total",
                           labelnames=("tenant",))
        o.tenant_depth = m.gauge("paddle_tpu_serving_tenant_queue_depth",
                                 labelnames=("tenant",))
        o.aborted = m.counter("paddle_tpu_serving_aborted_total")
        o.recoveries = m.counter("paddle_tpu_serving_recoveries_total")
        o.preemptions = m.counter("paddle_tpu_serving_preemptions_total")
        o.cancelled = m.counter("paddle_tpu_serving_cancelled_total")
        o.spec_drafted = m.counter(
            "paddle_tpu_serving_spec_draft_tokens_total")
        o.spec_accepted = m.counter(
            "paddle_tpu_serving_spec_accepted_tokens_total")
        o.spec_rate = m.gauge("paddle_tpu_serving_spec_accept_rate")
        o.pool_bytes = m.gauge("paddle_tpu_serving_kv_pool_bytes")
        o.jit_compiles = m.counter("paddle_tpu_jit_compiles_total",
                                   labelnames=("function",))
        o.jit_hits = m.counter("paddle_tpu_jit_cache_hits_total",
                               labelnames=("function",))
        o.jit_sigs = m.gauge("paddle_tpu_jit_cached_signatures",
                             labelnames=("function",))
        o.phase_ns = m.counter("paddle_tpu_serving_step_phase_ns_total",
                               labelnames=("phase", "kind"))
        o.steps = m.counter("paddle_tpu_serving_steps_total",
                            labelnames=("kind",))
        o.token_gap = m.histogram(
            "paddle_tpu_serving_token_gap_ns",
            buckets=m.catalog.TOKEN_GAP_NS_BUCKETS)
        o.attn_blocks = m.counter("paddle_tpu_serving_attn_blocks_total",
                                  labelnames=("extent",))
        o.kind_blocks = m.counter(
            "paddle_tpu_serving_attn_kind_blocks_total",
            labelnames=("kind",))
        o.attn_lanes = m.counter("paddle_tpu_serving_attn_lanes_total",
                                 labelnames=("path",))
        o.block_steps = m.counter("paddle_tpu_kv_block_steps_total",
                                  labelnames=("kind",))
        o.expert_pairs = m.counter("paddle_tpu_serving_expert_pairs_total",
                                   labelnames=("where",))
        o.window_released = m.counter(
            "paddle_tpu_kv_window_blocks_released_total")
        o.linear_tokens = m.counter("paddle_tpu_serving_linear_tokens_total",
                                    labelnames=("path",))
        o.linear_runs = m.counter("paddle_tpu_serving_linear_runs_total",
                                  labelnames=("path",))
        o.byte_steps = m.counter("paddle_tpu_cache_byte_steps_total",
                                 labelnames=("kind",))
        o.state_bytes = m.gauge("paddle_tpu_state_pool_bytes")
        o.state_resets = m.counter("paddle_tpu_state_slots_reset_total")
        o.dispatched = m.counter("paddle_tpu_serving_dispatch_total",
                                 labelnames=("ahead",))
        o.forwards = m.counter("paddle_tpu_serving_forwards_total")
        o.passes = m.counter("paddle_tpu_serving_stack_passes_total")
        o.lane_steps = m.counter("paddle_tpu_serving_lane_steps_total",
                                 labelnames=("use",))
        o.relayout = m.gauge("paddle_tpu_serving_weight_relayout_bytes",
                             labelnames=("where",))
        o.shared_rows = m.counter(
            "paddle_tpu_serving_shared_expert_rows_total",
            labelnames=("lanes",))
        _MON = o
    return _MON


class _FetchFirst(Exception):
    """Raised inside the schedule where it cannot go on before the step in
    flight has been routed (the pool is short of blocks: a request may end
    and free its row there, and a preemption copies KV and tokens that
    must be on the host); the step fetches, then schedules anew."""


class _Flight:
    """One dispatched step whose output the host has not fetched: what
    routing needs of it, written when it was dispatched."""

    __slots__ = ("kind", "out", "epoch", "t0", "decode", "chunks",
                 "forwards", "n_lanes", "n_draft")

    def __init__(self, kind, out, epoch, t0, forwards=1):
        self.kind = kind                # mixed | burst
        self.out = out                  # the program's output, on the device
        self.epoch = epoch
        self.t0 = t0                    # now_ns of its schedule's start
        # (slot, request, index of its first token in the flattened
        # output, draft lanes behind it, tokens granted, whether the last
        # of them ends the request by length, lens before the step)
        self.decode = []
        # (slot, request, start, take, its first token's index or -1,
        # whether that token ends the request by length)
        self.chunks = []
        self.forwards = forwards        # forward passes (a burst: K)
        self.n_lanes = 0
        self.n_draft = 0


@jax.jit
def _compose_tokens(pack, prev):
    """The pack a step's program takes, ``(2, n)``: token ids over
    positions, from the host's ``(3, n)`` pack, whose third row says for
    each lane where in the output ``prev`` of the step before (flattened)
    its token lies, or -1 for a lane whose id the host wrote in row 0.
    A decode lane's input token goes from one program to the next without
    a visit to the host."""
    src = pack[2]
    tok = jnp.where(src >= 0, prev.reshape(-1)[jnp.maximum(src, 0)], pack[0])
    return jnp.stack([tok, pack[1]])


def _free_formats(weights):
    """The formats the mixed step is first compiled with: each weight on the
    device it lies on, its layout left to the compiler."""
    return jax.tree.map(lambda x: Format(Layout.AUTO, x.sharding), weights)


class _Request:
    """Host-side state of one admitted request (one slot)."""

    __slots__ = ("rid", "prompt", "prefill_pos", "chunks", "shared_tokens",
                 "max_new", "last_token", "outputs", "token_times",
                 "t_submit", "t_admit", "t_first", "tenant", "priority",
                 "spill", "granted", "done")

    def __init__(self, rid, prompt, max_new, t_submit, tenant="",
                 priority=0):
        self.rid = rid
        self.prompt = prompt            # np.int32 (L,)
        self.prefill_pos = 0            # prompt tokens already in KV
        self.chunks = 0                 # prefill chunks consumed so far
        self.shared_tokens = 0          # prompt tokens served by the cache
        self.max_new = max_new          # per-request cap (None = step's)
        self.last_token = 0
        self.outputs = []
        # output tokens of DISPATCHED steps (>= len(outputs): the scheduler
        # counts these, routing fills ``outputs`` a fetch later)
        self.granted = 0
        self.done = False               # ended: reported, cancelled, aborted
        self.token_times = []           # now_ns of each output's step fetch
        self.t_submit = t_submit
        self.t_admit = 0
        self.t_first = 0
        self.tenant = tenant
        self.priority = priority
        # preemption payload: (tokens_in_kv, per-layer host KV contents,
        # decode_ready) — present only between a preempt and the
        # re-admission that restores it bit-exact
        self.spill = None

    @property
    def prefilled(self):
        return self.prefill_pos >= len(self.prompt)


class _Tenant:
    """One tenant's admission lane: weighted-fair share (stride
    scheduling over ``1 / weight``) within its priority class."""

    __slots__ = ("name", "weight", "priority", "vtime", "queue")

    def __init__(self, name, weight=1.0, priority=0):
        self.name = name
        self.weight = float(weight)
        if self.weight <= 0:
            raise ValueError("tenant weight must be > 0")
        self.priority = int(priority)
        self.vtime = 0.0
        self.queue = collections.deque()


def _drain(dq):
    """Drain a deque that concurrent threads may still be appending to
    (popleft-until-empty is the one atomic deque idiom; no lock)."""
    out = []
    while True:
        try:
            out.append(dq.popleft())
        except IndexError:
            return out


def _pool_bytes(pools):
    """Device bytes of the per-layer pool entries. Quantized pools are
    4-leaf — int8 K/V values + fp32 per-(token, head) scales, about half
    the bytes per token — bf16 pools are 2-leaf; every pool consumer
    (mixed step, CoW, spill) treats the entry as an opaque leaf tuple."""
    return int(sum(leaf.size * leaf.dtype.itemsize
                   for entry in pools for leaf in entry))


class ContinuousBatchingEngine:
    """Token-budget continuous batching: every step runs ONE fixed-shape
    compiled program over a pack of decode lanes and chunked-prefill
    lanes; requests join and leave between steps, shared prompt prefixes
    ride the radix cache.

    Threading contract: ``submit()`` is thread-safe (pure enqueue, any
    number of producers). ``step()`` and ``add_request()`` mutate slot /
    pager / cache state and belong to ONE driving thread."""

    def __init__(self, model, max_batch=8, max_len=None, block_size=64,
                 chunk_size=32, max_step_tokens=None, policy="fcfs",
                 decode_priority=0.0, decode_burst=4, max_queue=None,
                 prefix_cache=True, kv_spill=False,
                 spill_capacity_blocks=None, strict_priority=False,
                 kv_cache_dtype=None, spec_lookahead=0, spec_ngram=3,
                 pool_blocks=None):
        """``max_step_tokens`` (default ``max_batch + chunk_size``) is the
        per-step token budget: decode lanes first, prefill chunks fill the
        remainder. ``policy`` orders prefill among admitted requests
        ("fcfs" | "spf" = shortest-prefill-first). ``decode_priority`` in
        [0, 1) additionally caps prefill at ``(1 - decode_priority) *
        max_step_tokens`` lanes per step — raising it bounds the decode
        latency a long prompt can add. ``decode_burst`` fuses up to that
        many decode iterations into one dispatch via lax.scan when NO
        prefill or admission work is pending (multi-step scheduling: the
        per-dispatch overhead amortizes over burst tokens; admissions wait
        at most one burst, and 1 disables it). ``max_queue`` bounds the
        submit() admission queue (backpressure; None = unbounded).
        ``kv_spill`` enables the host-RAM resilience layer:
        radix-cache evictions spill their KV bits to host (restorable on
        a later prefix match) and, under pool pressure, the lowest-
        priority active request is PREEMPTED — KV spilled, blocks freed,
        request requeued and later restored bit-exact — instead of the
        step failing (docs/serving.md, resilience). ``strict_priority``
        hardens the QoS lever: queued work is DEFERRED while any
        strictly-higher-priority request is active, so a low-priority
        flood can never join a high-priority batch (high-priority lanes
        keep their isolated steady state — decode bursts and all — and
        the flood drains only into idle capacity, shedding under queue
        pressure; the graceful-degradation mode of docs/serving.md).
        ``kv_cache_dtype="int8"`` runs the WHOLE engine — prefill
        chunks, decode lanes, CoW, radix sharing, spill/restore —
        against quantized pools (int8 values + per-(token, head) fp32
        scales): roughly half the KV bytes per token, so the same pool
        byte budget admits ~2x the concurrent requests (docs/serving.md,
        quantized KV). ``spec_lookahead`` > 0 enables self-speculative
        decoding: an n-gram/prompt-lookup drafter (models/spec_decode.py
        — no second model) proposes up to that many tokens per decode
        lane; the scheduler packs them as extra ragged lanes of the SAME
        compiled mixed step, which verifies them device-side (longest
        agreeing prefix, rejected tokens rolled back by not advancing
        seq_lens) — greedy outputs stay bit-identical with speculation
        on or off, accepted drafts just arrive several-per-dispatch.
        ``spec_ngram`` bounds the drafter's n-gram match length.
        ``pool_blocks`` overrides the KV pool size (default: exactly
        enough for max_batch max-length requests) — radix-cache-heavy
        serving sizes the pool PAST the live batch so shared prefixes
        and registered decode chains survive between requests instead of
        churning through LRU eviction."""
        # the model's class names its decode engine (the serving block's
        # description of its layers); a Llama-shaped one needs none
        engine_cls = getattr(type(model), "decode_engine_class",
                             LlamaDecodeEngine)
        self._inner = engine_cls(model, max_len=max_len,
                                 kv_cache_layout="paged",
                                 block_size=block_size,
                                 kv_cache_dtype=kv_cache_dtype)
        e = self._inner
        # the model's parameter of each weight the engine took as it is, so
        # that a weight placed in another layout replaces the parameter's
        # array (one device copy of each weight; ``_compile_step``)
        held = {id(p.value): p for p in model.parameters()}
        self._owners = {id(x): held[id(x)] for x in jax.tree.leaves(e.weights)
                        if id(x) in held}
        # weight bytes placed in another layout before the first program ran
        self.weight_relayout_bytes = 0
        self.max_batch = int(max_batch)
        self.max_len = e.max_len
        self.block_size = int(block_size)
        self.chunk_size = int(chunk_size)
        if self.chunk_size < 1:
            raise ValueError("chunk_size must be >= 1")
        self.max_step_tokens = int(max_step_tokens
                                   or self.max_batch + self.chunk_size)
        if self.max_step_tokens <= self.max_batch:
            raise ValueError(
                f"max_step_tokens ({self.max_step_tokens}) must exceed "
                f"max_batch ({self.max_batch}): every active slot gets a "
                "decode lane and prefill needs at least one more")
        if policy not in ("fcfs", "spf"):
            raise ValueError(f"unknown policy {policy!r} (fcfs | spf)")
        self.policy = policy
        self.decode_priority = float(decode_priority)
        if not 0.0 <= self.decode_priority < 1.0:
            raise ValueError("decode_priority must be in [0, 1)")
        self.decode_burst = max(1, int(decode_burst))
        self.max_queue = None if max_queue is None else int(max_queue)
        self.strict_priority = bool(strict_priority)
        max_blocks = -(-e.max_len // self.block_size)
        # default pool: exactly max_batch worst-case requests (+ null);
        # pool_blocks sizes it independently — prefix-cache-heavy serving
        # wants headroom so registered chains outlive their producers
        num_blocks = self.max_batch * max_blocks + 1 if pool_blocks is None \
            else max(int(pool_blocks), max_blocks + 2)
        # what the model's cache kinds permit decides what the engine may
        # be asked for: each kind's description says whether a sequence's
        # cache of it can be reused by another sequence (a radix hit),
        # spilled with a preempted request, and rolled back behind a
        # rejected draft, and why not
        asked = {"reuse": ("prefix_cache", prefix_cache, False),
                 "spill": ("kv_spill", kv_spill, False),
                 "rollback": ("spec_lookahead", spec_lookahead, 0)}
        refused = [(field, k) for field, (_, value, _) in asked.items()
                   if value for k in e.kinds if not getattr(k, field)]
        if refused:
            fields = {f for f, _ in refused}
            raise ValueError(
                "a model with "
                + " and ".join(sorted({k.name for _, k in refused}))
                + " layers is served with "
                + " and ".join(f"{option}={off!r}"
                               for f, (option, _, off) in asked.items()
                               if f in fields)
                + ": " + "; ".join(dict.fromkeys(k.why_not[f]
                                                 for f, k in refused))
                + "; none of it is implemented, and silent wrong reuse is "
                  "not an option")
        # one pager a PAGED cache kind, the whole-length kind's first:
        # ``_pager`` is the one the radix cache, copy-on-write and the spill
        # layer work on (a model with a second kind has none of the three).
        # A window kind's pool holds what its rows can keep at once: per row
        # the blocks its last ``window`` positions span and the one being
        # filled, plus the blocks one step's prefill budget adds before
        # the next release. A recurrent kind has a slot a row and the null
        # slot (``_states``)
        windows = [k.window for k in e.kinds if k.window is not None]
        window_blocks = None
        if windows:
            window_blocks = (self.max_batch
                             * ((max(windows) - 1) // self.block_size + 3)
                             + -(-self.max_step_tokens // self.block_size)
                             + 1)
        self._caches, self._pools = e.make_pagers(
            self.max_batch, num_blocks, window_blocks)
        self._pagers = [c for c, k in zip(self._caches, e.kinds) if k.paged]
        self._pager = self._pagers[0]
        # per paged kind: its label, how many layer caches keep a pool of it
        # (layers x passes of a looped stack), the bytes of one block in one
        # layer cache; per recurrent kind: its label, its layers, its slots
        self._kind_layers = [
            (k.name, e.layer_kind.count(i) * c.passes,
             _pool_bytes([self._pools[e.layer_kind.index(i)]])
             // (c.num_blocks * c.passes))
            for i, (c, k) in enumerate(zip(self._caches, e.kinds))
            if k.paged]
        self._states = [(k.name, e.layer_kind.count(i), c)
                        for i, (c, k) in enumerate(zip(self._caches,
                                                       e.kinds))
                        if not k.paged]
        self.state_pool_bytes = sum(layers * c.slots * c.slot_bytes
                                    for _, layers, c in self._states)
        # expert layers with a shared expert beside the routed ones
        self._shared_layers = sum("shared_gate" in p for p in e.layers)
        self._slot_resets = 0           # admissions since the last step
        # the capacity lever the pool-bytes gauge documents: equal byte
        # budgets admit ~2x the requests when the pools are quantized
        self.kv_pool_bytes = _pool_bytes(self._pools)
        self.kv_cache_dtype = kv_cache_dtype
        self.kv_spill = bool(kv_spill)
        self.prefix_cache = PrefixCache(
            self._pager, spill=self.kv_spill,
            spill_capacity_blocks=spill_capacity_blocks) if prefix_cache \
            else None
        self.spec_lookahead = max(0, int(spec_lookahead))
        if self.spec_lookahead:
            from .spec_decode import SuffixDrafter

            self._drafter = SuffixDrafter(
                lookahead=self.spec_lookahead, max_ngram=int(spec_ngram),
                prefix_cache=self.prefix_cache)
        else:
            self._drafter = None
        # host counters behind the spec metrics (read directly, so accept
        # rates report with the monitor off too)
        self.spec_drafted = 0
        self.spec_accepted = 0
        # per-slot radix-registration cursors (see _register_decode_blocks);
        # content-addressed, so any slot reuse invalidates the entry
        self._chain_cursors = {}
        # host-side slot state (numpy mirrors so pack assembly and
        # capacity checks vectorize — the step's host tax is part of the
        # serving hot path)
        self.lens = np.zeros(self.max_batch, np.int64)  # tokens in cache
        self._slots = [None] * self.max_batch           # _Request or None
        self._active = np.zeros(self.max_batch, bool)
        self._decode_ready = np.zeros(self.max_batch, bool)
        self._last_tok = np.zeros(self.max_batch, np.int32)
        # -- the step in flight ------------------------------------------
        # THE INVARIANT the scheduler's book rests on: programs run on the
        # device in dispatch order and the pools are threaded through them
        # (donated), so a block, a table row or a state slot may be handed
        # on as soon as the last step that reads it has been DISPATCHED.
        # So the book (lens, prefill_pos, _decode_ready, a request's
        # granted tokens, released window blocks, noted state slots,
        # registered prefix blocks, a request's row when its last token
        # is granted) advances at dispatch, and step() dispatches step
        # N + 1 before it fetches step N's output: routing keeps only what
        # needs the token VALUES. ``_flight`` is the one dispatched step
        # not yet fetched; ``_tok_src[b]`` says where in its (flattened)
        # output slot b's newest token lies, -1 once ``_last_tok[b]``
        # holds it; ``_unreported`` (rid -> request) holds the requests
        # whose row was released at dispatch and whose end routing has
        # yet to report: in no slot, and while their step is being
        # fetched in no ``_flight`` either, so recover() finds them here
        self._flight = None
        self._tok_src = np.full(self.max_batch, -1, np.int32)
        self._unreported = {}
        self._out_shapes = {}           # kind -> its program's output shape
        # (output shape, pack width) -> the composition of such an output
        # into such a pack, compiled (``_compose_tokens``)
        self._compose = {}
        # the running step()'s eos_token_id, max_new_tokens and epoch
        self._call = (None, None, 0)
        self._out = []                  # ... and what it will return
        # device lane vectors keyed by pack composition: in steady decode
        # the composition repeats every step, so slot_ids/valid upload once
        self._lane_cache = {}
        self._next_rid = 0
        self._jit_cache = {}
        # graftsan label qualifier: compile budgets are PER ENGINE (ONE
        # mixed-step program each); a process-wide label would falsely
        # trip the sentinel on the second engine
        self._san_tag = f"e{next(_ENGINE_SEQ)}"
        # numsan step index: bumped only while the numerics sanitizer is
        # on, so trip dumps name the step the NaN crossed, not wall time
        self._san_steps = 0
        # submit() queues (host-side, one lane per tenant); _submit_lock
        # guards the bounded check+append only — nothing blocks and no
        # jax dispatch runs under it (GL004)
        self._tenants = {"": _Tenant("")}
        self._vnow = 0.0                # WFQ virtual clock (last pop)
        # graftsan-witnessed (lock order + the race witness's held-set)
        # when sanitizers are enabled at construction
        self._submit_lock = _sanitizers.new_lock(
            f"serving.engine[{self._san_tag}]._submit_lock")
        # per-request trace trees (monitor.trace): rid -> [root, queue_wait]
        self._req_spans = {}
        # per-request stats kept for the caller (bench TTFT percentiles);
        # popped via pop_stats, bounded so an indifferent caller can't leak
        self._stats = collections.OrderedDict()
        # -- resilience state (recover / driving thread / shedding) ------
        self._epoch = 0                 # bumped by every recover()
        # the running step's open trace.phase, the phases it has been
        # through and its kind (mixed | burst): written by the driving
        # thread alone, read back when the step returns
        self._phase = _mon().trace._NOOP
        self._phases = ()
        self._step_kind = None
        self._recover_lock = threading.Lock()
        self._shed = collections.deque(maxlen=4096)     # RequestShed
        self._aborted = collections.deque(maxlen=4096)  # RequestAborted
        # driver-mode finished pairs; bounded like _shed/_aborted so a
        # dead consumer can't grow host RSS without bound
        self._results = collections.deque(maxlen=4096)
        self._driver = None
        self._drive_stop = threading.Event()
        self._drive_args = None
        self._dog = None
        # [{reason, ms, aborted, cold}]; bounded: a flapping engine must
        # not leak one record per crash loop iteration
        self.recovery_stats = collections.deque(maxlen=256)
        self.last_recovery_dump = None
        # -- fleet-facing surface (serving/fleet.py) ---------------------
        # staged knob changes (paddle_tpu/control/): request_knobs()
        # stores under _submit_lock, step() applies at its entry on the
        # single driving thread — a knob never changes mid-step, and an
        # engine nobody tunes never takes this branch (empty-dict check)
        self._pending_knobs = {}
        # cancellation requests (thread-safe enqueue; the driving thread
        # applies them at the next step boundary) — the hedging loser's
        # exit path
        self._cancel_q = collections.deque()
        self.cancelled = 0
        # monotonic timestamp of the step currently executing (None when
        # no step is in flight): the host-side mirror of the open
        # serving.step span, readable without tracing on — the fleet
        # health monitor's step-staleness signal
        self.step_open_since = None
        # graftscope: every engine is a /statusz section (held via
        # WeakMethod, so an engine stays collectable while registered)
        from ..monitor import server as _obs

        _obs.register_status_provider(f"serving.{self._san_tag}",
                                      self.status)
        # the device parts of the programs compiled before the monitor is
        # turned on are mapped at its enable() (held weakly, like /statusz)
        from ..monitor import parts as _parts

        self._parts_have = None
        _parts.on_enable(self._record_parts)

    # -- compiled path -------------------------------------------------------
    def _record_parts(self):
        """Map the instructions of the programs this engine has compiled
        (the mixed step, the decode burst, the compositions of a known
        output into either pack) to their device parts
        (``monitor.parts``): from the text of each executable the engine
        holds, with nothing compiled, and set the gauge of the weight bytes placed in another layout at build and
        of those the programs still re-lay out in a call. Only while the
        monitor is on (``monitor.enable()`` calls it, and a dispatch while
        the monitor is on); a program set already mapped is skipped."""
        mon = _mon()
        if not mon.state.on:
            return
        parts = mon.mod.parts
        programs = [*self._jit_cache.values(), *self._compose.values()]
        have = (parts.generation(), tuple(map(id, programs)))
        if have == self._parts_have:
            return
        self._parts_have = have
        texts = [prog.as_text() for prog in programs]
        for text in texts:
            parts.record(text)
        # the programs' weights are their last argument, ``w``
        mon.relayout.labels("build").set(self.weight_relayout_bytes)
        mon.relayout.labels("program").set(
            sum(parts.relayout_bytes(text, "w") for text in texts))

    def _compile_step(self):
        """The mixed step, compiled with the weights' layouts left to the
        compiler (``_free_formats``). Each weight it reads in another
        layout than the one it has is placed in that layout before the
        program first runs (``place_weights``), so that it, the burst and
        every other program compiled against the placed arrays read them
        as they lie, and none re-lays out a weight in a call. Off a TPU
        the compiler keeps the layouts the weights have: nothing moves."""
        e = self._inner
        sds, i32, T = jax.ShapeDtypeStruct, jnp.int32, self.max_step_tokens
        args = (sds((2, T), i32), self._pools, self._tables(),
                sds((T,), i32), sds((T,), jnp.bool_), sds((T,), jnp.bool_))
        step = jax.jit(e.build_mixed_step(), donate_argnums=(1,),
                       in_shardings=(None,) * len(args)
                       + (_free_formats(e.weights),))
        exe = step.lower(*args, jax.tree.map(
            lambda x: sds(x.shape, x.dtype), e.weights)).compile()
        self.weight_relayout_bytes = e.place_weights(
            exe.input_formats[0][-1], self._owners)
        self._owners = {}
        return exe

    def _step_jit(self):
        cache = self._jit_cache
        mon = _mon()
        if mon.state.on:
            if "step" in cache:
                mon.jit_hits.labels("serving.step").inc()
            else:
                mon.jit_compiles.labels("serving.step").inc()
                mon.jit_sigs.labels("serving.step").set(1)
        if "step" not in cache:
            san = _sanitizers
            if san._state.recompile:
                # graftsan: the mixed step is ONE program by design — a
                # second signature here is the recompile storm the token
                # budget exists to prevent
                san.note_compile(f"serving.step[{self._san_tag}]",
                                 signature="step")
            cache["step"] = self._compile_step()
        return cache["step"]

    def _burst_jit(self):
        cache = self._jit_cache
        mon = _mon()
        if mon.state.on:
            if "burst" in cache:
                mon.jit_hits.labels("serving.step").inc()
            else:
                mon.jit_compiles.labels("serving.step").inc()
                mon.jit_sigs.labels("serving.step").set(2)
        if "burst" not in cache:
            san = _sanitizers
            if san._state.recompile:
                # the engine's SECOND program. Burst size only changes
                # through request_knobs (which drops this cache entry),
                # so every signature here is an intentional, slew-bounded
                # actuation — visible to the sentinel, never a storm
                san.note_compile(f"serving.step[{self._san_tag}]",
                                 signature=("burst", self.decode_burst))
            # against the weights as the mixed step placed them
            cache["burst"] = jax.jit(
                self._inner.build_decode_burst(self.decode_burst),
                donate_argnums=(1,)).lower(
                    jax.ShapeDtypeStruct((2, self.max_batch), jnp.int32),
                    self._pools, self._tables(),
                    self._inner.weights).compile()
        return cache["burst"]

    # -- admission -----------------------------------------------------------
    def _check_prompt(self, prompt_ids):
        prompt = np.asarray(getattr(prompt_ids, "value", prompt_ids),
                            np.int32).reshape(-1)
        L = len(prompt)
        if L == 0 or L >= self.max_len:
            raise ValueError(f"prompt length {L} out of range (1.."
                             f"{self.max_len - 1})")
        # a prompt whose KV can never fit the whole pool would otherwise
        # head-of-line-block the admission queue forever — refuse it up
        # front, at the caller
        need = -(-(L + 1) // self.block_size)
        if need > self._pager.num_blocks - 1:  # block 0 is the null block
            raise ValueError(
                f"prompt needs {need} KV blocks but the pool only has "
                f"{self._pager.num_blocks - 1}")
        return prompt

    # -- tenants (weighted-fair queuing, priority lanes, load shedding) ------
    def set_tenant(self, name, weight=1.0, priority=0):
        """Configure (or reconfigure) a tenant lane: ``weight`` is the
        weighted-fair share of admissions within the tenant's priority
        class (stride scheduling — a weight-4 tenant admits 4x a
        weight-1 peer under contention), ``priority`` the lane class
        (higher admits first; under sustained overload the LOWEST
        priority sheds first, with typed :class:`RequestShed`
        rejections). Tenants submitted without configuration default to
        weight 1, priority 0."""
        with self._submit_lock:
            t = self._tenants.get(name)
            if t is None:
                t = self._tenants[name] = _Tenant(name, weight, priority)
                t.vtime = self._vnow
            else:
                new_w = float(weight)
                if new_w <= 0:
                    raise ValueError("tenant weight must be > 0")
                t.weight = new_w
                t.priority = int(priority)

    def _tenant_locked(self, name):
        t = self._tenants.get(name)
        if t is None:
            t = self._tenants[name] = _Tenant(name)
            t.vtime = self._vnow
        return t

    def _prioritized(self):
        return len({t.priority for t in list(self._tenants.values())}) > 1

    def _shed_victim_locked(self, priority):
        """The queued request shed for a priority-``priority`` arrival:
        newest request of the lowest-priority non-empty lane STRICTLY
        below the arrival (equal-priority work is never displaced)."""
        best = None
        for t in self._tenants.values():
            if not t.queue or t.priority >= priority:
                continue
            if best is None or t.priority < best.priority:
                best = t
        if best is None:
            return None
        return best, best.queue.pop()    # newest: it waited least

    def _shed_locked(self, ten, req, mon, why):
        err = RequestShed(
            f"request {req.rid} (tenant {ten.name!r}) shed under "
            f"overload: {why}", tenant=ten.name, rid=req.rid)
        self._shed.append(err)
        entry = self._req_spans.pop(req.rid, None)
        if entry is not None:
            mon.trace.drop(entry[1])
            mon.trace.end_span(entry[0])
        self._stats[req.rid] = {
            "rid": req.rid, "tenant": ten.name, "shed": True,
            "prompt_len": len(req.prompt), "submit_ns": req.t_submit}
        while len(self._stats) > 4096:
            self._stats.popitem(last=False)
        if mon.state.on:
            mon.shed.labels(ten.name).inc()

    def pop_shed(self):
        """Drain the typed :class:`RequestShed` records of queued
        requests displaced by higher-priority arrivals (the shed
        request's owner learns here; an arrival shed on ITS OWN submit
        gets the exception directly)."""
        return _drain(self._shed)

    # -- admission -----------------------------------------------------------
    def add_request(self, prompt_ids, max_new_tokens=None, tenant=""):
        """Admit one prompt into a free slot; returns the request id (or
        None when the batch is full — callers queue and retry, or use
        submit() which queues host-side). The prompt's KV is built by
        chunked prefill inside subsequent step() packs; the first token
        arrives from the step that consumes the last prompt token."""
        prompt = self._check_prompt(prompt_ids)
        mon = _mon()
        self._drain_pending()
        slot = self._free_slot()
        if slot is None:
            if mon.state.on:
                mon.rejected.inc()
            return None
        with self._submit_lock:
            # rid minting shares the counter with producer-thread
            # submit()s — unlocked, two requests could get one id
            ten = self._tenant_locked(tenant)
            rid = self._next_rid
            self._next_rid += 1
        req = _Request(rid, prompt, max_new_tokens, mon.mod.now_ns(),
                       tenant=tenant, priority=ten.priority)
        self._admit(slot, req)
        return rid

    def submit(self, prompt_ids, max_new_tokens=None, timeout=None,
               tenant=""):
        """Always-queueing admission: the request waits host-side until
        the DRIVING thread's next step() (or add_request()) assigns it a
        free slot, then prefills chunk-by-chunk inside step packs.
        Returns the request id (TTFT measures queue wait + chunked
        prefill). submit() is the engine's one thread-safe entry point —
        it only enqueues, never touching slot state, so any number of
        producer threads may call it while one thread drives step().
        With a bounded queue (``max_queue``), a full queue first sheds
        the newest QUEUED request of any strictly-lower-priority tenant
        (typed :class:`RequestShed`, surfaced via :meth:`pop_shed`) to
        make room; when nothing outranks, it raises — immediately when
        ``timeout`` is None, else after blocking up to ``timeout``
        seconds for the stepping thread to drain space. The raise is a
        :class:`RequestShed` when priority lanes are configured (this
        arrival IS the lowest-priority work), else the plain
        :class:`AdmissionTimeout`."""
        prompt = self._check_prompt(prompt_ids)
        mon = _mon()
        t_submit = mon.mod.now_ns()
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            with self._submit_lock:
                ten = self._tenant_locked(tenant)
                total = sum(len(t.queue)
                            for t in self._tenants.values())
                victim = None
                if self.max_queue is not None and total >= self.max_queue:
                    victim = self._shed_victim_locked(ten.priority)
                if self.max_queue is None or total < self.max_queue \
                        or victim is not None:
                    if victim is not None:
                        self._shed_locked(
                            victim[0], victim[1], mon,
                            f"displaced by a priority-{ten.priority} "
                            f"arrival (queue full at {self.max_queue})")
                    rid = self._next_rid
                    self._next_rid += 1
                    req = _Request(rid, prompt, max_new_tokens, t_submit,
                                   tenant=tenant, priority=ten.priority)
                    if mon.tstate.on:
                        root = mon.trace.start_span(
                            "serving.request", attrs={"rid": rid})
                        _sanitizers.race_access(self._san_tag,
                                                "_req_spans", write=True)
                        self._req_spans[rid] = [
                            root, mon.trace.start_span("serving.queue_wait",
                                                       parent=root)]
                    if not ten.queue:
                        # an idle lane re-syncs to the virtual clock, or
                        # its lagging vtime would grant an unfair burst
                        ten.vtime = max(ten.vtime, self._vnow)
                    ten.queue.append(req)
                    break
            if deadline is None or time.monotonic() >= deadline:
                if mon.state.on:
                    mon.adm_rejected.inc()
                if self._prioritized():
                    if mon.state.on:
                        mon.shed.labels(tenant).inc()
                    raise RequestShed(
                        f"load shed: admission queue full "
                        f"({self.max_queue} requests) and tenant "
                        f"{tenant!r} (priority {ten.priority}) outranks "
                        "no queued work", tenant=tenant)
                raise AdmissionTimeout(
                    f"admission queue full ({self.max_queue} requests)"
                    + ("" if timeout is None
                       else f" after {timeout}s wait"))
            time.sleep(0.0005)   # poll; the lock is NEVER held while waiting
        # NO _drain_pending here: admission mutates slot/pager/cache state
        # and belongs to the driving thread alone — a concurrent drain
        # from here could hand two requests the same slot
        if mon.state.on:
            self._update_gauges(mon)
        return rid

    def _free_slot(self):
        for b in range(self.max_batch):
            if self._slots[b] is None:
                return b
        return None

    def _pop_pending(self):
        """Next queued request: highest priority class first, weighted-
        fair (stride scheduling on ``1 / weight``) among that class's
        tenants, then the admission policy (fcfs | spf) within the
        chosen tenant's lane."""
        with self._submit_lock:
            ready = [t for t in self._tenants.values() if t.queue]
            if not ready:
                return None
            pmax = max(t.priority for t in ready)
            if self.strict_priority:
                # defer queued work that a strictly-higher-priority
                # ACTIVE request outranks: the flood never joins a
                # high-priority batch (slots read-only here; the driving
                # thread owns them and is the only _pop_pending caller)
                act = [s.priority for s in self._slots if s is not None]
                if act and pmax < max(act):
                    return None
            cands = [t for t in ready if t.priority == pmax]
            ten = min(cands, key=lambda t: (t.vtime, t.name))
            self._vnow = ten.vtime
            ten.vtime += 1.0 / ten.weight
            if self.policy == "spf":
                req = min(ten.queue, key=lambda r: len(r.prompt))
                ten.queue.remove(req)
                return req
            return ten.queue.popleft()

    def _requeue_front(self, req):
        """Head-of-lane requeue for a PREEMPTED request (it was already
        admitted once; it resumes before new arrivals of its tenant)."""
        with self._submit_lock:
            self._tenant_locked(req.tenant).queue.appendleft(req)

    def _drain_pending(self):
        """Assign queued requests to free slots (no compute here: the
        prompt KV is built by chunked prefill inside step packs). Driving
        thread only — see the class threading contract."""
        _fi.fire("serving.admission")
        while True:
            slot = self._free_slot()
            if slot is None:
                return
            req = self._pop_pending()
            if req is None:
                return
            if req.spill is not None:
                if not self._restore(slot, req):
                    # the pool lacks headroom to restore the preempted
                    # KV: park the request back at the head of its lane
                    # and stop admitting — an eviction must free blocks.
                    # Refund the WFQ charge _pop_pending just took, or a
                    # stalled restore inflates the tenant's vtime once
                    # per blocked step and starves its later arrivals.
                    self._requeue_front(req)
                    with self._submit_lock:
                        ten = self._tenant_locked(req.tenant)
                        ten.vtime -= 1.0 / ten.weight
                    return
            else:
                self._admit(slot, req)

    def _admit(self, slot, req):
        mon = _mon()
        req.t_admit = mon.mod.now_ns()
        L = len(req.prompt)
        with self._submit_lock:
            if req.rid not in self._req_spans and mon.tstate.on:
                # add_request path: root opens at admission (no queue wait)
                self._req_spans[req.rid] = [
                    mon.trace.start_span("serving.request",
                                         attrs={"rid": req.rid}), None]
            entry = self._req_spans.get(req.rid)
        if entry is not None and entry[1] is not None:
            mon.trace.end_span(entry[1], t1_ns=req.t_admit)
            entry[1] = None
        # radix descent: map every cached prefix block read-only into the
        # new request's table; a FULL (block-aligned) hit still re-runs
        # the last prompt token for its logits — that single write
        # copy-on-writes the shared tail block
        if self.prefix_cache is not None:
            blocks, shared = self.prefix_cache.match(req.prompt)
            if self.kv_spill:
                # evicted-but-hot prefixes parked in host RAM rejoin the
                # chain here: restored bit-exact into fresh pool blocks
                blocks, shared, self._pools = \
                    self.prefix_cache.restore_chain(
                        req.prompt, blocks, shared, self._pools)
            if blocks:
                self._pager.adopt_blocks(slot, blocks)
                req.shared_tokens = shared
                req.prefill_pos = min(shared, L - 1)
            if mon.state.on:
                (mon.pc_hits if blocks else mon.pc_misses).inc()
                if blocks:
                    mon.pc_shared.inc(len(blocks))
        self.lens[slot] = req.prefill_pos
        self._slots[slot] = req
        self._active[slot] = True
        self._decode_ready[slot] = False
        self._slot_resets += 1
        self._chain_cursors.pop(slot, None)
        if self._drafter is not None:
            self._drafter.admit(req.rid, req.prompt)
        with self._submit_lock:
            _sanitizers.race_access(self._san_tag, "_stats", write=True)
            self._stats[req.rid] = {
                "rid": req.rid, "slot": slot, "prompt_len": L,
                "tenant": req.tenant,
                "shared_tokens": req.shared_tokens,
                "submit_ns": req.t_submit}
            if len(self._stats) > 4096:
                self._stats.popitem(last=False)
        if mon.state.on:
            mon.admitted.inc()
            self._update_gauges(mon)

    def pop_stats(self, rid):
        """Per-request stats (ttft_ns, prefill chunks, shared prefix
        tokens and, once the request has ended, token_times_ns: one
        now_ns time per returned token, the first equal to submit_ns +
        ttft_ns), retained until popped — the bench reads TTFT
        percentiles from here after each eviction."""
        with self._submit_lock:
            _sanitizers.race_access(self._san_tag, "_stats", write=True)
            return self._stats.pop(rid, None)

    def _span_entry(self, rid):
        """The [root, queue_wait] span pair of one in-flight request.
        The returned list is mutated only by the driving thread; the
        table itself is shared with submit/abort and stays under the
        submit lock."""
        with self._submit_lock:
            _sanitizers.race_access(self._san_tag, "_req_spans")
            return self._req_spans.get(rid)

    def status(self):
        """The engine's graftscope ``/statusz`` section: host-readable
        state only (counters, pool headroom, compile counts, last
        recovery) — no jax dispatch, no locks, safe to call from the
        scrape thread while another thread drives step()."""
        pager = self._pager
        free = len(pager._free)
        total = pager.num_blocks - 1          # block 0 is the null block
        doc = {
            "engine": self._san_tag,
            "health": "ok",
            "active": int(self._active.sum()),
            "pending": self.num_pending,
            "max_batch": self.max_batch,
            "kv": {
                "free_blocks": free,
                "total_blocks": total,
                "headroom": round(free / max(total, 1), 4),
                "pool_bytes": int(self.kv_pool_bytes),
                "state_pool_bytes": int(self.state_pool_bytes),
                "dtype": self.kv_cache_dtype or "full",
            },
            "compiled_programs": len(self._jit_cache),
            "epoch": self._epoch,
            "recoveries": len(self.recovery_stats),
            "cancelled": self.cancelled,
            "driver_alive": bool(self._driver is not None
                                 and self._driver.is_alive()),
            "knobs": {
                "chunk_size": self.chunk_size,
                "decode_burst": self.decode_burst,
                "decode_priority": self.decode_priority,
                "max_queue": self.max_queue,
            },
        }
        if self.recovery_stats:
            doc["last_recovery"] = dict(self.recovery_stats[-1])
        opened = self.step_open_since
        if opened is not None:
            doc["step_open_s"] = round(time.monotonic() - opened, 4)
        if self._drafter is not None:
            doc["spec"] = {
                "drafted": self.spec_drafted,
                "accepted": self.spec_accepted,
                "accept_rate": round(
                    self.spec_accepted / max(self.spec_drafted, 1), 4),
            }
        if self.prefix_cache is not None:
            doc["kv"]["prefix_cache_blocks"] = len(self.prefix_cache)
        return doc

    # -- preemption + restore (host-RAM KV spill under pool pressure) --------
    def _preempt_lowest(self, exclude=()):
        """Preempt the lowest-priority active request (ties: newest
        first): its exact KV bits spill to host RAM, its blocks return to
        the pool, and the request rejoins the HEAD of its tenant's lane —
        restored bit-exact by :meth:`_restore` on re-admission. Returns
        the freed slot, or None when nothing is preemptible. What it
        copies (KV, the last token, the outputs so far) must be on the
        host: its callers fetch the step in flight first
        (``_short_of_blocks``)."""
        skip = set(int(b) for b in exclude)
        cands = [b for b in range(self.max_batch)
                 if self._slots[b] is not None and b not in skip]
        if not cands:
            return None
        slot = min(cands, key=lambda b: (self._slots[b].priority,
                                         -self._slots[b].rid))
        mon = _mon()
        t0 = mon.mod.now_ns()
        req = self._slots[slot]
        n_tok = int(self.lens[slot])
        nblk = -(-n_tok // self.block_size) if n_tok else 0
        contents = None
        if nblk:
            blocks = [int(b) for b in self._pager._tables_np[slot][:nblk]]
            contents = _pk.read_blocks(self._pools, blocks)
        req.spill = (n_tok, contents, bool(self._decode_ready[slot]))
        self._release(slot)
        if self._drafter is not None:
            self._drafter.drop(req.rid)   # _restore re-admits the context
        self._requeue_front(req)
        if mon.tstate.on:
            with self._submit_lock:
                entry = self._req_spans.get(req.rid)
            mon.trace.record_span(
                "serving.preempt", t0, mon.mod.now_ns(),
                parent=None if entry is None else entry[0],
                attrs={"slot": slot, "rid": req.rid,
                       "tokens_in_kv": n_tok})
        if mon.state.on:
            mon.preemptions.inc()
            self._update_gauges(mon)
        return slot

    def _restore(self, slot, req):
        """Re-admit a preempted request: fresh blocks, the spilled KV
        bits re-uploaded at the same in-block offsets, slot state
        rebuilt — the continuation is bit-identical to an undisturbed
        run. Returns False (leaving the request untouched) when the pool
        lacks headroom even after cache relief."""
        n_tok, contents, decode_ready = req.spill
        nblk = -(-n_tok // self.block_size) if n_tok else 0
        blks = []
        if nblk:
            blks = self._pager.take_blocks(nblk)
            if blks is None and self.prefix_cache is not None \
                    and len(self.prefix_cache):
                mon = _mon()
                freed = self.prefix_cache.evict(nblk, pools=self._pools)
                if mon.state.on and freed:
                    mon.pc_evictions.inc(freed)
                    mon.pc_blocks.set(len(self.prefix_cache))
                blks = self._pager.take_blocks(nblk)
            if blks is None:
                return False
        mon = _mon()
        req.t_admit = mon.mod.now_ns()
        if nblk:
            self._pager.place_blocks(slot, blks)
            self._pools = self._pager.write_block_contents(
                self._pools, blks, contents)
        req.spill = None
        self.lens[slot] = n_tok
        self._slots[slot] = req
        self._active[slot] = True
        self._decode_ready[slot] = decode_ready
        self._last_tok[slot] = req.last_token
        self._chain_cursors.pop(slot, None)
        if self._drafter is not None:
            # rebuild the draft context (prompt + everything emitted so
            # far) so the restored continuation speculates like an
            # undisturbed run
            ctx = req.prompt if not req.outputs else np.concatenate(
                [req.prompt, np.asarray(req.outputs, np.int32)])
            self._drafter.drop(req.rid)
            self._drafter.admit(req.rid, ctx)
        with self._submit_lock:
            st = self._stats.get(req.rid)
            if st is None:
                st = self._stats[req.rid] = {
                    "rid": req.rid, "prompt_len": len(req.prompt),
                    "tenant": req.tenant,
                    "shared_tokens": req.shared_tokens,
                    "submit_ns": req.t_submit}
            st["slot"] = slot
            st["restored"] = True
        if mon.state.on:
            self._update_gauges(mon)
        return True

    # -- staged knob changes (paddle_tpu/control/) ---------------------------
    _KNOB_NAMES = ("chunk_size", "decode_burst", "decode_priority",
                   "max_queue")

    def request_knobs(self, **knobs):
        """Stage serving-knob changes for the next step boundary
        (thread-safe): ``chunk_size`` / ``decode_burst`` /
        ``decode_priority`` / ``max_queue``. Values are validated HERE
        (a controller with a typo must fail at the actuation site, not
        corrupt a step); the driving thread applies them at the top of
        :meth:`step`, so a knob never changes mid-step. A
        ``decode_burst`` change drops the compiled burst program — the
        next burst-eligible step recompiles ONE program under the
        graftsan compile sentinel (signature ``("burst", K)``); the
        knob's declared slew limit is what bounds the recompile rate."""
        staged = {}
        for name, v in knobs.items():
            if name not in self._KNOB_NAMES:
                raise ValueError(f"unknown serving knob {name!r} "
                                 f"(known: {self._KNOB_NAMES})")
            if name == "max_queue":
                v = None if v is None else max(1, int(v))
            elif name == "decode_priority":
                v = float(v)
                if not 0.0 <= v < 1.0:
                    raise ValueError("decode_priority must be in [0, 1)")
            else:
                v = max(1, int(v))
            staged[name] = v
        with self._submit_lock:
            self._pending_knobs.update(staged)

    def _apply_pending_knobs(self):
        """Apply staged knobs (driving thread, step entry). The
        emptiness check lives under the lock too, so the common
        nothing-staged step is one uncontended acquire, no lock-free
        peek at shared state."""
        with self._submit_lock:
            if not self._pending_knobs:
                return
            knobs, self._pending_knobs = self._pending_knobs, {}
        for name, v in knobs.items():
            if name == "decode_burst" and v != self.decode_burst:
                # a burst in flight is routed under the K it ran with
                self._fetch_flight(then_schedule=True)
                # invalidate the compiled burst program; the cache key is
                # stable ("burst"), so the sentinel sees ONE recompile
                # with the new signature, not a cache leak
                self._jit_cache.pop("burst", None)
            setattr(self, name, v)

    # -- the mixed step ------------------------------------------------------
    def step(self, eos_token_id=None, max_new_tokens=None):
        """Keep ONE compiled step in flight. A call schedules and packs
        step N + 1 from the scheduler's book as it stands AFTER step N
        (every prefilled slot decodes one token, or ``decode_burst`` of
        them; admitted-but-unprefilled slots consume prefill chunks from
        the remaining token budget), dispatches it, THEN fetches step N's
        output, routes it and returns the finished (request_id, tokens)
        pairs of step N: results arrive ONE CALL LATER than the step that
        computed them, and while the host routes, returns and packs, the
        device runs. A call with nothing to dispatch routes the step in
        flight and returns; ``num_active`` stays true until every request
        has been handed back, so ``while eng.num_active or
        eng.num_pending: eng.step()`` returns them all.

        Where the next schedule needs a step's tokens on the host, the
        same code keeps nothing in flight (depth 0: the step is fetched
        in the call that dispatched it, as before PR 37): with a drafter
        (drafts are made from the last tokens) and under the numerics
        sanitizer, always; before a preemption or any grant the pool
        cannot fund, before ``cancel`` of a request that is not queued
        and before a ``decode_burst`` change, for that step; ``recover()``
        drops the step in flight with its epoch. A request that ends by
        LENGTH gets no lane in the next step and its row is released when
        its last token is dispatched (see ``_flight`` in ``__init__`` for
        the invariant); one that ends by ``eos_token_id`` already has a
        lane in the step in flight: that lane's tokens are discarded."""
        epoch = self._epoch
        mon = _mon()
        # the host-side twin of the open serving.step span: set while a
        # step runs, cleared on exit — a fleet health monitor reads its
        # age as the step-staleness signal without needing tracing on
        self.step_open_since = time.monotonic()
        step_ctx = self._phase = mon.trace._NOOP
        self._phases = ()
        self._step_kind = None
        self._call = (eos_token_id, max_new_tokens, epoch)
        finished = self._out = []
        if mon.tstate.annotate:
            # an OPEN serving.step span is what a flight dump names when
            # the driving thread hangs or dies mid-step. It and its four
            # phases (schedule -> dispatch -> wait -> route, handed over
            # by _next_phase) are trace.phase()s: profiler annotations on
            # the device trace's clock under either switch, ring spans
            # under span tracing
            step_ctx = mon.trace.phase("serving.step",
                                       attrs={"engine": self._san_tag})
            sp = step_ctx.__enter__()
            self._phase = mon.trace.phase(
                "serving.pack_tokens", parent=sp, t0_ns=step_ctx.t0_ns)
            self._phase.__enter__()
            self._phases = [["schedule", None, self._phase]]
        counted = False
        try:
            # staged controller knobs land here, on the driving thread,
            # before any slot state is read — never mid-step
            self._apply_pending_knobs()
            # chaos drills kill/hang the step INSIDE the open span, so
            # the hang dump lists serving.step among its open spans
            _fi.fire("serving.step")
            if epoch != self._epoch:
                # a recovery superseded this step while it was stuck at
                # the injection point — the new epoch owns the slot state
                return []
            san = _sanitizers
            try:
                if san._state.hostsync:
                    # graftsan: the step is device-resident by contract
                    # (GL002) — a Tensor host sync inside it is a
                    # regression the tripwire turns into a raise
                    with san.protected_region("serving.step"):
                        self._step_impl(max_new_tokens)
                else:
                    self._step_impl(max_new_tokens)
            except Exception:
                if epoch != self._epoch:
                    # a hang recovery superseded this SLOW-but-alive
                    # step mid-flight (e.g. the watchdog timeout was
                    # tighter than a compile): its crash hit the dead
                    # epoch's state, not the recovered engine's
                    return []
                raise
            if epoch != self._epoch:
                # recovery aborted (and possibly re-admitted) every
                # request this step computed for — its results belong
                # to the dead epoch and must not double-report
                return []
            counted = True
            return finished
        finally:
            self.step_open_since = None
            last = self._phase
            last.close()
            step_ctx.close(last.t1_ns)
            if counted and mon.state.on:
                # the phases of a call that dispatched or fetched a step:
                # schedule and dispatch under the kind of the step they
                # PREPARED, wait and route under that of the step they
                # FETCHED. An early return (no active lane, superseded
                # epoch) or a raise counts nothing
                for label, kind, ph in self._phases:
                    if kind is not None:
                        mon.phase_ns.labels(label, kind).inc(
                            ph.t1_ns - ph.t0_ns)

    def _next_phase(self, name, kind=None):
        """Hand the running step over to its next phase at one shared
        instant (a no-op with both switches off). ``kind`` (mixed |
        burst) is that of the step the phase works for: the dispatch
        phase names the step it and the schedule phase before it
        PREPARE, the wait phase the step it and the route phase after it
        FETCH (and a schedule phase that prepared nothing)."""
        ph = self._phase.then(name)
        if ph is self._phase:
            return
        phases = self._phases
        if name == "serving.route":
            kind = phases[-1][1]
        if kind is not None:
            for entry in phases:
                if entry[1] is None:
                    entry[1] = kind
        self._phase = ph
        phases.append([_PHASE_LABEL[name], kind, ph])

    def _depth(self):
        """How many dispatched steps step() may leave unfetched: 1, or 0
        where the NEXT schedule reads this step's tokens on the host (a
        drafter drafts from them) or every step's output is checked as it
        lands (the numerics sanitizer)."""
        return 0 if self._drafter is not None \
            or _sanitizers._state.numerics else 1

    def _fetch_flight(self, then_schedule=False):
        """Fetch and route the step in flight, if there is one: every
        token is on the host after it. ``then_schedule``: the call goes
        on to schedule (the schedule phase opens again)."""
        fl, self._flight = self._flight, None
        if fl is not None:
            self._fetch(fl)
            self._tok_src[:] = -1
            if then_schedule:
                self._next_phase("serving.pack_tokens")

    def _fetch(self, fl):
        """The wait and route phases of one dispatched step."""
        if self._step_kind is None:
            self._step_kind = fl.kind   # (a call that dispatched nothing)
        self._next_phase("serving.wait", fl.kind)
        out = np.asarray(fl.out)
        self._next_phase("serving.route")
        self._route(fl, out)

    def _tables(self):
        """The block tables the programs take: one a cache kind (None for
        a recurrent kind, whose slot is the row)."""
        return tuple(c.block_tables for c in self._caches)

    def _count_attn_blocks(self, mon, positions, lanes, rows=None,
                           valid=None):
        """How far the paged attention's ragged read engages this step:
        of the ``lanes`` x table-width blocks its lanes' rows span, a
        kernel brings in what ``blocks_walked`` counts (``positions``: a
        burst's valid lanes, every iteration's; a mixed step's whole pack
        with its slot ids ``rows``, the first ``valid`` lanes real): a
        lane of its own ``position // block_size + 1`` blocks, from its
        window's first on in a window layer; a query tile (the lanes of a
        prefill chunk) each block from its first lane's first to its last
        lane's last ONCE; the plain gather path reads them all. Counted a
        kind (one layer of it once), and all kinds together under
        ``extent``; the valid lanes by the path that served them (the
        first kind's) under ``path``."""
        from ..ops.pallas import paged_attention as _pa

        e = self._inner
        width = self._pager.max_blocks_per_seq
        n_valid = positions.size if valid is None else valid
        total = read = 0
        plans = {}                      # one plan a tile size
        first = True
        for ki, kind in enumerate(e.kinds):
            if not kind.paged:          # no blocks: _note_state_slots counts
                continue
            q = jax.ShapeDtypeStruct((e.num_heads, kind.head_dim),
                                     e.emb.dtype)
            entry = self._pools[e.layer_kind.index(ki)]
            k, v = (entry[0], entry[2]) if e.kv_int8 else entry
            tiled = 0
            if _pk.kernel_applies(q, k, v):      # (an int8 pool: never)
                plan = None
                if rows is not None and _pa.tiles_apply(k, kind.num_kv):
                    tq = _pa.tile_lanes(e.num_heads // kind.num_kv,
                                        k.ndim == 3)
                    if tq not in plans:
                        plans[tq] = _pa.plan_tiles(rows, positions, tq, np)
                    plan = plans[tq]
                n, tiled = _pa.blocks_walked(
                    positions, self.block_size, valid, plan, kind.window)
            else:
                n = lanes * width
            if first:
                first = False
                mon.attn_lanes.labels("tiled").inc(tiled)
                mon.attn_lanes.labels("lane").inc(n_valid - tiled)
            mon.kind_blocks.labels(kind.name).inc(n)
            read += n
            total += lanes * width
        mon.attn_blocks.labels("read").inc(read)
        mon.attn_blocks.labels("skipped").inc(total - read)

    def _release_window_blocks(self, mon):
        """What the scheduler adds for sliding-window layers: before a
        step's grants, hand back every block that lies wholly behind the
        window of its row's next query (``lens``: queries only move on).
        Also adds this step's block-steps: blocks in use a kind, times the
        layer caches that keep a pool of it (a looped stack's every pass
        one)."""
        if any(pg.window is not None for pg in self._pagers):
            t0 = mon.mod.now_ns() if mon.tstate.on else 0
            freed = 0
            for pg in self._pagers:
                if pg.window is not None:
                    freed += pg.release_behind(
                        np.where(self._active, self.lens, 0))
            if mon.state.on and freed:
                mon.window_released.inc(freed)
            if mon.tstate.on and freed:
                mon.trace.record_span(
                    "serving.release_window", t0, mon.mod.now_ns(),
                    parent=self._phase.span, attrs={"blocks": freed})
        if mon.state.on:
            for (name, layers, nbytes), pg in zip(self._kind_layers,
                                                  self._pagers):
                held = pg.blocks_in_use * layers
                mon.block_steps.labels(name).inc(held)
                mon.byte_steps.labels(name).inc(held * nbytes)

    def _note_state_slots(self, mon, steps, chunks=()):
        """What the scheduler adds for recurrent layers, in the schedule
        phase: the slots reset since the last step (a request admitted to
        a slot starts at position 0, which the program takes from zeros),
        the tokens and runs the step sends through the recurrence (a layer
        once) by the path that serves them, ``steps`` runs of one (decode
        lanes; a burst's every iteration) and the prefill ``chunks``, a
        chunk of one token a run of one too, and the bytes the slots in use
        hold times the kind's layers."""
        if not self._states:
            return
        t0 = mon.mod.now_ns() if mon.tstate.on else 0
        resets, self._slot_resets = self._slot_resets, 0
        takes = [take for _b, _s, take in chunks]
        ones = steps + sum(1 for n in takes if n == 1)
        runs = sum(1 for n in takes if n > 1)
        tokens = sum(n for n in takes if n > 1)
        if mon.state.on:
            mon.state_resets.inc(resets)
            mon.linear_tokens.labels("step").inc(ones)
            mon.linear_runs.labels("step").inc(ones)
            mon.linear_tokens.labels("chunk").inc(tokens)
            mon.linear_runs.labels("chunk").inc(runs)
            in_use = int(self._active.sum())
            for name, layers, c in self._states:
                mon.byte_steps.labels(name).inc(
                    in_use * layers * c.slot_bytes)
        if mon.tstate.on:
            mon.trace.record_span(
                "serving.state_slots", t0, mon.mod.now_ns(),
                parent=self._phase.span,
                attrs={"reset": resets, "step_runs": ones,
                       "chunk_runs": runs, "chunk_tokens": tokens})

    def _ensure(self, need):
        """ensure_capacity, in every cache kind, with radix-cache relief:
        pool exhaustion evicts exactly the LRU cache-only blocks the grant
        is short of, then retries once (blocks mapped into live requests
        are never taken). A grant that one kind made before another ran
        dry stays with its row: it is used when the row's turn comes, or
        freed with the row."""
        for pg in self._pagers[1:]:
            pg.ensure_capacity(need)
        try:
            self._pager.ensure_capacity(need)
            return
        except RuntimeError:
            if self.prefix_cache is None or not len(self.prefix_cache):
                raise
        pager = self._pager
        owned = (pager._tables_np > 0).sum(axis=1)
        want = -(-np.maximum(np.asarray(need, np.int64), 0)
                 // self.block_size)
        shortfall = int(np.maximum(want - owned, 0).sum()) \
            - len(pager._free)
        mon = _mon()
        freed = self.prefix_cache.evict(max(shortfall, 1),
                                        pools=self._pools)
        if mon.state.on and freed:
            mon.pc_evictions.inc(freed)
            mon.pc_blocks.set(len(self.prefix_cache))
        self._pager.ensure_capacity(need)

    def _free_row(self, slot):
        for pg in self._pagers:
            pg.free_sequence(slot)

    def _step_impl(self, max_new_tokens):
        mon = _mon()
        fl = self._flight
        if fl is not None and fl.epoch != self._epoch:
            # the step in flight belongs to a dead epoch: recover()
            # aborted every request it computed for. Dropped unrouted
            fl = self._flight = None
        if fl is not None and not self._depth():
            self._fetch_flight(then_schedule=True)
        # cancellations first: a cancelled queued request must not be
        # admitted by the drain below, and a cancelled active slot frees
        # its lane (and blocks) before the pack assembles
        self._apply_cancels()
        plan, again = None, False
        while True:
            self._drain_pending()
            if not self._active.any():
                break
            try:
                plan = self._schedule(max_new_tokens, mon, again)
                break
            except _FetchFirst:
                self._fetch_flight(then_schedule=True)
                again = True
        if plan is None:
            # nothing to dispatch: route the step in flight and return
            self._fetch_flight()
            if mon.state.on:
                self._update_gauges(mon)
            return
        prev = self._flight
        self._flight = self._dispatch(plan, prev, max_new_tokens, mon)
        if prev is not None:
            self._fetch(prev)
        if not self._depth():
            self._fetch_flight()

    def _short_of_blocks(self):
        """The pool cannot fund a grant. The step in flight may end a
        request (by EOS) and free its row when it is routed, and what
        comes next (a preemption, a raise) is the last resort: fetch
        first, then schedule anew."""
        if self._flight is not None:
            raise _FetchFirst()

    def _schedule(self, max_new_tokens, mon, again=False):
        """What the next step runs, from the book as it stands after every
        DISPATCHED step: ``(kind, t0, decode_slots, chunks, draft_map)``,
        or None where nothing can (a request was preempted to unstick the
        pool). Grants blocks; advances nothing else. ``again``: the call's
        second schedule, behind an early fetch."""
        t0 = mon.mod.now_ns()
        if not again:
            self._release_window_blocks(mon)
        T = self.max_step_tokens
        decode_slots = np.flatnonzero(self._decode_ready)
        prefill_slots = np.flatnonzero(self._active
                                       & ~self._decode_ready).tolist()
        nd = len(decode_slots)
        draft_map = {}
        spec_ok = False
        if self._drafter is not None and nd:
            # THE verify site of the speculative path: a flag fault here
            # degrades the drafter to plain 1-token decode for this step
            # — outputs stay correct (drafts are only ever verified),
            # just no speedup while the drill holds
            _sp = _fi.fire("serving.spec_verify")
            spec_ok = _sp is None or _sp.action != "flag"
        if spec_ok and not prefill_slots:
            # steady state: the whole spare budget is draft-verify lanes.
            # Grant their blocks HERE, before the burst gate — a pool
            # that cannot fund the drafts must fall back to the K-token
            # burst, not to bare 1-token steps (the grant is idempotent:
            # the mixed path's later _grant_drafts re-ensures owned
            # blocks through the no-grant fast path)
            draft_map = self._collect_drafts(decode_slots, T - nd,
                                             max_new_tokens)
            if draft_map:
                base = np.where(self._active, self.lens, 0)
                base[decode_slots] += 1
                _trial, draft_map = self._grant_drafts(base, draft_map)
        K = self.decode_burst
        if K > 1 and not prefill_slots and not draft_map and nd \
                and (self.lens[decode_slots] + K < self.max_len).all() \
                and self._burst_useful(decode_slots, K, max_new_tokens):
            # steady state: no prefill work in the batch — fuse K decode
            # iterations into one dispatch (multi-step scheduling: the
            # per-dispatch overhead amortizes K-fold). Queued requests
            # lose nothing: _drain_pending just ran, so a non-empty queue
            # means no slot is free until an eviction anyway.
            need = np.where(self._active, self.lens, 0)
            need[decode_slots] += K
            try:
                self._ensure(need)
                granted = True
            except RuntimeError:
                self._short_of_blocks()
                if not self.kv_spill:
                    raise
                granted = False   # single-step path preempts for room
            if granted:
                # every position the burst will write must target an
                # UNSHARED block — CoW runs outside compiled code, so a
                # shared write target forces the single-step path for
                # this step (its per-position CoW handles it)
                t = self._pager._tables_np
                first = self.lens[decode_slots] // self.block_size
                last = (self.lens[decode_slots] + K - 1) // self.block_size
                targets = np.concatenate(
                    [t[b, f:g + 1] for b, f, g in
                     zip(decode_slots, first, last)])
                if not (self._pager._refs[targets] > 1).any():
                    return "burst", t0, decode_slots, (), {}
        if self.policy == "spf":
            prefill_slots.sort(key=lambda b: (
                -self._slots[b].priority,
                len(self._slots[b].prompt) - self._slots[b].prefill_pos,
                self._slots[b].rid))
        else:
            # priority lanes first (the QoS lever), then admission order
            prefill_slots.sort(key=lambda b: (-self._slots[b].priority,
                                              self._slots[b].rid))
        budget = T - nd
        if self.decode_priority > 0.0:
            # bound the prefill share of the pack, but never starve it to
            # zero — an all-prefill engine must still make progress
            budget = min(budget, max(1, int((1.0 - self.decode_priority)
                                            * T)))
        # capacity grants: decode slots MUST proceed; a prefill chunk that
        # cannot get blocks (even after cache eviction) waits a step.
        # With kv_spill, a grant the cache cannot relieve PREEMPTS the
        # lowest-priority non-decoding request (KV to host RAM, blocks
        # back to the pool) instead of failing the step.
        need = np.where(self._active, self.lens, 0)
        need[decode_slots] += 1
        while True:
            try:
                self._ensure(need)
                break
            except RuntimeError:
                self._short_of_blocks()
                if not self.kv_spill:
                    raise
                victim = self._preempt_lowest(exclude=decode_slots)
                if victim is None:
                    raise
                need[victim] = 0
                if victim in prefill_slots:
                    prefill_slots.remove(victim)
        # draft-verify lanes write one position each past the decode
        # fence — their blocks grant opportunistically (speculation is
        # best-effort: a pool that cannot cover the drafts decodes plain)
        need, draft_map = self._grant_drafts(need, draft_map)
        chunks = []                     # (slot, start, take)
        for b in prefill_slots:
            if budget <= 0:
                break
            req = self._slots[b]
            take = min(len(req.prompt) - req.prefill_pos, self.chunk_size,
                       budget)
            trial = need.copy()
            trial[b] = req.prefill_pos + take
            try:
                self._ensure(trial)
            except RuntimeError:
                continue                # waits for evictions to free blocks
            need = trial
            chunks.append((b, req.prefill_pos, take))
            budget -= take
        if spec_ok and not draft_map and prefill_slots:
            # mixed steps spend prefill first (it unblocks new streams);
            # lanes the chunks left over still carry draft verification
            left = T - nd - sum(take for _b, _s, take in chunks)
            if left > 0:
                draft_map = self._collect_drafts(decode_slots, left,
                                                 max_new_tokens)
                need, draft_map = self._grant_drafts(need, draft_map)
        if not nd and not chunks:
            self._short_of_blocks()
            if self.kv_spill and self._preempt_lowest() is not None:
                # pool fully pinned and nothing can progress: spill one
                # request's KV to host RAM; the freed blocks unstick the
                # rest next step and the victim resumes bit-exact later
                return None
            # admitted requests exist but nothing can make progress (pool
            # fully pinned by live sequences) — surface it, the caller
            # sized the pool too small for the batch
            raise RuntimeError(
                "serving step cannot pack any lane: paged KV pool "
                "exhausted with no evictable prefix-cache blocks")
        return "mixed", t0, decode_slots, chunks, draft_map

    def _dispatch(self, plan, prev, max_new_tokens, mon):
        """Pack and dispatch the planned step, then advance the book to
        where it stands once the step has run. ``prev`` is the step still
        unfetched (None at depth 0): the lanes whose token it produced
        take it from its output on the device (``_compose_tokens``).
        Returns the new step's ``_Flight``."""
        kind, t0, decode_slots, chunks, draft_map = plan
        self._step_kind = kind
        if mon.state.on:
            mon.dispatched.labels("no" if prev is None else "yes").inc()
            forwards = self.decode_burst if kind == "burst" else 1
            mon.forwards.inc(forwards)
            mon.passes.inc(forwards * self._inner.passes)
        if kind == "burst":
            fl = self._dispatch_burst(t0, decode_slots, prev, mon)
            lanes = [(int(b) * fl.forwards, 0) for b in decode_slots]
            emits = ()
        else:
            fl, lanes, emits = self._dispatch_mixed(
                t0, decode_slots, chunks, draft_map, prev, mon)
        if mon.state.on and self._shared_layers:
            self._count_shared_rows(mon, fl, len(decode_slots))
        # the download starts now: the fetch, a call later, finds it done
        fl.out.copy_to_host_async()
        if self._out_shapes.get(kind) != fl.out.shape:
            # a program's first output (or a burst's after a decode_burst
            # change): compile now, beside the program, every composition
            # of a known output into either pack, so that none compiles
            # when two kinds first follow each other in steady state
            self._out_shapes[kind] = fl.out.shape
            sds, i32 = jax.ShapeDtypeStruct, jnp.int32
            for shape in self._out_shapes.values():
                for n in (self.max_step_tokens, self.max_batch):
                    if (shape, n) not in self._compose:
                        self._compose[shape, n] = _compose_tokens.lower(
                            sds((3, n), i32),
                            sds(shape, i32, sharding=fl.out.sharding)
                        ).compile()
        if mon.state.on:
            self._record_parts()
        if fl.epoch != self._epoch:
            # a hang recovery superseded this step while it sat in
            # compile/dispatch: the pools rebind stands, the book is the
            # new epoch's. The step is dropped by the next call
            if mon.state.on:
                self._count_lanes(mon, fl)
            return fl
        # -- the book ------------------------------------------------------
        K = fl.forwards
        ended = []
        for b, (first, kb) in zip(decode_slots.tolist(), lanes):
            req = self._slots[b]
            pre = int(self.lens[b])
            take, ends = self._grant(req, pre, K, max_new_tokens)
            self.lens[b] = pre + take
            self._tok_src[b] = first + K - 1
            fl.decode.append((b, req, first, kb, take, ends, pre))
            if ends:
                ended.append(b)
        for (b, start, take), emit in zip(chunks, emits):
            b = int(b)
            req = self._slots[b]
            req.prefill_pos = start + take
            req.chunks += 1
            self.lens[b] = req.prefill_pos
            if self.prefix_cache is not None:
                n = self.prefix_cache.register(
                    req.prompt, req.prefill_pos, self._pager._tables_np[b])
                if mon.state.on and n:
                    mon.pc_blocks.set(len(self.prefix_cache))
            ends = False
            if emit >= 0:
                self._decode_ready[b] = True
                self._tok_src[b] = emit
                # (the first token: lens stands at the prompt's length)
                _, ends = self._grant(req, req.prefill_pos - 1, 1,
                                      max_new_tokens)
                if ends:
                    ended.append(b)
            fl.chunks.append((b, req, start, take, emit, ends))
        if mon.state.on:
            self._count_lanes(mon, fl)
        if self._depth():
            # a request whose last token has been dispatched needs its
            # row no more (the invariant, __init__): the slot is free for
            # the next schedule, routing reports the request
            for b in ended:
                req = self._slots[b]
                self._unreported[req.rid] = req
                self._release(b)
        return fl

    def _count_lanes(self, mon, fl):
        """``paddle_tpu_serving_lane_steps_total{use}`` for a dispatched
        step: each of ``max_batch`` rows once a forward pass; the row-passes
        the book granted work (a token, or a prompt chunk; none for a step
        a recovery superseded) are granted, the rest of the rows that hold
        a request held, the others empty. Before the step's ended requests
        release their rows."""
        granted = sum(d[4] for d in fl.decode) + len(fl.chunks)
        rows = int(self._active.sum()) * fl.forwards
        mon.lane_steps.labels("granted").inc(granted)
        mon.lane_steps.labels("held").inc(rows - granted)
        mon.lane_steps.labels("empty").inc(
            self.max_batch * fl.forwards - rows)

    def _count_shared_rows(self, mon, fl, decoding):
        """``paddle_tpu_serving_shared_expert_rows_total{lanes}`` for a
        dispatched step of a model with a shared expert: the rows its shared
        experts multiply, each lane of the program once a forward pass and a
        layer that has one. lanes=used the valid lanes (a burst: the
        ``decoding`` rows), lanes=idle the program's padding lanes and idle
        rows, which the shared expert multiplies all the same."""
        if fl.kind == "burst":
            used, total = decoding * fl.forwards, self.max_batch * fl.forwards
        else:
            used, total = fl.n_lanes, self.max_step_tokens
        mon.shared_rows.labels("used").inc(used * self._shared_layers)
        mon.shared_rows.labels("idle").inc(
            (total - used) * self._shared_layers)

    def _grant(self, req, lens, n, max_new_tokens):
        """Grant ``req`` up to ``n`` more output tokens from a row that
        holds ``lens``: ``(tokens it takes, whether the last of them ends
        it by length)``, by its own or the step's limit on new tokens or
        by ``max_len``. At least one: a step's token is always routed."""
        take = min(n, max(self.max_len - 1 - lens, 1))
        limit = req.max_new if req.max_new is not None else max_new_tokens
        if limit is not None:
            take = min(take, max(limit - req.granted, 1))
        req.granted += take
        return take, (limit is not None and req.granted >= limit) \
            or lens + take + 1 >= self.max_len

    def _dispatch_mixed(self, t0, decode_slots, chunks, draft_map, prev,
                        mon):
        T = self.max_step_tokens
        nd = len(decode_slots)
        # pack assembly (vectorized — this runs every step): decode lanes
        # (each followed by its draft-verify lanes, so accept chains are
        # contiguous for the device-side scan) first, then prefill
        # chunks. tok_ids/positions (and, behind a step in flight, the
        # lanes' token sources) ride ONE upload; a fresh array each step
        # so the async transfer never races a host-side reuse
        pack_np = np.zeros((3, T), np.int32)
        tok_ids, positions, src = pack_np
        src[:] = -1
        fl = _Flight("mixed", None, self._call[2], t0)
        if draft_map:
            dec_lanes = []              # (slot, base lane, n drafts)
            lane = 0
            for b in decode_slots:
                d = draft_map.get(int(b))
                kb = 0 if d is None else len(d)
                tok_ids[lane] = self._last_tok[b]
                positions[lane] = self.lens[b]
                if kb:
                    # draft j rides position lens+j — exactly where the
                    # serial decode would have fed it; a rejected
                    # draft's write past the accept fence is rolled back
                    # by simply not advancing lens (the position is
                    # re-written before any lane's mask can read it)
                    tok_ids[lane + 1:lane + 1 + kb] = d
                    positions[lane + 1:lane + 1 + kb] = \
                        self.lens[b] + 1 + np.arange(kb)
                dec_lanes.append((int(b), lane, kb))
                lane += 1 + kb
            n_dec_lanes = lane
        else:
            # the draft-free pack (every non-spec engine, every step):
            # keep the PR 5 vectorized assembly — no per-slot loop in
            # the hot path
            dec_lanes = None
            tok_ids[:nd] = self._last_tok[decode_slots]
            src[:nd] = self._tok_src[decode_slots]
            positions[:nd] = self.lens[decode_slots]
            lane = n_dec_lanes = nd
        emits = []                      # a chunk's emitting lane, or -1
        for b, start, take in chunks:
            req = self._slots[b]
            tok_ids[lane:lane + take] = req.prompt[start:start + take]
            positions[lane:lane + take] = np.arange(start, start + take)
            # the lane of its LAST prompt token emits its first token
            emits.append(lane + take - 1
                         if start + take == len(req.prompt) else -1)
            lane += take
        n_lanes = lane
        # copy-on-write: any lane writing into a SHARED block (prefix-
        # cache full hits, beam-style forks) gets a private copy first;
        # the all-refs<=1 guard keeps the unshared steady state free
        if (self._pager._refs > 1).any():
            rows = np.empty(n_lanes, np.int64)
            if dec_lanes is None:
                rows[:nd] = decode_slots
            else:
                for b, lane0, kb in dec_lanes:
                    rows[lane0:lane0 + 1 + kb] = b
            lane = n_dec_lanes
            for b, _start, take in chunks:
                rows[lane:lane + take] = b
                lane += take
            try:
                self._pools = self._pager.make_positions_exclusive(
                    rows, positions[:n_lanes], self._pools)
            except _pk.CowPoolExhausted as e:
                # copies made before the pool ran dry ARE applied and the
                # donated-in buffers were consumed — adopt the exception's
                # replacement pools, hand cache-only blocks back, retry
                self._pools = e.pools
                if self.prefix_cache is None \
                        or not len(self.prefix_cache):
                    raise
                freed = self.prefix_cache.evict(n_lanes,
                                                pools=self._pools)
                if mon.state.on and freed:
                    mon.pc_evictions.inc(freed)
                    mon.pc_blocks.set(len(self.prefix_cache))
                try:
                    self._pools = self._pager.make_positions_exclusive(
                        rows, positions[:n_lanes], self._pools)
                except _pk.CowPoolExhausted as e2:
                    # the retry donates buffers too: adopt its replacement
                    # before propagating, or the engine is left holding
                    # consumed device arrays
                    self._pools = e2.pools
                    raise
        # slot-id/valid/chain lane vectors depend only on the pack
        # COMPOSITION, which repeats every step in steady decode — reuse
        # the uploaded device copies instead of re-transferring them
        key = (decode_slots.tobytes(),
               () if dec_lanes is None
               else tuple(kb for _b, _l, kb in dec_lanes),
               tuple((b, take) for b, _s, take in chunks))
        cached = self._lane_cache.get(key)
        if cached is None:
            slot_np = np.zeros(T, np.int32)
            valid_np = np.zeros(T, bool)
            chain_np = np.zeros(T, bool)
            if dec_lanes is None:
                slot_np[:nd] = decode_slots
            else:
                for b, lane0, kb in dec_lanes:
                    slot_np[lane0:lane0 + 1 + kb] = b
                    chain_np[lane0 + 1:lane0 + 1 + kb] = True
            lane = n_dec_lanes
            for b, _start, take in chunks:
                slot_np[lane:lane + take] = b
                lane += take
            valid_np[:n_lanes] = True
            cached = (jnp.asarray(slot_np), jnp.asarray(valid_np),
                      jnp.asarray(chain_np), slot_np)
            if len(self._lane_cache) > 256:
                self._lane_cache.clear()
            self._lane_cache[key] = cached
        slots_dev, valid_dev, chain_dev, slot_np = cached
        step = self._step_jit()
        if self._phase.span is not None:
            self._phase.attrs = {
                "n_decode": nd, "n_draft": n_dec_lanes - nd,
                "n_prefill": n_lanes - n_dec_lanes, "budget": T}
        if mon.state.on:
            self._count_attn_blocks(mon, positions, T, slot_np, n_lanes)
        self._note_state_slots(mon, nd, chunks)
        self._next_phase("serving.dispatch", "mixed")
        pack = jnp.asarray(pack_np[:2]) if prev is None \
            else self._compose[prev.out.shape, T](jnp.asarray(pack_np),
                                                  prev.out)
        fl.out, self._pools = step(
            pack, self._pools, self._tables(),
            slots_dev, valid_dev, chain_dev, self._inner.weights)
        if _sanitizers._state.numerics:
            self._san_steps += 1
            _sanitizers.numsan_check(
                "serving.mixed_step",
                (("tokens", fl.out), ("kv_pools", self._pools)),
                step=self._san_steps)
        fl.n_lanes = n_lanes
        fl.n_draft = n_dec_lanes - nd
        # each decode slot's (base lane, draft lanes behind it)
        lanes = [(i, 0) for i in range(nd)] if dec_lanes is None \
            else [(lane0, kb) for _b, lane0, kb in dec_lanes]
        return fl, lanes, emits

    def _dispatch_burst(self, t0, decode_slots, prev, mon):
        """Steady-state fast path: K fused decode iterations, one
        dispatch, one (2, B) upload, one (B, K) download."""
        K = self.decode_burst
        pack = np.empty((3, self.max_batch), np.int32)
        pack[0] = self._last_tok
        pack[1] = self.lens
        pack[2] = self._tok_src
        burst = self._burst_jit()
        if self._phase.span is not None:
            self._phase.attrs = {"n_decode": len(decode_slots), "burst": K}
        if mon.state.on:
            self._count_attn_blocks(
                mon, np.add.outer(self.lens[decode_slots], np.arange(K)),
                self.max_batch * K)
        self._note_state_slots(mon, K * len(decode_slots))
        self._next_phase("serving.dispatch", "burst")
        fl = _Flight("burst", None, self._call[2], t0, forwards=K)
        pack_dev = jnp.asarray(pack[:2]) if prev is None \
            else self._compose[prev.out.shape, self.max_batch](
                jnp.asarray(pack), prev.out)
        fl.out, self._pools = burst(
            pack_dev, self._pools, self._tables(), self._inner.weights)
        if _sanitizers._state.numerics:
            self._san_steps += 1
            _sanitizers.numsan_check(
                "serving.decode_burst",
                (("tokens", fl.out), ("kv_pools", self._pools)),
                step=self._san_steps)
        return fl

    def _route(self, fl, out):
        """What needs the token VALUES of a fetched step: the requests'
        outputs and token times, TTFT, the token gaps, the finished list
        (``self._out``), an end by EOS. A lane whose request has ended
        since the step was dispatched is discarded."""
        mon = _mon()
        if fl.epoch != self._epoch:
            # a recovery superseded this step while it was in flight or
            # sat in compile/dispatch: every request it computed for was
            # aborted, and every host slot/table/token mutation now
            # belongs to the new epoch. Apply nothing. (The pools the
            # step returned stand, rebound at its dispatch — the jit
            # result is the only live buffer set on donation platforms,
            # and the radix cache's pinned blocks live in it untouched:
            # the step only wrote positions the dead epoch's tables
            # mapped, all freed by the recovery.)
            return
        eos_token_id, max_new_tokens = self._call[:2]
        finished = self._out
        K = fl.forwards
        flat = out.reshape(-1)          # row 0 (a burst: the rows) first
        if mon.state.on:
            if fl.kind == "mixed" and len(out) > 2:
                self._count_expert_pairs(mon, out[2][:4], 1)
            elif fl.kind == "burst" and len(out) > self.max_batch:
                self._count_expert_pairs(mon, out[-4:].sum(axis=1), K)
        t0, t1 = fl.t0, mon.mod.now_ns()
        nd = len(fl.decode)
        if mon.tstate.on:
            for b, req, *_ in fl.decode:
                entry = self._span_entry(req.rid)
                if entry is not None:
                    attrs = {"slot": b, "n_active": nd}
                    if fl.kind == "burst":
                        attrs["burst"] = K
                    mon.trace.record_span(
                        "serving.decode_step", t0, t1, parent=entry[0],
                        attrs=attrs)
            for b, req, start, take, _emit, _ends in fl.chunks:
                entry = self._span_entry(req.rid)
                if entry is not None:
                    mon.trace.record_span(
                        "serving.prefill_chunk", t0, t1, parent=entry[0],
                        attrs={"slot": b, "start": start, "tokens": take})
        # route decode results: every slot emits the tokens it was
        # granted (a burst's up to K) plus one per ACCEPTED draft (longest
        # agreeing prefix, computed on device; granted here, where their
        # number is known) — the greedy sequence, several tokens a dispatch
        emitted = 0
        n_accept = 0
        for b, req, first, kb, take, ends, pre in fl.decode:
            if req.done:
                continue                # ended since: the lane is discarded
            n = take
            if kb:
                n += int(out[1][first + 1:first + 1 + kb].sum())
            routed = 0
            for j in range(n):
                if j < take:
                    last = ends and j == take - 1
                else:
                    self.lens[b] += 1
                    last = self._grant(req, pre + j, 1, max_new_tokens)[1]
                emitted += 1
                routed += 1
                if self._note_token(req, b, int(flat[first + j]), last,
                                    pre + j + 1, eos_token_id, finished,
                                    mon, t1):
                    break       # ended: the rest of its lane is discarded
            # accepted = draft tokens actually DELIVERED: an eos
            # mid-chain discards the rest of the lane, and the
            # cataloged counter promises emitted tokens
            n_accept += max(routed - 1, 0) if kb else 0
            if self._slots[b] is req:
                self._register_decode_blocks(b, pre, mon)
        if fl.n_draft:
            self.spec_drafted += fl.n_draft
            self.spec_accepted += n_accept
            if mon.state.on:
                mon.spec_drafted.inc(fl.n_draft)
                mon.spec_accepted.inc(n_accept)
                mon.spec_rate.set(self.spec_accepted
                                  / max(self.spec_drafted, 1))
            if mon.tstate.on:
                mon.trace.record_span(
                    "serving.spec_verify", t0, t1,
                    attrs={"drafted": fl.n_draft, "accepted": n_accept,
                           "lanes": nd})
        # first tokens of completed prefills
        for b, req, _start, _take, emit, ends in fl.chunks:
            if emit < 0 or req.done:
                continue
            req.t_first = t1
            emitted += 1
            with self._submit_lock:
                st = self._stats.get(req.rid)
                if st is not None:
                    st["ttft_ns"] = t1 - req.t_submit
                    st["prefill_chunks"] = req.chunks
            if mon.state.on:
                mon.ttft.observe(t1 - req.t_submit)
                mon.prefill.observe(t1 - req.t_admit)
                mon.chunk_depth.observe(req.chunks)
            entry = self._span_entry(req.rid)
            if entry is not None:
                mon.trace.record_span(
                    "serving.prefill", req.t_admit, t1,
                    parent=entry[0],
                    attrs={"slot": b,
                           "prompt_len": len(req.prompt),
                           "chunks": req.chunks,
                           "shared_tokens": req.shared_tokens})
            self._note_token(req, b, int(flat[emit]), ends,
                             len(req.prompt), eos_token_id, finished, mon,
                             t1)
        if mon.state.on:
            mon.decode.observe(t1 - t0)
            mon.tokens.inc(emitted)
            if fl.kind == "mixed":
                mon.pack.observe(fl.n_lanes)
            mon.steps.labels(fl.kind).inc()
            self._update_gauges(mon)
            mon.mod.sample()   # chrome-trace counter timeline, per step

    def _register_decode_blocks(self, slot, pre_lens, mon):
        """With speculation on, GENERATED full blocks join the radix
        chain too (prompt blocks already do, at prefill): a repeated
        prompt then finds its previous run's whole continuation as chain
        children, and the drafter's radix source proposes it — greedy
        decoding is deterministic, so those drafts verify near-perfectly.
        Only spec engines pay the pins: without a drafter nothing would
        ever read the decode chain. ``pre_lens=None`` registers
        unconditionally (the eviction-time tail sweep); otherwise only
        when this step crossed a block boundary."""
        if self._drafter is None or self.prefix_cache is None:
            return
        req = self._slots[slot]
        if req is None or not req.outputs:
            return
        bs = self.block_size
        if pre_lens is not None \
                and int(self.lens[slot]) // bs == int(pre_lens) // bs:
            return                      # no block filled this step
        # resume the chain walk where the last crossing left it (the
        # context is append-only, so the cursor digest stays valid) and
        # hand register_from only the tokens past the cursor block —
        # re-digesting (or re-copying) the whole context on every
        # crossing is quadratic in generation length, on the serving
        # hot path
        cursor = self._chain_cursors.get(slot, (0, b""))
        start = int(cursor[0]) * bs
        lp = len(req.prompt)
        if start < lp:
            tail = np.concatenate([
                np.asarray(req.prompt[start:], np.int32),
                np.asarray(req.outputs, np.int32)])
        else:
            tail = np.asarray(req.outputs[start - lp:], np.int32)
        n, cursor = self.prefix_cache.register_from(
            cursor, tail, int(self.lens[slot]),
            self._pager._tables_np[slot])
        self._chain_cursors[slot] = cursor
        if mon.state.on and n:
            mon.pc_blocks.set(len(self.prefix_cache))

    def _collect_drafts(self, decode_slots, budget, max_new_tokens):
        """Ask the drafter (models/spec_decode.py) for up to
        ``spec_lookahead`` tokens per decode lane, bounded by the step's
        spare lane budget, the cache capacity, and the request's
        remaining token allowance — drafting past any of them would burn
        lanes that can never emit."""
        draft_map = {}
        left = int(budget)
        for b in decode_slots:
            if left <= 0:
                break
            req = self._slots[b]
            cap = min(self.spec_lookahead, left,
                      self.max_len - 1 - int(self.lens[b]))
            limit = req.max_new if req.max_new is not None \
                else max_new_tokens
            if limit is not None:
                cap = min(cap, limit - req.granted - 1)
            if cap <= 0:
                continue
            d = self._drafter.draft(req.rid, cap)
            if len(d):
                draft_map[int(b)] = d
                left -= len(d)
        return draft_map

    def _grant_drafts(self, need, draft_map):
        """Opportunistic block grant for draft-verify lanes: every
        drafted position may be written (rejected drafts included), so
        each needs a granted block. Speculation is best-effort — a slot
        whose drafts the pool cannot cover just decodes plainly this
        step, WITHOUT dropping the other slots' drafts (per-slot
        grants). The grant goes to the RAW allocator, never through
        _ensure's radix relief: speculation must not evict (or spill)
        the very cache blocks its chain drafts read from."""
        if not draft_map:
            return need, draft_map
        trial = need.copy()
        kept = {}
        for b, d in draft_map.items():
            t2 = trial.copy()
            t2[b] += len(d)
            try:
                self._pager.ensure_capacity(t2)
            except RuntimeError:
                continue
            trial = t2
            kept[b] = d
        return trial, kept

    def _burst_useful(self, decode_slots, K, max_new_tokens):
        """Worth bursting only when at least half the fused lanes would
        emit kept tokens — slots at the edge of their max_new budget (or
        requests queued behind an imminent eviction) prefer the
        single-step path's per-token scheduling."""
        useful = 0
        for b in decode_slots:
            req = self._slots[b]
            limit = req.max_new if req.max_new is not None \
                else max_new_tokens
            useful += K if limit is None \
                else min(K, max(limit - req.granted, 0))
        return 2 * useful >= K * len(decode_slots)

    def _count_expert_pairs(self, mon, pairs, forwards):
        """What a step's program counted (``pairs`` [4]): the (token, expert)
        pairs on the experts held here and in all, the held experts that got
        a pair, and the rows of the row tiles the grouped product visited
        (every lane's pairs, each group padded to whole tiles of the Pallas
        kernels' sublane rows, or of ``ragged_dot``'s one row: held over it
        is the tiles' fill); and the expert calls the pairs had to share:
        held experts x expert layers x forward passes."""
        e = self._inner
        for where, n in zip(("held", "routed", "experts_hit", "kernel_rows"),
                            pairs):
            mon.expert_pairs.labels(where).inc(int(n))
        mon.expert_pairs.labels("expert_calls").inc(
            forwards * e.held_experts * sum("router" in p for p in e.layers))

    def _note_token(self, req, slot, tok, last, pos, eos_token_id,
                    finished, mon, t_now):
        """Route one output token of ``req`` (in, or lately in, ``slot``);
        ``last``: it was granted as the request's last (an end by
        length), ``pos``: what the row holds with it. Returns whether the
        request ended, by length or by EOS."""
        req.outputs.append(tok)
        # one time per output token: the fetch time of the step that
        # yielded it (tokens of one step share it)
        times = req.token_times
        if times and mon.state.on:
            mon.token_gap.observe(t_now - times[-1])
        times.append(t_now)
        req.last_token = tok
        held = self._slots[slot] is req
        if held:
            self._last_tok[slot] = tok
        if self._drafter is not None:
            self._drafter.note(req.rid, tok)
        if not last and (eos_token_id is None or tok != eos_token_id):
            return False
        finished.append((req.rid, list(req.outputs)))
        if held:
            # (an end by EOS: steps dispatched since may have moved the
            # row on, past the request's end)
            self.lens[slot] = pos
            self._evict(slot, t_now)
        else:
            self._report(req, slot, t_now)
        return True

    def _release(self, slot):
        """Free ``slot``: its row in every cache kind and its place in
        the scheduler's book. Sound as soon as the last step that reads
        the row has been dispatched (the invariant, ``__init__``)."""
        self._free_row(slot)
        self._slots[slot] = None
        self._active[slot] = False
        self._decode_ready[slot] = False
        self.lens[slot] = 0
        self._tok_src[slot] = -1
        self._chain_cursors.pop(slot, None)

    def _evict(self, slot, t0=None):
        req = self._slots[slot]
        # last chance to chain the generation's tail blocks: a finishing
        # request's final block-crossings happen inside the same routing
        # loop that evicts it, so register (and pin) them before the row
        # is freed — a repeated prompt then drafts the WHOLE previous run
        self._register_decode_blocks(slot, None, _mon())
        self._release(slot)
        self._report(req, slot, t0)

    def _report(self, req, slot, t0=None):
        """The end of a request that holds no slot any more: its stats,
        its trace tree, the counters."""
        mon = _mon()
        req.done = True
        self._unreported.pop(req.rid, None)
        with self._submit_lock:
            _sanitizers.race_access(self._san_tag, "_req_spans",
                                    write=True)
            _sanitizers.race_access(self._san_tag, "_stats", write=True)
            entry = self._req_spans.pop(req.rid, None)
            st = self._stats.get(req.rid)
            if st is not None:
                st["tokens"] = len(req.outputs)
                st["token_times_ns"] = req.token_times
        t0 = t0 or (mon.mod.now_ns() if entry is not None else 0)
        if self._drafter is not None:
            self._drafter.drop(req.rid)
        if entry is not None:
            t1 = mon.mod.now_ns()
            mon.trace.drop(entry[1])   # only open if tracing toggled off
            mon.trace.record_span("serving.evict", t0, t1, parent=entry[0],
                                  attrs={"slot": slot,
                                         "tokens": len(req.outputs)})
            mon.trace.end_span(entry[0], t1_ns=t1)   # request tree complete
        if mon.state.on:
            mon.evictions.inc()
            self._update_gauges(mon)

    def _update_gauges(self, mon):
        depth = 0
        with self._submit_lock:
            lanes = [(t.name, len(t.queue))
                     for t in self._tenants.values()]
        for name, n in lanes:
            depth += n
            mon.tenant_depth.labels(name).set(n)
        mon.queue_depth.set(depth)
        mon.occupancy.set(float(self._active.sum()) / self.max_batch)
        mon.pool_bytes.set(self.kv_pool_bytes)
        mon.state_bytes.set(self.state_pool_bytes)

    @property
    def num_active(self):
        """Requests in a slot; and true while a dispatched step is
        unfetched, whose routing may still hand a request back."""
        return int(self._active.sum()) or int(self._flight is not None)

    @property
    def num_pending(self):
        return sum(len(t.queue) for t in list(self._tenants.values()))

    # -- fleet-facing surface (cancellation + queue withdrawal) --------------
    def cancel(self, rid):
        """Request cancellation of one request (thread-safe: pure
        enqueue, like submit()). The DRIVING thread applies it at the
        next step boundary: a queued request leaves its tenant lane, an
        active request's slot is evicted (blocks freed) without emitting
        a result. A request that already finished is unaffected — its
        result stands. This is the tail-hedging loser's exit path
        (serving/fleet.py): the slower duplicate stops burning lanes
        the moment the winner lands."""
        self._cancel_q.append(rid)

    def _apply_cancels(self):
        """Driving thread only: apply every pending cancellation."""
        rids = set(_drain(self._cancel_q))
        if not rids:
            return
        mon = _mon()
        n = 0
        with self._submit_lock:
            for ten in self._tenants.values():
                for req in [r for r in ten.queue if r.rid in rids]:
                    ten.queue.remove(req)
                    rids.discard(req.rid)
                    self._stats.pop(req.rid, None)
                    entry = self._req_spans.pop(req.rid, None)
                    if entry is not None:
                        mon.trace.drop(entry[1])
                        mon.trace.end_span(entry[0])
                    n += 1
        if rids:
            # not queued: in a slot, or already ended. The step in flight
            # is routed first (a request that ends there stands), so that
            # what is dropped below is a request with no token under way
            self._fetch_flight(then_schedule=True)
        for b in range(self.max_batch):
            req = self._slots[b]
            if req is not None and req.rid in rids:
                self._evict(b)          # frees blocks; no result emitted
                with self._submit_lock:
                    self._stats.pop(req.rid, None)
                n += 1
        if n:
            self.cancelled += n
            if mon.state.on:
                mon.cancelled.inc(n)
                self._update_gauges(mon)

    def withdraw_pending(self):
        """Pull every QUEUED (not yet admitted) request out of the
        tenant lanes (thread-safe: queue surgery under the submit lock
        only — slot/pager state is untouched). Returns a list of
        ``{"rid", "prompt", "max_new", "tenant", "outputs"}`` dicts
        (``outputs`` is non-empty for a preempted request re-queued
        mid-generation). The fleet router uses this to MIGRATE a
        draining or circuit-broken replica's queued work to its peers
        — zero requests stranded behind a down replica."""
        mon = _mon()
        out = []
        with self._submit_lock:
            for ten in self._tenants.values():
                while ten.queue:
                    req = ten.queue.popleft()
                    self._stats.pop(req.rid, None)
                    entry = self._req_spans.pop(req.rid, None)
                    if entry is not None:
                        mon.trace.drop(entry[1])
                        mon.trace.end_span(entry[0])
                    out.append({"rid": req.rid, "prompt": req.prompt,
                                "max_new": req.max_new,
                                "tenant": req.tenant,
                                "outputs": list(req.outputs)})
        if out and mon.state.on:
            self._update_gauges(mon)
        return out

    # -- crash/hang recovery (the drilled path) ------------------------------
    def recover(self, reason="", stuck=""):
        """Tear down the slot state of a dead or hung epoch and restart
        WARM: a flight dump documents what was running (coalescing with
        any watchdog dump of the same hang into ONE file), every in-
        flight request is aborted with a typed :class:`RequestAborted`
        carrying its partial tokens (drained via :meth:`pop_aborted` —
        no caller hangs silently), slots and pager rows are freed, and
        the radix cache SURVIVES — re-submissions of the same prompts
        prefix-hit instead of recomputing (and with ``kv_spill``,
        spilled prefixes restore from host RAM). Queued requests stay
        queued. Thread-safe and idempotent per hang: concurrent
        observers (the dying driving thread, the hang watchdog) collapse
        to one recovery — the loser returns immediately. A SLOW-but-
        alive step this recovery supersedes is fenced on the epoch: it
        wakes from its dispatch, re-binds only the pool buffers (which
        the warm restart deliberately shares — the radix cache's pinned
        blocks live there) and applies no host slot/table state; the
        remaining unfenced window is the microseconds of host-side pack
        assembly before its dispatch, vs the seconds-scale hang timeout
        that triggers a recovery at all."""
        if not self._recover_lock.acquire(blocking=False):
            # another observer of the same failure is already recovering
            return None
        try:
            mon = _mon()
            t0 = mon.mod.now_ns()
            # the epoch bump FIRST: a step stuck at its injection point
            # wakes, sees the new epoch, and returns without touching
            # the state this recovery owns
            self._epoch += 1
            open_serving = [s.name for s in mon.trace.open_spans()
                            if s.name.startswith("serving.")]
            path = None
            try:
                if mon.tstate.on or os.environ.get("PADDLE_TPU_FLIGHT_DIR"):
                    path = mon.trace.flight_dump(
                        reason=f"serving recovery ({self._san_tag}): "
                               f"{reason}"
                               + (f"; stuck span: {stuck}" if stuck
                                  else ""),
                        extra={"engine": self._san_tag,
                               "open_serving_spans": open_serving,
                               "active": int(self._active.sum()),
                               "epoch": self._epoch},
                        # per-engine dump file: this recovery coalesces
                        # with THIS engine's watchdog dump and never
                        # blends with a sibling replica's
                        key=self._san_tag)
            except Exception:  # noqa: BLE001 - a dump failure never
                pass           # masks the recovery it documents
            self.last_recovery_dump = path
            aborted = 0
            # the step in flight, and one the driving thread is stuck
            # fetching, belong to the dead epoch: dropped, as a superseded
            # step is (step() and _route check its epoch). A request whose
            # row either released, and which routing had yet to report, is
            # aborted like those in the slots
            self._flight = None
            held = [(b, req) for b, req in enumerate(self._slots)
                    if req is not None]
            held += [(None, req) for req in list(self._unreported.values())]
            self._unreported.clear()
            for b, req in held:
                req.done = True
                # the partial stats ride the typed abort (popped, not
                # orphaned: nobody ever pops the dead rid's record —
                # callers track the replacement) so a router can merge
                # ttft/chunks/shared into the re-routed request's final
                # stats and fleet TTFT percentiles stay honest
                with self._submit_lock:
                    st = self._stats.pop(req.rid, None)
                    if st is not None:
                        st["aborted"] = True
                        st["tokens"] = len(req.outputs)
                        st["token_times_ns"] = list(req.token_times)
                    entry = self._req_spans.pop(req.rid, None)
                self._aborted.append(RequestAborted(
                    f"request {req.rid} aborted by engine recovery: "
                    f"{reason}", rid=req.rid, tokens=req.outputs,
                    tenant=req.tenant, stats=st))
                aborted += 1
                if entry is not None:
                    mon.trace.drop(entry[1])
                    mon.trace.end_span(entry[0])
                if b is not None:
                    self._free_row(b)
                    self._slots[b] = None
                if self._drafter is not None:
                    self._drafter.drop(req.rid)
            self._active[:] = False
            self._decode_ready[:] = False
            self.lens[:] = 0
            self._last_tok[:] = 0
            self._tok_src[:] = -1
            self._lane_cache.clear()
            self._chain_cursors.clear()
            # NOT torn down: the compiled programs (still valid), the
            # admission queues, and the radix cache + its pinned blocks
            # (request refs were freed above; cache refs keep the prefix
            # KV alive) — that is what makes the restart WARM
            cold = self.prefix_cache is None or not len(self.prefix_cache)
            t1 = mon.mod.now_ns()
            self.recovery_stats.append({
                "reason": reason, "ms": (t1 - t0) / 1e6,
                "aborted": aborted, "cold": cold, "dump": path})
            if mon.tstate.on:
                mon.trace.record_span(
                    "serving.recover", t0, t1,
                    attrs={"reason": reason[:120], "aborted": aborted,
                           "cold": cold})
            if mon.state.on:
                mon.recoveries.inc()
                if aborted:
                    mon.aborted.inc(aborted)
                self._update_gauges(mon)
            return aborted
        finally:
            self._recover_lock.release()

    def pop_aborted(self):
        """Drain the typed :class:`RequestAborted` records of requests a
        recovery cut short (each carries the partial ``tokens``)."""
        return _drain(self._aborted)

    # -- driving thread (crash/hang drills run against THIS loop) ------------
    def start_driver(self, eos_token_id=None, max_new_tokens=None,
                     hang_timeout=None, poll_s=0.0005):
        """Spawn the engine's driving thread: it drains admissions and
        steps whenever work is pending, parking finished
        ``(rid, tokens)`` pairs for :meth:`pop_results`. Producers keep
        calling :meth:`submit` from any thread. If the thread DIES
        (anything step() raises — an injected fault, a real allocator
        bug), it runs :meth:`recover` and relaunches itself warm.
        ``hang_timeout`` arms a hang watchdog: a step stuck longer than
        that many seconds gets a watchdog flight dump naming the stuck
        section AND a recovery from the scanner thread (the two dumps
        coalesce into one file; the stuck step returns empty on wake-up
        via the epoch check)."""
        if self._driver is not None and self._driver.is_alive():
            return
        self._drive_args = (eos_token_id, max_new_tokens, float(poll_s))
        self._drive_stop.clear()
        if hang_timeout is not None:
            from ..distributed.watchdog import CommWatchdog

            self._dog = CommWatchdog(timeout=float(hang_timeout),
                                     on_timeout=self._on_hang,
                                     flight_key=self._san_tag)
        self._spawn_driver()

    def stop_driver(self, timeout=5.0):
        """Stop the driving thread (current step completes first)."""
        self._drive_stop.set()
        drv = self._driver
        if drv is not None and drv.is_alive():
            drv.join(timeout=timeout)
        if self._dog is not None:
            self._dog.stop()
            self._dog = None
        self._driver = None

    def pop_results(self):
        """Drain finished ``(rid, tokens)`` pairs collected by the
        driving thread."""
        return _drain(self._results)

    def _spawn_driver(self):
        t = threading.Thread(target=self._drive_loop, daemon=True,
                             name=f"serving-driver-{self._san_tag}")
        self._driver = t
        t.start()

    def _on_hang(self, desc, dump):
        """Watchdog scanner callback: a watched step exceeded the hang
        timeout. The watchdog already wrote its flight dump; recover()'s
        dump coalesces with it (same file, both reasons)."""
        self.recover(f"watchdog-detected hang: {desc} exceeded "
                     f"{self._dog.timeout}s", stuck=desc)

    def _drive_loop(self):
        eos, max_new, poll = self._drive_args
        while not self._drive_stop.is_set():
            try:
                if not (self.num_active or self.num_pending):
                    time.sleep(poll)
                    continue
                # chaos drills kill the driving thread here, right before
                # a step that HAS work (an idle poll never burns the
                # trigger count) — the except below IS the crash-recovery
                # path being drilled
                _fi.fire("serving.drive")
                if self._dog is not None:
                    with self._dog.watch("serving.step"):
                        finished = self.step(eos, max_new)
                else:
                    finished = self.step(eos, max_new)
                self._results.extend(finished)
            except Exception as e:  # noqa: BLE001 - the drill contract:
                # ANY driving-thread death recovers + relaunches warm
                if self._drive_stop.is_set():
                    return
                point = getattr(e, "point", "")
                self.recover(
                    f"driving thread died: {type(e).__name__}: {e}",
                    stuck=point or "serving.step")
                if not self._drive_stop.is_set():
                    self._spawn_driver()
                return

