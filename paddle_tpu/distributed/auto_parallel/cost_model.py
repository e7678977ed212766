"""Analytic cost model: step-time and memory estimates per parallel config.

Reference analog: python/paddle/distributed/auto_parallel/static/cost/ — the
op-level comp/comm cost tables and estimator that power Engine.cost() and the
planner. TPU-first redesign: transformer training cost has a closed form on
this hardware — MXU FLOPs, HBM traffic, and collective volume over ICI/DCN —
so the estimator is a roofline calculation over (model, parallel config,
hardware profile) instead of per-op cost tables. The FLOPs accounting is
PaLM appendix B's (6N + 12*L*h*s per token); the collective terms use
ring costs (2(n-1)/n for allreduce, (n-1)/n for reduce-scatter/allgather).

Powers Engine.cost() and the AutoTuner's pre-trial pruning/ordering
(round-3 VERDICT #6).
"""
from __future__ import annotations

__all__ = ["HardwareProfile", "ModelDesc", "ParallelConfig", "CostEstimate",
           "estimate_cost", "rank_candidates"]


class HardwareProfile:
    """Per-chip peaks + interconnect bandwidths (bytes/s)."""

    # chip name -> (peak bf16 FLOP/s, HBM B/s, ICI B/s per direction)
    KNOWN = {
        "tpu v4": (275e12, 1.2e12, 4 * 50e9),
        "tpu v5e": (197e12, 0.82e12, 4 * 25e9),
        "tpu v5 lite": (197e12, 0.82e12, 4 * 25e9),
        "tpu v5p": (459e12, 2.8e12, 6 * 100e9),
        "tpu v6e": (918e12, 1.6e12, 4 * 50e9),
        "a100": (312e12, 2.0e12, 300e9),        # for parity comparisons
        "cpu": (0.5e12, 0.05e12, 10e9),
    }

    def __init__(self, peak_flops, hbm_bw, ici_bw, dcn_bw=25e9,
                 mfu_ceiling=0.6):
        self.peak_flops = float(peak_flops)
        self.hbm_bw = float(hbm_bw)
        self.ici_bw = float(ici_bw)
        self.dcn_bw = float(dcn_bw)
        # assumed achievable fraction of peak on large matmuls; no measured
        # figure stands behind it yet (PERF.md)
        self.mfu_ceiling = float(mfu_ceiling)

    @classmethod
    def named(cls, name, **kw):
        key = name.lower()
        for k, (f, h, i) in cls.KNOWN.items():
            if k in key:
                return cls(f, h, i, **kw)
        raise KeyError(f"unknown hardware {name!r}; pass explicit peaks")

    @classmethod
    def calibrated(cls, measured_matmul_flops, hbm_bw=None, ici_bw=None):
        """Build a profile from a measured large-matmul throughput (the CPU
        test path: peak is whatever this box actually sustains)."""
        return cls(measured_matmul_flops, hbm_bw or measured_matmul_flops / 8,
                   ici_bw or 10e9, mfu_ceiling=1.0)


class ModelDesc:
    """Transformer shape (the flagship-LLaMA parameterization)."""

    def __init__(self, n_params, hidden, layers, seq, vocab=32000,
                 dtype_bytes=2):
        self.n_params = int(n_params)
        self.hidden = int(hidden)
        self.layers = int(layers)
        self.seq = int(seq)
        self.vocab = int(vocab)
        self.dtype_bytes = int(dtype_bytes)

    @classmethod
    def from_llama_config(cls, cfg, n_params=None):
        if n_params is None:
            h, i, l, v = (cfg.hidden_size, cfg.intermediate_size,
                          cfg.num_hidden_layers, cfg.vocab_size)
            n_params = l * (4 * h * h + 3 * h * i) + 2 * v * h
        return cls(n_params, cfg.hidden_size, cfg.num_hidden_layers,
                   cfg.max_position_embeddings, cfg.vocab_size,
                   2 if "bf16" in str(getattr(cfg, "dtype", "")) else 4)


class ParallelConfig:
    def __init__(self, dp=1, mp=1, pp=1, sep=1, micro_batch_size=1,
                 n_micro=1, sharding_stage=0, recompute=False):
        self.dp = int(dp)
        self.mp = int(mp)
        self.pp = int(pp)
        self.sep = int(sep)
        self.micro_batch_size = int(micro_batch_size)
        self.n_micro = max(1, int(n_micro))
        self.sharding_stage = int(sharding_stage)
        self.recompute = bool(recompute)

    @classmethod
    def from_candidate(cls, cand, global_batch=None):
        dp = cand.get("dp_degree", 1)
        mbs = cand.get("micro_batch_size", 1)
        n_micro = 1
        if global_batch:
            n_micro = max(1, global_batch // (dp * mbs))
        return cls(dp=dp, mp=cand.get("mp_degree", 1),
                   pp=cand.get("pp_degree", 1),
                   sep=cand.get("sep_degree", 1),
                   micro_batch_size=mbs, n_micro=n_micro,
                   sharding_stage=cand.get("sharding_stage", 0),
                   recompute=cand.get("recompute", False))


class CostEstimate:
    """Breakdown + headline numbers; ordered by step_time."""

    def __init__(self, **kw):
        self.compute_time = kw["compute_time"]
        self.memory_time = kw["memory_time"]
        self.comm_time = kw["comm_time"]
        self.bubble_fraction = kw["bubble_fraction"]
        self.step_time = kw["step_time"]
        self.tokens_per_sec_per_chip = kw["tokens_per_sec_per_chip"]
        self.memory_bytes = kw["memory_bytes"]
        self.detail = kw.get("detail", {})

    def as_dict(self):
        return {
            "compute_time": self.compute_time,
            "memory_time": self.memory_time,
            "comm_time": self.comm_time,
            "bubble_fraction": self.bubble_fraction,
            "step_time": self.step_time,
            "tokens_per_sec_per_chip": self.tokens_per_sec_per_chip,
            "memory_bytes": self.memory_bytes,
            "detail": self.detail,
        }

    def __repr__(self):
        return (f"CostEstimate(step={self.step_time * 1e3:.2f}ms, "
                f"tok/s/chip={self.tokens_per_sec_per_chip:.0f}, "
                f"mem={self.memory_bytes / 2**30:.2f}GiB)")


def estimate_cost(model: ModelDesc, par: ParallelConfig,
                  hw: HardwareProfile):
    """One optimizer step's estimated wall time and per-device memory."""
    m, p = model, par
    n_devices_model = p.mp * p.pp * p.sep
    tokens_per_micro = p.micro_batch_size * m.seq
    tokens_per_step_dev = tokens_per_micro * p.n_micro

    # ---- compute: fwd+bwd matmul FLOPs on this device's param shard -------
    flops_per_token = 6 * m.n_params + 12 * m.layers * m.hidden * m.seq
    flops_dev = flops_per_token * tokens_per_step_dev / n_devices_model
    if p.recompute:
        flops_dev *= 4.0 / 3.0      # fwd replayed inside bwd
    compute_time = flops_dev / (hw.peak_flops * hw.mfu_ceiling)

    # ---- HBM traffic: weights streamed per micro-batch + activations ------
    param_bytes_dev = m.n_params * m.dtype_bytes / n_devices_model
    if p.sharding_stage >= 3:
        param_bytes_dev /= p.dp
    act_bytes_micro = (4 * m.layers * m.hidden * tokens_per_micro
                       * m.dtype_bytes) / n_devices_model
    hbm_bytes = (3 * param_bytes_dev * p.n_micro          # fwd+bwd+grad
                 + 2 * act_bytes_micro * p.n_micro)
    memory_time = hbm_bytes / hw.hbm_bw

    # ---- collectives ------------------------------------------------------
    comm = {}
    grad_bytes = m.n_params * m.dtype_bytes / n_devices_model
    if p.dp > 1:
        ring = ((p.dp - 1) / p.dp if p.sharding_stage >= 2
                else 2 * (p.dp - 1) / p.dp)
        comm["dp_grad"] = ring * grad_bytes / hw.ici_bw
    if p.sharding_stage >= 3 and p.dp > 1:
        # parameter allgather fwd+bwd
        comm["zero3_gather"] = (2 * (p.dp - 1) / p.dp
                                * grad_bytes / hw.ici_bw)
    if p.mp > 1:
        act_full = (m.hidden * tokens_per_micro * m.dtype_bytes)
        vol = 4 * m.layers / p.pp * act_full * 2 * (p.mp - 1) / p.mp
        comm["mp_allreduce"] = vol * p.n_micro / hw.ici_bw
    if p.pp > 1:
        boundary = m.hidden * tokens_per_micro * m.dtype_bytes
        comm["pp_p2p"] = 2 * boundary * p.n_micro / hw.ici_bw
    if p.sep > 1:
        kv = 2 * m.hidden * tokens_per_micro * m.dtype_bytes
        comm["sep_ring"] = (m.layers / p.pp) * kv * (p.sep - 1) \
            * p.n_micro / hw.ici_bw
    comm_time = sum(comm.values())

    # ---- pipeline bubble (1F1B): (pp-1)/(m + pp - 1) idle fraction --------
    bubble = (p.pp - 1) / (p.n_micro + p.pp - 1) if p.pp > 1 else 0.0

    busy = max(compute_time, memory_time) + comm_time
    step_time = busy / (1.0 - bubble) if bubble < 1 else float("inf")

    # ---- per-device memory (same accounting the tuner pruned with) --------
    master_opt = m.n_params * 12 / n_devices_model
    if p.sharding_stage >= 1 and p.dp > 1:
        master_opt /= p.dp
    pbytes = m.n_params * m.dtype_bytes / n_devices_model
    if p.sharding_stage >= 3 and p.dp > 1:
        pbytes /= p.dp
    # stashed activations: per-layer remat keeps only the layer-boundary
    # tensor (~1 of the 4 per-layer activations in act_bytes_micro)
    act_live = act_bytes_micro / 4 if p.recompute else act_bytes_micro
    memory_bytes = pbytes + master_opt + act_live

    tokens_total = tokens_per_step_dev * p.dp
    n_chips = p.dp * n_devices_model
    tok_per_chip = tokens_total / step_time / n_chips if step_time else 0.0

    return CostEstimate(
        compute_time=compute_time, memory_time=memory_time,
        comm_time=comm_time, bubble_fraction=bubble, step_time=step_time,
        tokens_per_sec_per_chip=tok_per_chip, memory_bytes=memory_bytes,
        detail={"comm": comm, "flops_dev": flops_dev,
                "hbm_bytes": hbm_bytes})


def rank_candidates(cands, model: ModelDesc, hw: HardwareProfile,
                    global_batch=None, hbm_bytes=None, keep_within=3.0):
    """Order tuner candidates by estimated step time; drop memory overflows
    and anything slower than keep_within x the best estimate. Returns
    [(candidate, CostEstimate)] best-first — the pre-trial pruning the
    reference's tuner does with its cost model."""
    scored = []
    for cand in cands:
        par = ParallelConfig.from_candidate(cand, global_batch=global_batch)
        est = estimate_cost(model, par, hw)
        if hbm_bytes is not None and est.memory_bytes > hbm_bytes:
            continue
        scored.append((cand, est))
    scored.sort(key=lambda ce: ce[1].step_time)
    if scored and keep_within is not None:
        best = scored[0][1].step_time
        scored = [ce for ce in scored if ce[1].step_time <= keep_within * best]
    return scored
