"""The whole step's share of the chip's bf16 peak: model FLOPs (the benchmark's
own count, ``benchmarks/flops/<module>.py``) per second of the window over the
published peak. ``params``: ``{"flops": <module>, "of": "train_steps" |
"requests_inside"}``. A request's prefill counts where its first token falls inside
the window, its decoding by the share of it that lies inside. Recomputation is
not counted, so this is a model FLOP/s utilization, not a hardware one."""
import stats


def read(raw, params, env):
    if env["peaks"] is None:
        return None                      # no published peak: a rehearsal
    flops = env["module"]("flops", params["flops"])
    cfg = env["config"]
    if params["of"] == "train_steps":
        if "steps" not in raw:
            return None
        done = (flops.train_flops_per_token(cfg, raw["sequence_length"])
                * raw["steps"] * raw["tokens_per_step"])
    elif params["of"] == "requests_inside":
        if "records" not in raw:
            return None
        done, w = 0.0, raw["window_s"]
        for r in raw["records"]:
            if r["finish"] is None:
                continue
            p = r["n_prompt"]
            prefill = flops.forward_flops(cfg, p, p * (p + 1) // 2)
            decode = flops.request_forward_flops(cfg, p, r["n_out"]) - prefill
            done += prefill * (0.0 < r["first"] <= w) \
                + decode * stats.decode_share_inside(r, w)
    else:
        raise ValueError(f"step_mfu: unknown 'of' {params['of']!r}")
    peak = env["peaks"]["bf16_flops_per_s"] * env["chips"]
    return 100.0 * done / raw["window_s"] / peak
