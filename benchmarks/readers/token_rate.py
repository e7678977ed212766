"""Tokens per second over all the work and all the time of the window.
``params``: ``{"of": "train_steps" | "requests_inside" | "requests_finished"}``:
all tokens of the steps that finished inside the window; the output tokens that
the requests produced inside it (each request's share by its own first-token
and finish times); all output tokens of the requests that finished inside it."""
import stats


def read(raw, params, env):
    if params["of"] == "train_steps":
        if "steps" not in raw:
            return None
        return stats.train_tokens_per_s(raw["steps"], raw["tokens_per_step"],
                                        raw["window_s"])
    if "records" not in raw:
        return None
    if params["of"] == "requests_inside":
        return stats.serve_tokens_per_s(raw["records"], raw["window_s"])
    if params["of"] == "requests_finished":
        return stats.finished_tokens_per_s(raw["records"], raw["window_s"])
    raise ValueError(f"token_rate: unknown 'of' {params['of']!r}")
