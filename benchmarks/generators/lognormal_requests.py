"""Serving requests from a seed: a backlog of log-normal prompt and output
lengths, every request due at t = 0.

Parameters (traffic file): ``prompt`` and ``output`` each
``{"median", "sigma", "min", "max"}``, ``max_total`` (prompt + output),
``shape_seed`` and ``backlog_requests``.

Every seed gets the SAME (prompt length, output length) pairs in the SAME
order, drawn once from ``shape_seed``: the schedule is one fixed trace, and the
run's seed draws only the token ids (and, in the driver, the weights). The
order is part of the work: while a prompt is being prefilled every lane decodes
one token a step instead of a burst of several, so another order of the same
sizes is another amount of work in the window (chip runs of PR 28 with the
order shuffled by the seed read 32 to 53 tokens/s of finished requests). Token
ids are uniform over the vocabulary: no two prompts share a block, so the
prefix cache finds nothing.
"""
from __future__ import annotations

import numpy as np


def _lengths(rng, spec, n):
    draw = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(draw), spec["min"], spec["max"]).astype(np.int64)


def shapes(traffic, n):
    """The fixed sequence: ``n`` (prompt_len, output_len) pairs."""
    rng = np.random.Generator(np.random.PCG64(int(traffic["shape_seed"])))
    prompt = _lengths(rng, traffic["prompt"], n)
    output = _lengths(rng, traffic["output"], n)
    output = np.minimum(output, int(traffic["max_total"]) - prompt)
    return prompt, np.maximum(output, 1)


def requests(seed, traffic, cfg):
    """``[{"prompt", "max_new"}]`` in the fixed order, all due at t = 0."""
    n = int(traffic["backlog_requests"])
    prompt, output = shapes(traffic, n)
    rng = np.random.Generator(np.random.PCG64([int(seed), 0]))
    vocab = int(cfg["vocab_size"])
    return [{"prompt": rng.integers(0, vocab, int(prompt[i]), dtype=np.int32),
             "max_new": int(output[i])} for i in range(n)]
