"""The comparison that decides ``correct``: the numbers compared, each beside
its limit. Pure functions of plain dicts and lists, so the tests can drive
them with hand-made readings."""
from __future__ import annotations

import statistics


def worst_leaf_gap(program, reference, keep=None):
    """Worst leaf's gap between two norms (not the norm of a difference),
    against the reference's norm of that leaf or of the median leaf, whichever
    is larger. ``keep`` limits the leaves that count. Returns (gap, leaf)."""
    names = [n for n in reference if keep is None or n in keep]
    if not names:
        raise ValueError("no leaf to compare")
    median = statistics.median(reference[n] for n in names)
    worst, at = 0.0, names[0]
    for n in names:
        if n not in program:
            raise KeyError(f"the program has no leaf {n!r}")
        gap = abs(program[n] - reference[n]) / max(reference[n], median, 1e-30)
        if gap != gap:                  # a NaN is the worst there is
            gap = float("inf")
        if gap > worst:
            worst, at = gap, n
    return float(worst), at


def moving_leaves(reference_grad_norms, floor=1e-3):
    """Leaves whose reference gradient is at least ``floor`` of the median
    leaf's: the others (a gradient that is nought to rounding) move by
    round-off alone and are left out of the change comparison."""
    median = statistics.median(reference_grad_norms.values())
    return {n for n, g in reference_grad_norms.items() if g >= floor * median}


def train_numbers(program, reference):
    """``program`` and ``reference``: {"losses": [..], "grad_norms": {leaf: x},
    "change_norms": {leaf: x}} over the same first steps."""
    loss_gap = max(abs(a - b) / max(abs(b), 1e-30)
                   for a, b in zip(program["losses"], reference["losses"]))
    if loss_gap != loss_gap or \
            len(program["losses"]) != len(reference["losses"]):
        loss_gap = float("inf")
    grad_gap, grad_leaf = worst_leaf_gap(program["grad_norms"],
                                         reference["grad_norms"])
    keep = moving_leaves(reference["grad_norms"])
    change_gap, change_leaf = worst_leaf_gap(program["change_norms"],
                                             reference["change_norms"], keep)
    # loss_gap is read, not compared: neither the control nor a fault reads
    # above what sound runs read (the program returns its loss in bfloat16)
    return ({"grad_norm_gap": grad_gap, "change_norm_gap": change_gap},
            {"loss_gap": float(loss_gap),
             "grad_leaf": grad_leaf, "change_leaf": change_leaf,
             "left_out_of_change": sorted(set(reference["grad_norms"]) - keep)})


def serve_numbers(gaps, valid, short_answers):
    """``gaps`` (N, R) how far each served token's logit lies below the
    reference's best at its position, ``valid`` (N, R) which entries are served
    tokens; ``short_answers`` how many sampled answers came back with another
    number of tokens than was asked for."""
    widest = 0.0
    for row, mask in zip(gaps, valid):
        for g, m in zip(row, mask):
            g = float("inf") if g != g else float(g)
            if m and g > widest:
                widest = g
    return {"token_logit_gap": widest, "short_answers": float(short_answers)}


def judge(numbers, limits):
    """``{name: {"value", "limit", "ok"}}`` and whether all are within limits.
    A number without a limit is an error: every number compared has one."""
    out, ok = {}, True
    for name, value in numbers.items():
        if name not in limits:
            raise KeyError(f"no limit for the compared number {name!r}")
        good = bool(value <= limits[name])
        out[name] = {"value": value, "limit": limits[name], "ok": good}
        ok = ok and good
    return out, ok
