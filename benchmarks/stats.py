"""Arithmetic from raw times and counts to end-to-end quantities. Times are
seconds on the host's monotonic clock, relative to the window's start."""
from __future__ import annotations


def train_tokens_per_s(steps_finished, tokens_per_step, window_s):
    """All tokens of all steps that finished inside the window over the
    window's seconds."""
    return steps_finished * tokens_per_step / window_s


def decode_share_inside(r, window_s):
    """The share of a finished request's decoding, from its first token to its
    finish, that lies inside the window [0, window_s]."""
    first, finish = r["first"], r["finish"]
    if first is None or finish is None:
        raise ValueError("a finished request with its first-token time is "
                         "needed")
    if finish <= first:
        return 1.0 if 0.0 < finish <= window_s else 0.0
    return max(0.0, min(finish, window_s) - max(first, 0.0)) / (finish - first)


def tokens_inside(r, window_s):
    """Output tokens of one finished request that fall inside the window, from
    the request's OWN times: its first token at ``first``, its other
    ``n_out - 1`` spread evenly from there to ``finish``. The engine hands
    tokens back only when a request finishes, so what a request had produced by
    an edge of the window is known no closer than that. A request wholly inside
    counts ``n_out``; one wholly outside counts nothing."""
    got = 1.0 if 0.0 < r["first"] <= window_s else 0.0
    return got + (r["n_out"] - 1) * decode_share_inside(r, window_s)


def serve_tokens_per_s(records, window_s):
    """Output tokens produced inside the window over the window's seconds. The
    window opens and closes mid-stream, so requests are in flight at both
    edges: every request is stepped to its end after the close, and then
    counts the share of its tokens that ``tokens_inside`` puts in the window.
    A request that never finished counts nothing (and is in ``failed``)."""
    return sum(tokens_inside(r, window_s) for r in records
               if r["finish"] is not None) / window_s


def finished_tokens_per_s(records, window_s):
    """All output tokens of the requests that finished inside the window, over
    the window's seconds: counted, nothing apportioned, and coarse (a request
    is some 110 tokens; some 30 finish in a window)."""
    return sum(r["n_out"] for r in records if r["finish"] is not None
               and 0.0 < r["finish"] <= window_s) / window_s


def lanes_in_use(calls):
    """Lanes in use in each step, from what the driver saw after each call of
    ``step()``: ``[(requests in a slot, requests queued, requests handed
    back)]``. The engine keeps one step in flight (PR 37): a call dispatches
    step N + 1 and hands back the requests that ended in step N, whose rows it
    released when it dispatched their last token. So the lanes of the step a
    call dispatched are the requests still in a slot after that call plus
    those that the NEXT call hands back; the two numbers of ONE call belong to
    different steps, and their sum reads ``max_batch + 1`` now and then. The
    last call's step has no next call here and is left out.

    ``num_active`` reads 1 also where NO request is in a slot and a step is in
    flight (every lane of it ended). Which of the two a 1 means follows from
    the call before: what was in a slot then, plus what left the queue, less
    what the dispatched step released (the next call's hand-back)."""
    lanes, before = [], None
    for (active, queued, _), (_, _, handed_back) in zip(calls, calls[1:]):
        if active == 1 and before is not None:
            held = before[0] + before[1] - queued - handed_back
            if held in (0, 1):
                active = held
        lanes.append(active + handed_back)
        before = (active, queued)
    return lanes


def occupancy(samples, max_batch):
    """Mean over the window's steps of lanes in use / lanes."""
    if not samples:
        raise ValueError("no step was sampled")
    return sum(samples) / len(samples) / max_batch
