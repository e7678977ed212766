"""The ratio of two sums over one of the program's labelled monitor counters:
the series whose label ``label`` takes a value in ``numerator`` over those where
it takes one in ``denominator`` (``counter_share`` without the percent).

``params``: as ``counter_share``'s. Read from the program's own export
(``paddle_tpu.monitor.snapshot()``) after the run. No such counter, or a
denominator of zero: nothing returned.
"""
from readers import counter_share as _share


def read(raw, params, env):
    got = _share.read(raw, params, env)
    if got is None:
        return None
    value, note = got
    return value / 100.0, note
