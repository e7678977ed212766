"""The increase of one of the program's monitor counters over the window, per
second of the window. ``params``: ``{"counter": <name>}``; the driver reads the
counter at the window's opening and at its close, in a traced run."""


def read(raw, params, env):
    counted = raw.get("counters", {}).get(params["counter"])
    if counted is None:
        return None
    return counted / raw["window_s"]
