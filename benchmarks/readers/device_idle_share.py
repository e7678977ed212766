"""1 - (union of the device operations' intervals) / (traced window)."""


def read(raw, params, env):
    if env.get("busy_s") is None:
        return None
    return 100.0 * (1.0 - env["busy_s"] / env["traced_window_s"])
