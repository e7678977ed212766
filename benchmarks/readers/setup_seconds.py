"""Process start to the window's start: import, weights, engine or step build,
warm-up, compilation."""


def read(raw, params, env):
    return raw["setup_s"]
