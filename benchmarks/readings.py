#!/usr/bin/env python3
"""The readings that a cell's limits are set from, many seeds in one process.

    python3 benchmarks/readings.py --workload <name> --seeds 1,2,3 \
        --control-seeds 1,2,3 [--seconds S] [--rehearse]

The program's model is constructed once (the host-side draw is the long part
of set-up) and reloaded with each seed's weights; every seed then goes through
the cell's own driver (``readings`` in its file): the timed path at the timed
sizes against the reference, and for ``--control-seeds`` the control (the
reference one precision down) and the planted faults in the program's place.
One JSON line per seed, then the largest program reading and the smallest
control or fault reading of each number: the lower and the upper reading that
a limit has to lie between. A benchmark run never calls this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import run as runner  # noqa: E402


def tool_context(workload, rehearse, seconds):
    """The cell's files, the environment, the look for a chip, and the context
    a driver's ``readings`` takes. Returns ``(cfg, driver, ctx)``, or None
    without a chip."""
    _, _, cfg, traffic, _ = runner.load_cell(workload, rehearse)
    runner.set_environment(rehearse)
    import jax

    if not rehearse and jax.devices()[0].platform != "tpu":
        print("no tpu (--rehearse runs tiny sizes on the CPU)", file=sys.stderr)
        return None
    ctx = {"config": cfg, "traffic": traffic, "seconds": seconds,
           **runner.cell_modules(cfg, traffic)}
    return cfg, runner._module("drivers", cfg["driver"]), ctx


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    got = tool_context(args.workload, args.rehearse, args.seconds)
    if got is None:
        return 1
    cfg, driver, ctx = got
    import jax

    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    model = ctx["builder"].construct(cfg)
    lower, upper = {}, {}
    for seed in seeds:
        got = driver.readings(ctx, model, seed, seed in controls)
        print(json.dumps({"seed": seed, **got}), flush=True)
        for name, v in got["program"].items():
            lower[name] = max(lower.get(name, 0.0), v)
        for who, numbers in got.items():
            if who.startswith(("control", "fault")):
                for name, v in numbers.items():
                    upper.setdefault(who, {})[name] = min(
                        upper.get(who, {}).get(name, float("inf")), v)
    print(json.dumps({"lower_readings": lower, "upper_readings": upper,
                      "device": jax.devices()[0].device_kind}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
