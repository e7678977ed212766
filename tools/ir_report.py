#!/usr/bin/env python
"""graftir CLI that does NOT import jax eagerly.

``python -m paddle_tpu.analysis.jaxpr`` initializes paddle_tpu (and the
jax backend) before its own main() can provision the 8-device virtual
CPU mesh the flagship mesh program needs, so it re-execs itself once to
fix the environment. This shim avoids that dance — and keeps ``--help``
/ usage errors instant in any venv — by parsing arguments FIRST, then
setting ``XLA_FLAGS``/``JAX_PLATFORMS`` (analysis is trace-only: always
the CPU backend, never the chip), and only then importing the analysis
package.

Default view: per-program findings plus the HBM estimate table (the
module CLI's ``--hbm``); every module-CLI flag passes through, and exit
codes are identical.
"""
from __future__ import annotations

import os
import sys


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    # fast paths that must not pay a framework import
    if "--help" in argv or "-h" in argv:
        print(__doc__.strip())
        print("\nFlags pass through to `python -m paddle_tpu.analysis."
              "jaxpr` (--json, --programs, --passes, --baseline, "
              "--no-baseline, --update-baseline, --checks-json, "
              "--optimize, --list-passes, --list-programs). "
              "--optimize prints the before/after GI003 bracket and "
              "the applied-rewrite table of the graftopt transform.")
        return 0

    # the env half of programs.ensure_virtual_devices (the canonical
    # copy) — inlined because this shim must not import ANYTHING before
    # the flags are set
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from paddle_tpu.analysis import jaxpr as graftir

    if not ({"--json", "--checks-json", "--update-baseline",
             "--list-passes", "--list-programs", "--hbm",
             "--optimize"} & set(argv)):
        argv.append("--hbm")    # the report view this shim exists for
    return graftir.main(argv)


if __name__ == "__main__":
    sys.exit(main())
