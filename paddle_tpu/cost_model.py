"""paddle.cost_model — program cost estimation API.

Reference analog: python/paddle/cost_model/cost_model.py:33 class CostModel
(build_program demo, profile_measure = run the program under the profiler and
collect per-op times, static_cost_data = load the shipped per-op cost table).

TPU-first form: the analytic roofline estimator
(distributed/auto_parallel/cost_model.py — FLOPs, bytes, collective volume
over a mesh/parallel config) plays the static-table role, and
profile-measuring a program is one timed XLA execution rather than a per-op
kernel profile (XLA fuses across op boundaries, so per-op times are not the
unit of cost on TPU; the estimator works at the model-shape level instead).
"""
from __future__ import annotations

import time

from .distributed.auto_parallel.cost_model import (  # noqa: F401
    CostEstimate, HardwareProfile, ModelDesc, ParallelConfig, estimate_cost)

__all__ = ["CostModel", "HardwareProfile", "ModelDesc", "ParallelConfig",
           "CostEstimate", "estimate_cost"]


class CostModel:
    """reference cost_model.py:33 — estimate or measure program cost."""

    def static_cost_data(self, model: ModelDesc = None,
                         parallel: ParallelConfig = None,
                         hardware: HardwareProfile = None):
        """Analytic cost estimate (the static-table equivalent): returns the
        CostEstimate (step time, FLOPs, bytes, collective volume) for the
        given model/parallel/hardware description."""
        if model is None:
            # a 542M-parameter decoder at seq 2048 as the default subject
            model = ModelDesc(n_params=542_148_608, hidden=2048, layers=8,
                              seq=2048)
        parallel = parallel or ParallelConfig()
        hardware = hardware or HardwareProfile.named("tpu v5e")
        return estimate_cost(model, parallel, hardware)

    def profile_measure(self, program=None, fn=None, args=(), iters=3,
                        device=None, feed=None, fetch_list=None):
        """Measure a compiled program/callable: median wall time per run.
        `program` may be a paddle.static.Program (replayed via Executor with
        the given ``feed``/``fetch_list``) or `fn` a callable; returns
        seconds per iteration."""
        import numpy as np

        if program is not None:
            from .static import Executor

            exe = Executor(device)

            def fn():  # noqa: A001 - deliberate rebinding
                return exe.run(program, feed=feed or {},
                               fetch_list=fetch_list or [])

        if fn is None:
            raise ValueError("pass a static Program or a callable")
        fn()  # warm / compile
        times = []
        for _ in range(max(1, iters)):
            t0 = time.perf_counter()
            fn()
            times.append(time.perf_counter() - t0)
        return float(np.median(times))
