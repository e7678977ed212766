"""Helpers of the benchmark's own tests: paths, module loading by file, and a
rehearsal of one cell either in a child process (as the driver runs it) or in
this process with a fault planted under the timed path."""
import importlib.util
import io
import json
import math
import os
import subprocess
import sys
from contextlib import redirect_stderr, redirect_stdout

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")
for _p in (ROOT, BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def load(kind, name):
    """``benchmarks/<kind>/<name>.py`` (or ``benchmarks/<name>.py`` when
    ``kind`` is empty) as a module."""
    path = os.path.join(BENCH, kind, name + ".py") if kind \
        else os.path.join(BENCH, name + ".py")
    spec = importlib.util.spec_from_file_location(f"bt_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark_json(root=ROOT):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def nothing_finished(result):
    """Whether a serve cell's window closed before any request had finished:
    the gap then reads ``inf`` and the comparison says nothing about the
    timed path, sound or broken."""
    gap = result["compared"].get("token_logit_gap")
    return gap is not None and not math.isfinite(gap["value"])


def run_cell(workload, seed, seconds, trace=0, rehearse=True, root=ROOT,
             extra_env=None, longer=(1, 4)):
    """One run as the driver makes it; returns (returncode, result or None,
    stdout, stderr). Where the machine's load starved the window (no request
    finished inside it, or it closed before its traced slice began: a step of
    a CPU rehearsal can take a second beside other busy processes), the run is
    made again with the window ``longer`` times as long."""
    bench = benchmark_json(root)
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    env.pop("XLA_FLAGS", None)
    env.update(extra_env or {})
    for factor in longer:
        cmd = [sys.executable if bench["command"][0].startswith("python")
               else bench["command"][0], *bench["command"][1:],
               "--workload", workload, "--seed", str(seed),
               "--seconds", str(seconds * factor), "--trace", str(trace)]
        if rehearse:
            cmd.append("--rehearse")
        proc = subprocess.run(cmd, cwd=root, env=env, capture_output=True,
                              text=True, timeout=900)
        lines = proc.stdout.strip().splitlines()
        result = json.loads(lines[-1]) if proc.returncode == 0 and lines else None
        starved = nothing_finished(result) if result is not None \
            else "before its traced slice began" in proc.stderr
        if not starved:
            break
    return proc.returncode, result, proc.stdout, proc.stderr


def run_cell_with_fault(workload, seed, seconds, fault, longer=(1, 4, 16)):
    """The rest of a run in this process, past the look for a chip, with
    ``fault(handle_or_engine)`` planted once the timed path is built.

    A serve cell's comparison needs requests to FINISH inside the window, and
    how many steps fit into ``seconds`` of the wall clock is for the machine's
    load to say (beside other busy JAX processes a step of the Olmo cell's CPU
    rehearsal takes a hundred times what it takes alone). So where nothing
    finished the run is made again, the fault planted anew, with the window
    ``longer`` times as long: what is returned has read finished requests, or
    the longest window has been tried."""
    run = load("", "run")
    for factor in longer:
        out, err = io.StringIO(), io.StringIO()
        with redirect_stdout(out), redirect_stderr(err):
            rc = run.main(["--workload", workload, "--seed", str(seed),
                           "--seconds", str(seconds * factor), "--trace", "0",
                           "--rehearse"], fault=fault)
        assert rc == 0, err.getvalue()[-2000:]
        result = json.loads(out.getvalue().strip().splitlines()[-1])
        if not nothing_finished(result):
            break
    return result, err.getvalue()
