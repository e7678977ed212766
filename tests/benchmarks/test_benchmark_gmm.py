"""The two metrics of the held experts' grouped Pallas kernels (ISSUE 35), on a
made-up trace and made-up counters: the kernels' roofline share picks
``%held_experts_gmm*`` events and is blind to ``%ragged-dot-none`` (the older
metric ``moe_experts_roofline.mix``, whose rule picked those over the SAME
least work, read nothing from PR 35 on and went in PR 39: a rule that matches
no event returns nothing), and the row tiles' fill is ``held`` over
``kernel_rows``, or nothing where the program has no such series."""
import json
import os

import pytest

import _bench_util as U

xtrace = U.load("", "xtrace")

PAIRS = "paddle_tpu_serving_expert_pairs_total"
H, WIDTH = 4096, 2048


def _metric(name):
    with open(os.path.join(U.BENCH, "metrics", name + ".json")) as f:
        return json.load(f)


def _cfg():
    with open(os.path.join(U.BENCH, "configs", "mimo-v2-flash.json")) as f:
        return json.load(f)


@pytest.fixture
def slice_env(tmp_path):
    """Two mixed steps of ONE expert layer: an up and a down kernel each (0.8
    + 0.4 ms), a fusion that only names a kernel among its operands, and one
    leftover ragged-dot (0.8 ms) as a parent's trace would hold."""
    import jax

    with open(os.path.join(U.FIXTURES, "experts_slice.textproto")) as f:
        raw = jax.profiler.ProfileData.text_proto_to_serialized_xspace(f.read())
    path = tmp_path / "experts_slice.xplane.pb"
    path.write_bytes(raw)
    dev = xtrace.device_ops(xtrace.load(str(path)))
    return {"device_ops": dev, "config": _cfg(), "module": U.load,
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


def _counted(**series):
    return {"slice_counters": {PAIRS: {"where=" + k: float(v)
                                       for k, v in series.items()}},
            "slice_seconds": 0.004}


def test_the_new_roofline_reads_the_kernels_events_by_name(slice_env):
    reader = U.load("readers", "kernel_roofline_counted")
    spec = _metric("moe_experts_gmm_roofline.mix")
    assert spec["reader"] == "kernel_roofline_counted"
    assert spec["params"]["costs"] == {"module": "mimo_v2_flash",
                                       "function": "held_experts_costs"}
    # 2 forward passes x 16 held experts hit, 10 pairs each, 512 tile rows
    raw = _counted(held=320, experts_hit=32, routed=5120, kernel_rows=512)
    value, note = reader.read(raw, spec["params"], slice_env)
    assert note["events"] == 4 and note["seconds"] == pytest.approx(2.4e-3)
    nbytes = (32 * 3 * H * WIDTH + 320 * 2 * H) * 2
    assert note["bytes"] == nbytes and note["bound"] == "memory"
    assert note["flops"] == 320 * 3 * 2 * H * WIDTH
    assert value == pytest.approx(100.0 * nbytes / 819e9 / 2.4e-3)
    assert 80 < value < 85
    assert note[PAIRS]["where=kernel_rows"] == 512.0
    # another rule over the same costs reads its own events against the same
    # work (the one leftover ragged-dot), and a rule that matches no event
    # returns nothing: never 0 for a share of a roofline
    other = dict(spec["params"], calls={
        "ragged_dot": {"name_regex": "^%ragged-dot-none"}})
    old, old_note = reader.read(raw, other, slice_env)
    assert old_note["events"] == 1 and old_note["bytes"] == nbytes
    assert old == pytest.approx(value * 2.4 / 0.8)
    nothing = dict(spec["params"], calls={
        "gone": {"name_regex": "^%no_such_kernel"}})
    assert reader.read(raw, nothing, slice_env) is None
    assert not os.path.exists(os.path.join(
        U.BENCH, "metrics", "moe_experts_roofline.mix.json"))


def test_the_new_roofline_returns_nothing_where_no_kernel_ran(slice_env):
    """A parent's trace (only ragged-dot events), an untraced run, nothing
    counted: nothing returned, nothing raised."""
    reader = U.load("readers", "kernel_roofline_counted")
    params = _metric("moe_experts_gmm_roofline.mix")["params"]
    raw = _counted(held=320, experts_hit=32)
    parent = {dev: [e for e in ops
                    if not e[0].startswith("%held_experts_gmm")]
              for dev, ops in slice_env["device_ops"].items()}
    assert xtrace.matching(parent, params["calls"]["held_experts_gmm"]) == []
    assert reader.read(raw, params, dict(slice_env, device_ops=parent)) is None
    assert reader.read(raw, params, dict(slice_env, device_ops=None)) is None
    assert reader.read(_counted(routed=5120), params, slice_env) is None
    assert reader.read({}, params, slice_env) is None


def _snapshot(**series):
    return {"provenance": {}, "metrics": {PAIRS: {
        "type": "counter", "help": "", "labelnames": ["where"],
        "values": {"where=" + k: float(v) for k, v in series.items()}}}}


def test_row_fill_is_held_pairs_over_the_tiles_rows():
    reader = U.load("readers", "counter_share")
    spec = _metric("moe_kernel_row_fill.mix")
    assert spec["reader"] == "counter_share"
    snap = _snapshot(held=1920, routed=30720, experts_hit=180,
                     expert_calls=192, kernel_rows=3072)
    value, note = reader.read({}, spec["params"], {"monitor_snapshot": snap})
    assert value == pytest.approx(62.5)
    assert note["numerator"] == 1920.0 and note["denominator"] == 3072.0
    assert note[PAIRS]["where=experts_hit"] == 180.0


def test_row_fill_returns_nothing_without_the_series():
    """A program that has no series ``where=kernel_rows`` (the parent of PR
    35), or one that never moved: a denominator of zero."""
    reader = U.load("readers", "counter_share")
    params = _metric("moe_kernel_row_fill.mix")["params"]
    absent = _snapshot(held=1920, routed=30720, experts_hit=180,
                       expert_calls=192)
    assert reader.read({}, params, {"monitor_snapshot": absent}) is None
    zero = _snapshot(held=1920, kernel_rows=0)
    assert reader.read({}, params, {"monitor_snapshot": zero}) is None
    assert reader.read({}, params, {"monitor_snapshot": {"metrics": {}}}) is None
