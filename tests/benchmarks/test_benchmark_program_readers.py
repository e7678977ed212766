"""The readers of what the program records itself (ISSUE 29): a share of a
labelled counter, a quantile of a histogram, and the three flash kernels
picked by their names."""
import json
import os

import pytest

import _bench_util as U

xtrace = U.load("", "xtrace")

PHASE_NS = "paddle_tpu_serving_step_phase_ns_total"


def _snapshot(**metrics):
    return {"provenance": {}, "metrics": {
        name: {"type": "counter", "help": "", "labelnames": [], "values": values}
        for name, values in metrics.items()}}


def _phase_snapshot():
    return _snapshot(**{
        PHASE_NS: {"phase=schedule,kind=mixed": 30.0, "phase=dispatch,kind=mixed": 10.0,
                   "phase=wait,kind=mixed": 700.0, "phase=route,kind=mixed": 60.0,
                   "phase=schedule,kind=burst": 5.0, "phase=dispatch,kind=burst": 5.0,
                   "phase=wait,kind=burst": 180.0, "phase=route,kind=burst": 10.0},
        "paddle_tpu_serving_steps_total": {"kind=mixed": 2.0, "kind=burst": 1.0}})


def _metric(name):
    with open(os.path.join(U.BENCH, "metrics", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("metric,expected", [
    ("step_host_share.sat", 100.0 * 120.0 / 1000.0),
    ("mixed_step_time_share.sat", 100.0 * 800.0 / 1000.0),
])
def test_counter_share_sums_the_series_by_one_label(metric, expected):
    reader = U.load("readers", "counter_share")
    spec = _metric(metric)
    assert spec["reader"] == "counter_share" and "window's opening" in spec["why"]
    value, note = reader.read({}, spec["params"],
                              {"monitor_snapshot": _phase_snapshot()})
    assert value == pytest.approx(expected)
    assert note["denominator"] == 1000.0
    if metric == "step_host_share.sat":
        assert note["paddle_tpu_serving_steps_total"] == {"kind=mixed": 2.0,
                                                          "kind=burst": 1.0}


def test_counter_share_returns_nothing_without_the_counter_or_a_denominator():
    reader = U.load("readers", "counter_share")
    params = _metric("step_host_share.sat")["params"]
    assert reader.read({}, params, {"monitor_snapshot": _snapshot()}) is None
    zero = _snapshot(**{PHASE_NS: {"phase=wait,kind=mixed": 0.0}})
    assert reader.read({}, params, {"monitor_snapshot": zero}) is None
    # a program that has no such counter (the parent of this PR): its own
    # export, read through the program, gives nothing and does not raise
    from paddle_tpu import monitor

    monitor.reset()
    assert reader.read({}, dict(params, counter="paddle_tpu_serving_no_such_total"),
                       {}) is None


def _hist(buckets, count):
    return {"provenance": {}, "metrics": {"paddle_tpu_serving_token_gap_ns": {
        "type": "histogram", "help": "", "labelnames": [], "values": {"": {
            "count": count, "sum": 0.0, "buckets": buckets,
            "p50": None, "p90": None, "p99": None}}}}}


def test_histogram_quantile_interpolates_inside_its_bucket():
    reader = U.load("readers", "histogram_quantile")
    spec = _metric("token_gap_p90_ms.sat")
    assert spec["reader"] == "histogram_quantile" and spec["params"]["q"] == 0.9
    # 100 gaps: 48 zeros, 20 in (200, 250] ms, 32 in (400, 500] ms
    buckets = [[0, 48], [200e6, 48], [250e6, 68], [400e6, 68], [500e6, 100],
               ["+Inf", 100]]
    value, note = reader.read({}, spec["params"],
                              {"monitor_snapshot": _hist(buckets, 100)})
    # rank 90 lies 22 of 32 observations into (400, 500]
    assert value == pytest.approx(400.0 + 100.0 * 22 / 32)
    assert note["count"] == 100
    assert note["first_bucket"] == {"le": 0, "share_pct": 48.0}
    # rank 50 lies 2 of 20 observations into (200, 250]
    assert note["quantiles"]["0.5"] == pytest.approx(200.0 + 50.0 * 2 / 20)
    assert reader.quantile(buckets, 0.3) == 0.0          # in the zero bucket
    assert reader.quantile([[0, 0], [10.0, 4], ["+Inf", 8]], 0.9) == 10.0


def test_histogram_quantile_returns_nothing_without_observations():
    reader = U.load("readers", "histogram_quantile")
    params = _metric("token_gap_p90_ms.sat")["params"]
    empty = _hist([[0, 0], ["+Inf", 0]], 0)
    assert reader.read({}, params, {"monitor_snapshot": empty}) is None
    assert reader.read({}, params, {"monitor_snapshot": _snapshot()}) is None


def test_the_token_gap_grid_keeps_the_quantile_within_a_tenth():
    """The reader's resolution is the program's grid: a quantile anywhere
    between 10 ms and 2 s is off by less than one bucket, under 10%."""
    from paddle_tpu.monitor import catalog

    reader = U.load("readers", "histogram_quantile")
    grid = list(catalog.TOKEN_GAP_NS_BUCKETS)
    for gap in (12e6, 237e6, 461e6, 1.9e9):
        cum = [[le, 10 if le >= gap else 0] for le in grid] + [["+Inf", 10]]
        got = reader.quantile(cum, 0.9)
        assert abs(got - gap) / gap < 0.10


# event names as the chip trace of PR 29 showed them, operands cut short
CHIP_NAMES = {
    "fwd": '%jvp_flash_attention_fwd_.3 = (bf16[2,32,4096,128]{3,2,1,0:T(8,128)'
           '(2,1)}, f32[2,32,4096,1]{3,2,1,0:T(8,128)}) custom-call(bf16[2,32,'
           '4096,128]{3,2,1,0} %x), custom_call_target="tpu_custom_call", '
           'operand_layout_constraints={}',
    # the forward that recomputation runs again carries no jvp_ prefix
    "fwd_recomputed": '%flash_attention_fwd.3 = (bf16[2,32,4096,128]{3,2,1,0:T(8,128)'
                      '(2,1)}, f32[2,32,4096,1]{3,2,1,0:T(8,128)}) custom-call(bf16[2,'
                      '32,4096,128]{3,2,1,0} %x), custom_call_target="tpu_custom_call", '
                      'operand_layout_constraints={}',
    "dq": '%flash_attention_dq.3 = bf16[2,32,4096,128]{3,2,1,0:'
          'T(8,128)(2,1)} custom-call(bf16[2,32,4096,128]{3,2,1,0} %x), '
          'custom_call_target="tpu_custom_call", operand_layout_constraints={}',
    "dkv": '%flash_attention_dkv.3 = (bf16[2,32,4096,128]{3,2,1,0'
           ':T(8,128)(2,1)}, bf16[2,32,4096,128]{3,2,1,0:T(8,128)(2,1)}) '
           'custom-call(bf16[2,32,4096,128]{3,2,1,0} %x), custom_call_target='
           '"tpu_custom_call", operand_layout_constraints={}',
    # a consumer that only mentions a kernel among its operands is no kernel
    "none": '%multiply_bitcast_fusion.2 = bf16[2,4096,32,128]{3,1,2,0:T(8,128)'
            '(2,1)S(1)} fusion(bf16[2,32,4096,128]{3,2,1,0:T(8,128)(2,1)} '
            '%flash_attention_dq.3, bf16[4096,128]{1,0:T(8,128)(2,1)S(1)} %y), '
            'kind=kLoop, calls=%fused_computation.9',
}


@pytest.mark.parametrize("kernel", ["fwd", "dq", "dkv"])
def test_each_flash_roofline_rule_picks_its_kernel_by_name(kernel):
    spec = _metric(f"flash_{kernel}_roofline")
    assert spec["reader"] == "kernel_roofline"
    assert list(spec["params"]["calls"]) == [kernel]
    dev = {0: [(n, 0.0, 1.0, {}) for n in CHIP_NAMES.values()]}
    expected = [n for k, n in CHIP_NAMES.items() if k.split("_")[0] == kernel]
    picked = xtrace.matching(dev, spec["params"]["calls"][kernel])
    assert [e[0] for e in picked] == expected
    # the rule by results (flash_attention_roofline) still tells them apart
    old = _metric("flash_attention_roofline")["params"]["calls"][kernel]
    assert [e[0] for e in xtrace.matching(dev, old)] == expected
