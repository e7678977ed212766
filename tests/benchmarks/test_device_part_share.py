"""The reader ``device_part_share``: self times of nested device events, the
join of a device event's text with the program's map of instructions to
device parts, and a traced rehearsal that prints every part metric and the
share of lanes used."""
import json

import pytest

import _bench_util as U

R = U.load("readers", "device_part_share")
BENCH = U.benchmark_json()
PART_METRICS = {m["name"]: m for m in BENCH["per_layer"]
                if m["name"].split(".")[0].endswith("_device_share")}


def _event(name, start, dur, **stats):
    return (name, float(start), float(dur), stats)


def _union(events):
    spans = sorted((st, st + du) for _, st, du, _ in events)
    total, end = 0.0, float("-inf")
    for st, en in spans:
        if en > end:
            total += en - max(st, end)
            end = en
    return total


# a while around its body's operations, two of which overlap, beside a fusion
# outside it and a gap
NESTED = [
    _event("%while.5 = (s32[], bf16[16,64]{1,0}) while(...)", 100, 1000),
    _event("%fusion.1 = bf16[16,64]{1,0:T(8,128)(2,1)} fusion(...)", 120, 300),
    _event("%fusion.2 = f32[16]{0} fusion(...)", 380, 200),
    _event("%paged_attention.3 = bf16[16,4,8]{2,1,0} custom-call(...)", 700,
           250),
    _event("%iota_reduce_fusion.1 = (bf16[16], u32[16]) fusion(...)", 1200,
           90),
]


def test_self_times_sum_to_the_busy_union_and_a_while_keeps_its_gaps():
    own = R.self_times(NESTED)
    assert abs(sum(own) - _union(NESTED)) <= 1e-9 * _union(NESTED)
    # the while keeps 100-120, 580-700 and 950-1100; fusion.1 gives way to
    # fusion.2, which started inside it, from 380 on
    assert own == [20 + 120 + 150, 260, 200, 250, 90]


MAP = {"jit_serving_mixed_step:aaa": {
           "while.5": ("(s32[],bf16[16,64])", None),
           "fusion.1": ("bf16[16,64]", "mlp"),
           "fusion.2": ("f32[16]", "head"),
           "paged_attention.3": ("bf16[16,4,8]", "paged_attention"),
           "iota_reduce_fusion.1": ("(bf16[16],u32[16])", "sample")},
       "jit_serving_decode_burst:bbb": {
           "fusion.2": ("f32[16]", "sample")}}


def _read(params, events=NESTED, maps=MAP):
    env = {"device_ops": {0: events}, "device_parts": maps}
    return R.read({}, params, env)


def test_events_join_the_map_by_name_and_shape():
    value, note = _read({"parts": ["sample"]})
    busy = _union(NESTED)
    assert note["busy_s"] == pytest.approx(busy * 1e-9)
    assert value == pytest.approx(100.0 * 90 / busy)
    assert note["parts"] == pytest.approx(
        {"mlp": 260e-9, "paged_attention": 250e-9, "sample": 90e-9})
    # fusion.2 is head in one program and sample in the other: ambiguous,
    # so unnamed; the while's own time is under no part
    assert note["ambiguous_s"] == pytest.approx(200e-9)
    assert note["no_part_s"] == pytest.approx(290e-9)
    assert note["unmatched_s"] == 0.0
    unnamed, _ = _read({"unnamed": True})
    assert unnamed == pytest.approx(100.0 * 490 / busy)
    named = sum(_read({"parts": [p]})[0] for p in note["parts"])
    assert named + unnamed == pytest.approx(100.0)


def test_an_event_the_map_does_not_know_is_unmatched():
    events = NESTED + [_event("%bench_rest.1 = s32[] add(...)", 2000, 10)]
    _, note = _read({"parts": ["mlp"]}, events)
    assert note["unmatched_s"] == pytest.approx(10e-9)


def test_a_rehearsals_host_events_join_by_their_stats():
    events = [_event("fusion.1", 0, 40, hlo_op="fusion.1",
                     hlo_module="jit_serving_decode_burst"),
              _event("fusion.2", 50, 60, hlo_op="fusion.2",
                     hlo_module="jit_serving_decode_burst")]
    value, note = _read({"parts": ["sample"]}, events)
    assert value == pytest.approx(60.0)
    assert note["unmatched_s"] == pytest.approx(40e-9)


@pytest.mark.parametrize("maps,events", [({}, NESTED), (MAP, [])])
def test_nothing_to_read_returns_nothing(maps, events):
    env = {"device_ops": {0: events} if events else None,
           "device_parts": maps}
    assert R.read({}, {"parts": ["sample"]}, env) is None


def test_a_program_without_maps_returns_nothing():
    """The reader asks the program, as a parent that builds no map is asked:
    with nothing mapped, nothing is read and the metric is left out."""
    from paddle_tpu import monitor

    monitor.reset()
    env = {"device_ops": {0: NESTED}}
    assert R.read({}, {"parts": ["sample"]}, env) is None


def test_the_metrics_list_the_serve_cells_and_name_their_reader():
    serve = [w["name"] for w in BENCH["workloads"] if "serve" in w["name"]]
    assert set(PART_METRICS) == {
        "sample_device_share.sat", "head_device_share.sat",
        "attention_device_share.sat", "unnamed_device_share.sat",
        "recurrent_device_share.linear"}
    for name, m in PART_METRICS.items():
        spec = json.load(open(f"{U.BENCH}/metrics/{name}.json"))
        assert spec["reader"] == "device_part_share"
        assert (m["layer"], m["moves"], m["better"], m["source"]) == (
            "serving programs", "serve_tokens_per_s", "lower", "program_span")
        assert m["workloads"] == (
            ["olmo-hybrid-7b-serve-long-answers"]
            if name.endswith(".linear") else serve)


def test_a_traced_rehearsal_prints_every_part_metric_and_the_lanes_used():
    cell = "olmo-hybrid-7b-serve-long-answers"
    rc, res, out, err = U.run_cell(cell, 2 ** 31 + 4242, 4, trace=1)
    assert rc == 0, err[-3000:]
    got = res["metrics"]
    assert set(PART_METRICS) | {"lanes_used_share.sat"} <= set(got)
    notes = {k: v for line in out.splitlines()
             if line.startswith('{"metric_notes"')
             for n in json.loads(line)["metric_notes"] for k, v in n.items()}
    note = notes["unnamed_device_share.sat"]
    named = sum(note["parts"].values())
    assert named + note["no_part_s"] + note["ambiguous_s"] \
        + note["unmatched_s"] == pytest.approx(note["busy_s"])
    assert note["map"]["programs"] >= 3 and note["map"]["instructions"] > 0
    assert 0 < got["recurrent_device_share.linear"]["value"] <= 100
    assert 0 < got["lanes_used_share.sat"]["value"] <= 100
