"""The yardstick's own arithmetic: generators, windows and their edges, the
FLOP counts against hand counts, the comparison, and the trace reduction."""
import json
import os

import numpy as np
import pytest

import _bench_util as U

stats = U.load("", "stats")
compare = U.load("", "compare")
xtrace = U.load("", "xtrace")
flops = U.load("flops", "llama")
batches = U.load("generators", "token_batches")
reqs = U.load("generators", "lognormal_requests")


def _cfg(name):
    with open(os.path.join(U.BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def _traffic(name):
    with open(os.path.join(U.BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


# -- generators ---------------------------------------------------------------
def test_token_batches_repeat_from_a_seed_and_differ_across_seeds():
    cfg, tr = _cfg("mistral-7b-v0.3"), _traffic("packed-4k-sgdm")
    a, b, c = (batches.batches(s, tr, cfg) for s in (2 ** 31 + 5, 2 ** 31 + 5, 6))
    for _ in range(3):
        (ia, la), (ib, lb), (ic, _) = next(a), next(b), next(c)
        assert ia.shape == (2, 4096) and ia.dtype == np.int32
        assert np.array_equal(ia, ib) and np.array_equal(la, lb)
        assert not np.array_equal(ia, ic)
        assert np.array_equal(ia[:, 1:], la[:, :-1])          # next-token labels
        assert not np.array_equal(ia[0], ia[1])               # rows all differ
        assert ia.min() >= 0 and ia.max() < cfg["vocab_size"]


@pytest.mark.parametrize("mix", ["chat-backlog"])
def test_requests_repeat_from_a_seed_and_every_seed_gets_the_same_sizes_in_order(mix):
    cfg, tr = _cfg("deepseek-llm-7b"), _traffic(mix)
    a = reqs.requests(4294967000, tr, cfg)
    b = reqs.requests(4294967000, tr, cfg)
    c = reqs.requests(17, tr, cfg)
    assert len(a) == len(b) == len(c) == tr["backlog_requests"]
    assert all(np.array_equal(x["prompt"], y["prompt"]) and
               x["max_new"] == y["max_new"]
               for x, y in zip(a, b))
    assert all(not np.array_equal(x["prompt"], y["prompt"]) for x, y in zip(a, c))
    # the same work in the same order: only the token ids differ
    assert [(len(r["prompt"]), r["max_new"]) for r in a] == \
        [(len(r["prompt"]), r["max_new"]) for r in c]
    for r in a:
        assert tr["prompt"]["min"] <= len(r["prompt"]) <= tr["prompt"]["max"]
        assert 1 <= r["max_new"] <= tr["output"]["max"]
        assert len(r["prompt"]) + r["max_new"] <= tr["max_total"]
    lens = [len(r["prompt"]) for r in a]
    assert 150 < np.median(lens) < 240 and len(set(lens)) > 50


# -- a window that opens and closes mid-stream ---------------------------------
def _rec(first, finish, n_out=11, n_prompt=100):
    return {"submit": -50.0, "first": first, "finish": finish, "n_out": n_out,
            "asked": n_out, "n_prompt": n_prompt}


@pytest.mark.parametrize("first,finish,tokens,finished", [
    (2.0, 4.0, 11.0, 11),        # wholly inside: all of it, counted
    (-1.0, 1.0, 5.0, 11),        # over the open edge: half of its 10 later tokens
    (9.0, 11.0, 1 + 5.0, 0),     # over the close: its first token and half the rest
    (-4.0, 16.0, 5.0, 0),        # spans the window: 10 tokens over 20 s, 10 s inside
    (-3.0, -1.0, 0.0, 0),        # ended before the window opened
    (10.5, 12.0, 0.0, 0),        # prefilled in the drain, after the close
    (10.0, 10.0, 1.0, 1),        # one token, at the close itself: inside
    (0.0, 0.0, 0.0, 0),          # one token, at the opening itself: before it
])
def test_a_request_counts_the_share_of_its_own_tokens_that_lies_inside(
        first, finish, tokens, finished):
    n = 1 if first == finish else 11
    r = _rec(first, finish, n_out=n)
    assert stats.tokens_inside(r, 10.0) == pytest.approx(tokens)
    assert stats.serve_tokens_per_s([r], 10.0) == pytest.approx(tokens / 10.0)
    assert stats.finished_tokens_per_s([r], 10.0) == pytest.approx(finished / 10.0)


def test_tokens_inside_never_reads_another_requests_times():
    slow, fast = _rec(-10.0, 10.0, n_out=21), _rec(4.0, 5.0, n_out=101)
    alone = stats.serve_tokens_per_s([slow], 10.0)
    assert alone == pytest.approx(1.0)                  # 20 tokens over 20 s
    assert stats.serve_tokens_per_s([slow, fast], 10.0) == \
        pytest.approx(alone + 10.1)
    # over all requests of a schedule the shares add up to what was produced
    recs = [_rec(float(t), t + 3.0) for t in range(-6, 14)]
    whole = sum(stats.tokens_inside(r, 10.0) for r in recs)
    split = sum(stats.tokens_inside(r, 4.0) for r in recs) + sum(
        stats.tokens_inside(dict(r, first=r["first"] - 4.0,
                                 finish=r["finish"] - 4.0), 6.0) for r in recs)
    assert whole == pytest.approx(split)


def test_a_stall_inside_the_window_moves_both_serve_rates():
    """One lane, a request of 21 tokens every 2 s from t = -3; a 3 s stall at
    t = 5 delays all that comes after it."""
    def lane(stall):
        out = []
        for k in range(-2, 8):
            first = 2.0 * k + 1.0
            shift = stall if first >= 5.0 else 0.0
            out.append(_rec(first + shift, first + 2.0 + shift, n_out=21))
        return out
    calm, stalled = lane(0.0), lane(3.0)
    # calm: [-1,1] gives 10, four whole ones 84, [9,11] its first and 10 more
    assert stats.finished_tokens_per_s(calm, 10.0) == pytest.approx(5 * 21 / 10)
    assert stats.serve_tokens_per_s(calm, 10.0) == pytest.approx(105 / 10)
    # stalled: [-1,1] [1,3] [3,5] as before, then [8,10] whole and [10,12]'s
    # first token alone
    assert stats.finished_tokens_per_s(stalled, 10.0) == pytest.approx(4 * 21 / 10)
    assert stats.serve_tokens_per_s(stalled, 10.0) == pytest.approx(74 / 10)


def test_an_unfinished_request_counts_nothing_and_has_no_share():
    lost = _rec(3.0, None)
    assert stats.serve_tokens_per_s([lost, _rec(1.0, 2.0)], 10.0) == \
        pytest.approx(1.1)
    with pytest.raises(ValueError):
        stats.decode_share_inside(lost, 10.0)


@pytest.mark.parametrize("of,rate", [("requests_inside", 1.7),
                                     ("requests_finished", 1.1)])
def test_token_rate_reader_reads_the_serve_records(of, rate):
    reader = U.load("readers", "token_rate")
    raw = {"records": [_rec(2.0, 4.0), _rec(9.0, 11.0)], "window_s": 10.0}
    assert reader.read(raw, {"of": of}, {}) == pytest.approx(rate)
    assert reader.read({"window_s": 10.0}, {"of": of}, {}) is None
    with pytest.raises(ValueError):
        reader.read(raw, {"of": "no_such"}, {})


def test_counter_rate_reads_a_program_counters_increase_over_the_window():
    reader = U.load("readers", "counter_rate")
    raw = {"window_s": 10.0, "counters": {"tokens_total": 820}}
    assert reader.read(raw, {"counter": "tokens_total"}, {}) == pytest.approx(82.0)
    assert reader.read(raw, {"counter": "another"}, {}) is None
    assert reader.read({"window_s": 10.0}, {"counter": "tokens_total"}, {}) is None


def test_serve_mfu_counts_prefill_where_the_first_token_falls_and_decode_by_share():
    reader = U.load("readers", "step_mfu")
    cfg = _cfg("deepseek-llm-7b")
    env = {"config": cfg, "module": U.load, "chips": 1,
           "peaks": {"bf16_flops_per_s": 197e12}}
    p, n = 100, 11
    prefill = flops.forward_flops(cfg, p, p * (p + 1) // 2)
    decode = flops.request_forward_flops(cfg, p, n) - prefill
    raw = {"window_s": 10.0, "records": [_rec(2.0, 4.0), _rec(-1.0, 1.0),
                                         _rec(9.0, 11.0), _rec(3.0, None)]}
    want = (prefill + decode) + 0.5 * decode + (prefill + 0.5 * decode)
    got = reader.read(raw, {"flops": "llama", "of": "requests_inside"}, env)
    assert got == pytest.approx(100.0 * want / 10.0 / 197e12)


def test_train_rate_counts_all_steps_over_the_whole_window():
    assert stats.train_tokens_per_s(40, 8192, 32.0) == pytest.approx(10240.0)
    assert stats.occupancy([16, 8, 16, 8], 16) == pytest.approx(0.75)
    # one step in flight: 4 lanes full in every step, a request ends in the
    # steps dispatched by calls 1 and 3 and is handed back one call later, its
    # row re-given at once from the queue; the two numbers of one call (in a
    # slot, handed back) sum to 5 twice
    calls = [(4, 9, 0), (3, 9, 0), (4, 8, 1), (3, 8, 0), (4, 7, 1)]
    assert [a + f for a, _, f in calls].count(5) == 2
    assert stats.lanes_in_use(calls) == [4, 4, 4, 4]
    assert stats.occupancy(stats.lanes_in_use(calls), 4) == 1.0
    # a step in which ALL four lanes end: num_active then reads 1 for "a step
    # is in flight" though no request is in a slot, and the queue's loss says so
    calls = [(4, 9, 0), (1, 9, 0), (4, 5, 4), (4, 5, 0)]
    assert stats.lanes_in_use(calls) == [4, 4, 4]
    # where one request does stay in its slot, 1 means one
    calls = [(4, 9, 0), (1, 9, 0), (4, 6, 3), (4, 6, 0)]
    assert stats.lanes_in_use(calls) == [4, 4, 4]


# -- FLOP counts against hand counts ------------------------------------------
def test_flop_counts_against_hand_counts():
    m, d = _cfg("mistral-7b-v0.3"), _cfg("deepseek-llm-7b")
    # Mistral: per layer q,o 4096x4096 each, k,v 4096x1024 each, MLP 3x4096x14336
    per_layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert per_layer == 218_103_808
    n_m = 3 * per_layer + 4096 * 32768
    assert flops.W.matmul_param_count(m) == n_m == 788_529_152
    per_token = 6 * n_m + 3 * (6 * 4096 * 4096)            # causal half square
    assert flops.train_flops_per_token(m, 4096) == per_token
    assert per_token * 8192 == pytest.approx(41.23e12, rel=1e-3)
    # DeepSeek: per layer 4 x 4096^2 + 3 x 4096 x 11008, head 4096 x 102400
    n_d = 12 * (4 * 4096 * 4096 + 3 * 4096 * 11008) + 4096 * 102400
    assert flops.W.matmul_param_count(d) == n_d
    # one request: prompt 3, output 2 feeds 4 tokens with contexts 1+2+3+4
    assert flops.request_forward_flops(d, 3, 2) == \
        2 * n_d * 4 + 4 * 10 * 4096 * 12
    fa = flops.flash_attention_costs(m, 2, 4096)
    one = 2 * 2 * 32 * 4096 * 4096 * 128 // 2
    assert fa["fwd"][0] == 2 * one and fa["dq"][0] == 3 * one \
        and fa["dkv"][0] == 4 * one
    assert fa["fwd"][1] == 2 * (2 * 4096 * 32 * 128 * 2) \
        + 2 * (2 * 4096 * 8 * 128 * 2) + 2 * 4096 * 32 * 4


# -- the comparison ------------------------------------------------------------
def test_worst_leaf_gap_is_a_gap_of_norms_against_leaf_or_median():
    ref = {"a": 1.0, "b": 2.0, "c": 1e-6}
    prog = {"a": 1.1, "b": 2.0, "c": 2e-6}
    gap, leaf = compare.worst_leaf_gap(prog, ref)
    assert leaf == "a" and gap == pytest.approx(0.1)      # c is held to the median
    assert compare.moving_leaves({"a": 1.0, "b": 2.0, "c": 1e-6}) == {"a", "b"}
    gap, _ = compare.worst_leaf_gap({"a": float("nan"), "b": 2.0, "c": 0.0}, ref)
    assert gap == float("inf")                 # a NaN is the worst there is


def test_an_unmoved_state_reads_one_and_judge_needs_every_limit():
    ref = {"losses": [10.0, 9.9, 9.8], "grad_norms": {"a": 1.0, "b": 1.0},
           "change_norms": {"a": 0.3, "b": 0.3}}
    prog = {"losses": [10.0, 10.0, 10.0], "grad_norms": {"a": 1.0, "b": 1.0},
            "change_norms": {"a": 0.0, "b": 0.0}}
    numbers, notes = compare.train_numbers(prog, ref)
    assert numbers["change_norm_gap"] == pytest.approx(1.0)
    assert numbers["grad_norm_gap"] == 0.0
    assert "loss_gap" not in numbers                 # read, not compared
    assert notes["loss_gap"] == pytest.approx(0.2 / 9.8)
    out, ok = compare.judge(numbers, {"grad_norm_gap": 0.01,
                                      "change_norm_gap": 0.1})
    assert not ok and out["change_norm_gap"]["ok"] is False
    with pytest.raises(KeyError):
        compare.judge(numbers, {"grad_norm_gap": 1.0})
    served = compare.serve_numbers([[0.0, 0.4], [0.2, 9.0]],
                                   [[True, True], [True, False]], 1)
    assert served == {"token_logit_gap": 0.4, "short_answers": 1.0}


# -- the trace reduction ---------------------------------------------------------
def _xplane(fixture, tmp_path):
    """A hand-written trace of ``fixtures/`` as the profiler would write it."""
    import jax

    with open(os.path.join(U.FIXTURES, fixture + ".textproto")) as f:
        raw = jax.profiler.ProfileData.text_proto_to_serialized_xspace(f.read())
    path = tmp_path / (fixture + ".xplane.pb")
    path.write_bytes(raw)
    return path


@pytest.fixture
def tpu_slice(tmp_path):
    return _xplane("tpu_slice", tmp_path)


def test_trace_reduction_on_a_tpu_shaped_trace(tpu_slice):
    planes = xtrace.load(str(tpu_slice))
    dev = xtrace.device_ops(planes)
    assert list(dev) == [0] and len(dev[0]) == 5          # XLA Ops line only
    assert xtrace.busy_seconds(dev) == pytest.approx(6500e-9)
    assert xtrace.top_ops(dev)[0] == ["fusion.1", pytest.approx(4000e-9)]
    assert planes["host"] == [("bench.make_batch", 4000.0, 3500.0)]
    assert xtrace.idle_gaps(dev, planes["host"]) == [
        ["bench.make_batch", pytest.approx(2500e-9)]]
    picked = xtrace.matching(dev, {"name_regex": r"^flash_fwd"})
    assert [e[2] for e in picked] == [1000.0, 1000.0]
    assert xtrace.matching(dev, {"name_regex": "fusion",
                                 "stats_regex": {"absent": "x"}}) == []
    reader = U.load("readers", "device_idle_share")
    assert reader.read({}, {}, {"busy_s": 1.0, "traced_window_s": 4.0}) == 75.0
    assert reader.read({}, {}, {"busy_s": None}) is None


def test_the_traced_window_is_the_devices_own_first_start_to_last_end(tpu_slice):
    """Busy and window on one clock: the window is each chip's first
    operation's start to its last one's end, the busy union lies inside it, and
    the idle share is the gaps between operations, never under nought. A
    HOST window shorter than the trace, which is what the profiler's edges
    gave while a step was in flight at both (PR 37), would have read a device
    busier than its window."""
    planes = xtrace.load(str(tpu_slice))
    dev = xtrace.device_ops(planes)
    # [0, 2] [2, 3] [2.5, 3.5] . . [6, 8] [8, 9] us: 6.5 busy in 9
    assert xtrace.window_seconds(dev) == pytest.approx(9000e-9)
    busy, window = xtrace.busy_seconds(dev), xtrace.window_seconds(dev)
    assert busy <= window
    gaps = sum(s for _, s in xtrace.idle_gaps(dev, planes["host"]))
    assert window - busy == pytest.approx(gaps) == pytest.approx(2500e-9)
    reader = U.load("readers", "device_idle_share")
    env = {"busy_s": busy, "traced_window_s": window}
    assert reader.read({}, {}, env) == pytest.approx(100.0 * 2.5 / 9.0)
    assert xtrace.programs(planes) == {"jit_step": 1}
    # the old formula over a host window of 6 us that the events overhang
    host_window = 6000e-9
    assert 1.0 - busy / host_window < 0 <= reader.read({}, {}, env)
    # two chips: each its own first start to last end, then the mean, as the
    # busy seconds are averaged; a chip whose operations abut idles nought
    two = {0: dev[0], 1: [("a", 100.0, 50.0, {}), ("b", 150.0, 250.0, {}),
                          ("c", 120.0, 30.0, {})]}
    assert xtrace.window_seconds(two) == pytest.approx((9000 + 300) / 2 * 1e-9)
    assert xtrace.busy_seconds(two) == pytest.approx((6500 + 300) / 2 * 1e-9)
    one = {1: two[1]}
    assert xtrace.busy_seconds(one) == xtrace.window_seconds(one)
    assert reader.read({}, {}, {"busy_s": xtrace.busy_seconds(one),
                                "traced_window_s": xtrace.window_seconds(one)}) == 0.0


@pytest.mark.parametrize("overhang_ns", [0.0, 400.0, 1500.0])
def test_no_overhang_of_the_hosts_window_reads_a_share_under_nought(overhang_ns):
    """A saturated slice (operations back to back with 10 ns between) that
    begins ``overhang_ns`` before the host's window and ends as long after
    it: the new share is the gaps' and the same whatever the overhang; the
    old one falls under nought as soon as the overhang outweighs the gaps."""
    n, op, gap = 200, 990.0, 10.0
    ops = [("op", -overhang_ns + i * (op + gap), op, {}) for i in range(n)]
    dev = {0: ops}
    host_window_ns = n * (op + gap) - 2 * overhang_ns
    busy, window = xtrace.busy_seconds(dev), xtrace.window_seconds(dev)
    assert busy == pytest.approx(n * op * 1e-9) and busy <= window
    new = U.load("readers", "device_idle_share").read(
        {}, {}, {"busy_s": busy, "traced_window_s": window})
    assert new == pytest.approx(100.0 * (n - 1) * gap / (n * op + (n - 1) * gap))
    assert 0.0 <= new <= 100.0
    old = 100.0 * (1.0 - busy / (host_window_ns * 1e-9))
    assert (old < 0.0) == (2 * overhang_ns > n * gap)


def test_trace_reduction_raises_on_a_truncated_unreadable_or_empty_trace(
        tpu_slice, tmp_path):
    whole = tpu_slice.read_bytes()
    cut = tmp_path / "cut.xplane.pb"
    cut.write_bytes(whole[:len(whole) // 2])
    with pytest.raises(xtrace.TraceError):
        xtrace.device_ops(xtrace.load(str(cut)))
    junk = tmp_path / "junk.xplane.pb"
    junk.write_bytes(b"\xff" * 64)
    with pytest.raises(xtrace.TraceError):
        xtrace.device_ops(xtrace.load(str(junk)))
    empty = tmp_path / "empty.xplane.pb"
    empty.write_bytes(b"")
    with pytest.raises(xtrace.TraceError):
        xtrace.load(str(empty))
    with pytest.raises(xtrace.TraceError):
        xtrace.find_xplane(str(tmp_path / "nowhere"))


def test_a_recorded_cpu_trace_has_no_device_plane_and_says_so():
    planes = xtrace.load(os.path.join(U.FIXTURES, "cpu_recorded.xplane.pb"))
    with pytest.raises(xtrace.TraceError):
        xtrace.device_ops(planes)                          # never a silent []
    dev = xtrace.device_ops(planes, rehearsal=True)        # the rehearsal's stand-in
    assert xtrace.busy_seconds(dev) > 0
    assert {name for name, _, _ in planes["host"]} == {
        "bench.handle_step", "bench.block_until_ready"}


def test_the_flash_rule_tells_the_three_kernels_apart_by_their_results():
    """Event names as a chip trace of PR 28 showed them, cut short."""
    with open(os.path.join(U.BENCH, "metrics", "flash_attention_roofline.json")) as f:
        calls = json.load(f)["params"]["calls"]
    tail = (' custom-call(bf16[2,32,4096,128]{3,2,1,0} %x), custom_call_target='
            '"tpu_custom_call", operand_layout_constraints={}')
    names = {
        "fwd": "%jvp__.3 = (bf16[2,32,4096,128]{3,2,1,0:T(8,128)(2,1)}, "
               "f32[2,32,4096,1]{3,2,1,0:T(8,128)})" + tail,
        "dkv": "%checkpoint.6 = (bf16[2,32,4096,128]{3,2,1,0:T(8,128)(2,1)}, "
               "bf16[2,32,4096,128]{3,2,1,0:T(8,128)(2,1)})" + tail,
        "dq": "%checkpoint.7 = bf16[2,32,4096,128]{3,2,1,0:T(8,128)(2,1)}" + tail,
        "none": '%custom-call.67 = bf16[4096,1024]{1,0} custom-call(bf16[1024,1024]'
                '{1,0} %s), custom_call_target="ConcatBitcast"',
    }
    dev = {0: [(n, 0.0, 1.0, {}) for n in names.values()]}
    for call, rule in calls.items():
        assert [e[0] for e in xtrace.matching(dev, rule)] == [names[call]]
    assert xtrace.short_name(names["dq"]) == \
        "%checkpoint.7 custom-call bf16[2,32,4096,128]{3,2,1,0:T(8,128)(2,1)}"


def test_kernel_roofline_reader_on_hand_made_events():
    reader = U.load("readers", "kernel_roofline")
    cfg = _cfg("mistral-7b-v0.3")
    cost = flops.flash_attention_costs(cfg, 2, 4096)["fwd"]
    least = cost[0] / 197e12
    dev = {0: [("flash_fwd.1", 0.0, 2 * least * 1e9, {}),
               ("flash_fwd.1", 9e9, 2 * least * 1e9, {}),
               ("fusion.7", 5e9, 1e6, {})]}
    env = {"device_ops": dev, "config": cfg, "module": U.load,
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    params = {"costs": {"module": "llama", "function": "flash_attention_costs"},
              "calls": {"fwd": {"name_regex": "^flash_fwd"},
                        "dq": {"name_regex": "^flash_dq"}}}
    value, note = reader.read({"batch": 2, "sequence_length": 4096}, params, env)
    assert value == pytest.approx(50.0) and note["fwd"]["bound"] == "compute"
    env["device_ops"] = {0: [("fusion.7", 0.0, 1e6, {})]}
    assert reader.read({"batch": 2, "sequence_length": 4096}, params, env) is None


# -- a served kernel's roofline share, from the counters of the traced slice ----
def _deepseek_paged_costs():
    """By hand, for 1,000 blocks read a layer at the DeepSeek cut: 12 layers x
    64 positions x 32 KV heads x 128 x (K and V) x 2 bytes is 12 MiB a block
    over all layers; the fewest lanes that read 1,000 blocks of rows 16 wide
    are 63, each with a query and an output row of 32 x 128 bf16 a layer."""
    kv = 1000 * 12 * 64 * 32 * 128 * 2 * 2
    assert kv == 1000 * 12 * 2 ** 20
    lanes = 63
    return 4 * 32 * 128 * 1000 * 64 * 12, kv + lanes * 12 * 32 * 128 * 2 * 2


def _paged_slice(tmp_path=None):
    """The planted slice: what the driver hands the readers and, where a
    place to write the trace is given, the device's operations in it."""
    with open(os.path.join(U.FIXTURES, "paged_slice_counters.json")) as f:
        planted = json.load(f)
    raw = {k: planted[k] for k in ("slice_counters", "slice_seconds")}
    if tmp_path is None:
        return raw
    return raw, xtrace.device_ops(xtrace.load(str(_xplane("paged_slice", tmp_path))))


def test_paged_attention_costs_against_a_hand_count():
    cfg, raw = _cfg("deepseek-llm-7b"), _paged_slice()
    assert flops.paged_attention_costs(cfg, cfg["engine"],
                                       raw["slice_counters"]) == \
        _deepseek_paged_costs()
    # a float32 pool moves twice the bytes of K and V, the queries' stay
    wide = flops.paged_attention_costs(
        cfg, dict(cfg["engine"], kv_cache_dtype="float32"), raw["slice_counters"])
    assert wide[1] - _deepseek_paged_costs()[1] == 1000 * 12 * 2 ** 20
    # grouped KV heads: K and V at the KV heads' width, FLOPs at the queries'
    gqa = flops.paged_attention_costs(dict(cfg, num_key_value_heads=8),
                                      cfg["engine"], raw["slice_counters"])
    assert gqa[0] == _deepseek_paged_costs()[0]
    assert gqa[1] == 1000 * 12 * 2 ** 18 + 63 * 12 * 32 * 128 * 2 * 2
    for nothing in ({}, {flops.ATTN_BLOCKS: {"extent=skipped": 9.0}},
                    {flops.ATTN_BLOCKS: {"extent=read": 0.0}}):
        assert flops.paged_attention_costs(cfg, cfg["engine"], nothing) is None


@pytest.mark.parametrize("peak_flops,bound", [(197e12, "memory"),
                                              (0.8e12, "compute")])
def test_kernel_roofline_counted_on_a_planted_slice(tmp_path, peak_flops, bound):
    reader = U.load("readers", "kernel_roofline_counted")
    with open(os.path.join(U.BENCH, "metrics",
                           "paged_attention_roofline.sat.json")) as f:
        spec = json.load(f)
    assert spec["reader"] == "kernel_roofline_counted"
    raw, dev = _paged_slice(tmp_path)
    cfg = _cfg("deepseek-llm-7b")
    env = {"device_ops": dev, "config": cfg, "module": U.load,
           "peaks": {"bf16_flops_per_s": peak_flops, "hbm_bytes_per_s": 819e9}}
    value, note = reader.read(raw, spec["params"], env)
    # the three kernel events (8 + 8 + 4 ms), not the fusion that only names
    # one among its operands
    assert note["events"] == 3 and note["seconds"] == pytest.approx(0.020)
    ops, nbytes = _deepseek_paged_costs()
    least = max(ops / peak_flops, nbytes / 819e9)
    assert note["bound"] == bound and (bound == "compute") == \
        (ops / peak_flops > nbytes / 819e9)
    assert value == pytest.approx(100.0 * least / 0.020)
    assert 60 < value < 100
    assert note["bytes"] == nbytes and note["flops"] == ops
    assert note["paddle_tpu_serving_attn_blocks_total"]["extent=read"] == 1000.0


def test_kernel_roofline_counted_returns_nothing_where_there_is_nothing_to_read(
        tmp_path):
    reader = U.load("readers", "kernel_roofline_counted")
    raw, dev = _paged_slice(tmp_path)
    cfg = _cfg("deepseek-llm-7b")
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    params = {"costs": {"module": "llama", "function": "paged_attention_costs"},
              "calls": {"k": {"name_regex": "^%paged_attention"}}}
    env = {"device_ops": dev, "config": cfg, "module": U.load, "peaks": peaks}
    assert reader.read(raw, params, env)[0] > 0
    # no matching event: a program that takes the plain path
    none = {"calls": {"k": {"name_regex": "^%no_such_kernel"}},
            "costs": params["costs"]}
    assert reader.read(raw, none, env) is None
    # an untraced run, a rehearsal without a peak, a driver that reads no
    # slice counters (the train driver), a slice in which nothing was counted
    assert reader.read(raw, params, dict(env, device_ops=None)) is None
    assert reader.read(raw, params, dict(env, peaks=None)) is None
    assert reader.read({"batch": 2, "sequence_length": 4096}, params, env) is None
    assert reader.read({"slice_counters": {}}, params, env) is None
