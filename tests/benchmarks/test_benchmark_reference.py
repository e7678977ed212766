"""The plain reference against the program at tiny widths on the CPU, so that a
disagreement on the chip points at the chip path and not at the reference; the
float8 control, which has to come out as not correct; and AdamW under
``mesh.parallelize``, expected to fail until the program is repaired."""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import _bench_util as U

import paddle_tpu as paddle

W = U.load("", "weights")
common = U.load("", "common")
compare = U.load("", "compare")
R = U.load("reference", "llama")
builder = U.load("builders", "llama")

CFG = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
           num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
           head_dim=16, max_position_embeddings=256, rope_theta=1e6,
           rms_norm_eps=1e-5, initializer_range=0.02)
SEED = 2 ** 31 + 77           # more than 32 signed bits hold


def _build_model(cfg, seed, training):
    model = builder.construct(cfg)
    n_params = common.load_weights(model, builder.weights(seed, cfg, "bfloat16"))
    model.train() if training else model.eval()
    return model, n_params


def _batch(seed, rows=2, length=64):
    r = np.random.RandomState(seed)
    return (r.randint(0, 256, (rows, length)).astype(np.int32),
            r.randint(0, 256, (rows, length)).astype(np.int32))


def test_weights_are_a_pure_function_of_the_seed():
    a, b = W.make_all(SEED, CFG), W.make_all(SEED, CFG)
    c = W.make_all(SEED + 1, CFG)
    specs = W.leaf_specs(CFG)
    assert sorted(a) == sorted(n for n, _, _ in specs)
    key = W.seed_key(SEED)
    for i, (name, shape, kind) in enumerate(specs):
        assert a[name].shape == tuple(shape) and a[name].dtype == jnp.bfloat16
        assert np.array_equal(np.asarray(a[name], np.float32),
                              np.asarray(b[name], np.float32))
        # drawn again alone, with a traced index, as the reference does
        again = jax.jit(lambda j: W.leaf(key, j, shape, kind, 0.02,
                                         jnp.bfloat16))(jnp.int32(i))
        assert np.array_equal(np.asarray(a[name], np.float32),
                              np.asarray(again, np.float32))
        if kind == "normal":
            assert not np.array_equal(np.asarray(a[name], np.float32),
                                      np.asarray(c[name], np.float32))
    zero = W.change_norms(SEED, CFG, a)
    assert max(zero.values()) == 0.0


def test_forward_loss_and_gradient_agree_with_the_program_in_float32():
    cfg = dict(CFG, model={"dtype": "float32", "recompute": True})
    model, n = _build_model(cfg, SEED, training=True)
    assert n == sum(int(np.prod(s)) for _, s, _ in W.leaf_specs(CFG))
    ids, labels = _batch(0)
    loss, _ = model(paddle.to_tensor(ids), labels=paddle.to_tensor(labels))
    loss.backward()
    ref = R.TrainReference(SEED, CFG, {"name": "SGD", "learning_rate": 0.0})
    ref_loss = ref.step(ids, labels)
    assert float(loss.value) == pytest.approx(ref_loss, rel=1e-5)
    prog = {name: float(jnp.linalg.norm(p.grad.value))
            for name, p in model.named_parameters()}
    gap, leaf = compare.worst_leaf_gap(prog, ref.grad_norms)
    assert gap < 1e-4, (gap, leaf)


def _engine_gaps(quant_of_reference=None, alter=None):
    from paddle_tpu.models.serving import ContinuousBatchingEngine

    cfg = dict(CFG, num_key_value_heads=4, model={"dtype": "float32"})
    model, _ = _build_model(cfg, SEED, training=False)
    eng = ContinuousBatchingEngine(model, max_batch=4, max_len=128,
                                   block_size=16, chunk_size=32)
    r = np.random.RandomState(3)
    prompts = [r.randint(0, 256, n).astype(np.int32) for n in (50, 9, 33)]
    new = 40
    rids = [eng.submit(p, max_new_tokens=new) for p in prompts]
    done = {}
    while len(done) < len(rids):
        for rid, toks in eng.step():
            done[rid] = np.asarray(toks, np.int32)
    tokens = np.zeros((3, 128), np.int32)
    positions = np.zeros((3, new), np.int32)
    query = np.zeros((3, new), np.int32)
    for j, (rid, p) in enumerate(zip(rids, prompts)):
        out = done[rid].copy()
        assert len(out) == new
        if alter is not None:
            out[alter] = (out[alter] + 1) % 256
        tokens[j, :len(p)] = p
        tokens[j, len(p):len(p) + new - 1] = out[:-1]
        positions[j] = len(p) - 1 + np.arange(new)
        query[j] = out
    ref = R.ServeReference(SEED, cfg)
    gaps, argmax = ref.gaps(tokens, positions, query)
    return np.asarray(gaps), np.asarray(argmax), query, (ref, tokens, positions)


def test_engine_tokens_through_chunked_prefill_and_cached_decode_are_the_references():
    gaps, argmax, served, _ = _engine_gaps()
    # float32 engine against the float32 reference: the same greedy tokens,
    # through chunked prefill (50 > chunk 32), paged writes and decode bursts
    assert gaps.max() < 1e-4
    assert np.array_equal(argmax, served)


def test_an_altered_token_reads_far_below_the_references_best():
    gaps, _, _, _ = _engine_gaps(alter=5)
    assert gaps.max() > 0.02


def test_the_float8_control_comes_out_as_not_correct_for_serving():
    _, _, _, (ref, tokens, positions) = _engine_gaps()
    cfg = ref.cfg
    control = R.ServeReference(SEED, cfg, quant="fp8")
    _, first_choice = control.gaps(tokens, positions, np.zeros_like(positions))
    gaps, _ = ref.gaps(tokens, positions, np.asarray(first_choice))
    with open(os.path.join(U.BENCH, "limits", "deepseek-7b-serve-offline.json")) as f:
        limit = json.load(f)["rehearse"]["token_logit_gap"]
    assert float(np.asarray(gaps).max()) > limit


def _follow(ref_like, steps=3):
    feed = [_batch(s) for s in range(steps)]
    losses = [ref_like.step(*b) for b in feed]
    return {"losses": losses, "grad_norms": ref_like.grad_norms,
            "change_norms": ref_like.change_norms()}


@pytest.mark.parametrize("fault,kwargs", [
    ("float8 control", {"quant": "fp8"}),
    ("half of the batch left out", {"rows": 1}),
])
def test_control_and_half_batch_fail_the_training_numbers(fault, kwargs):
    opt = {"name": "Momentum", "learning_rate": 0.1, "momentum": 0.9}
    reference = _follow(R.TrainReference(SEED, CFG, opt))
    broken = _follow(R.TrainReference(SEED, CFG, opt, **kwargs))
    numbers, _ = compare.train_numbers(broken, reference)
    with open(os.path.join(U.BENCH, "limits", "mistral-7b-train-4k-sgdm.json")) as f:
        limits = json.load(f)["rehearse"]
    assert set(numbers) == {"grad_norm_gap", "change_norm_gap"}
    assert any(numbers[k] > limits[k] for k in numbers), (fault, numbers)


def _program_follow(opt_spec, steps=3):
    from paddle_tpu import mesh as pmesh

    cfg = dict(CFG, model={"dtype": "bfloat16", "recompute": True})
    model, _ = _build_model(cfg, SEED, training=True)
    cls = getattr(paddle.optimizer, opt_spec["name"])
    opt = cls(parameters=model.parameters(), multi_precision=True,
              **{k: v for k, v in opt_spec.items() if k != "name"})

    def loss_fn(m, ids, labels):
        return m(ids, labels=labels)[0]

    feed = [_batch(s) for s in range(steps)]
    handle = pmesh.parallelize(model, opt, loss_fn, feed[0],
                               config={"dp_degree": 1})
    losses = [float(handle.step(*b).value) for b in feed]
    names = list(handle.param_names)
    return {"losses": losses,
            "change_norms": W.change_norms(SEED, CFG, dict(zip(names, handle._mv)))}


def test_momentum_under_parallelize_follows_the_reference():
    opt = {"name": "Momentum", "learning_rate": 0.1, "momentum": 0.9}
    reference = _follow(R.TrainReference(SEED, CFG, opt))
    program = _program_follow(opt)
    gap, leaf = compare.worst_leaf_gap(
        program["change_norms"], reference["change_norms"],
        compare.moving_leaves(reference["grad_norms"]))
    assert gap < 0.05, (gap, leaf)


@pytest.mark.xfail(strict=False, reason=(
    "the program's fault that keeps AdamW out of the training cell (PERF.md, "
    "Open questions): optimizer.step() is traced once inside the jitted mesh "
    "step, so _step_count is a constant 1 in the compiled program, the bias "
    "corrections never advance, and after three steps every parameter has "
    "moved about 1.3 times as far as AdamW's equations say. Passes once the "
    "program is repaired; the AdamW cell can then come in"))
def test_adamw_under_parallelize_follows_the_reference():
    opt = {"name": "AdamW", "learning_rate": 1e-3}
    reference = _follow(R.TrainReference(SEED, CFG, opt))
    program = _program_follow(opt)
    gap, leaf = compare.worst_leaf_gap(
        program["change_norms"], reference["change_norms"],
        compare.moving_leaves(reference["grad_norms"]))
    assert gap < 0.05, (gap, leaf)
