"""``paddle_tpu_serving_lane_steps_total{use}``: the engine counts each of its
``max_batch`` rows once a forward pass of every step it dispatches, as granted
(a token, or a chunk of its prompt), held (a request given no work) or empty,
so the three sum to ``max_batch`` x ``paddle_tpu_serving_forwards_total``."""
import numpy as np
import pytest

from paddle_tpu import monitor
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.serving import ContinuousBatchingEngine


@pytest.fixture
def model():
    m = LlamaForCausalLM(LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128))
    m.eval()
    return m


@pytest.fixture
def on():
    monitor.disable()
    monitor.reset()
    monitor.enable()
    yield
    monitor.disable()
    monitor.reset()


def _lanes():
    snap = monitor.snapshot()["metrics"]
    uses = {k.split("=", 1)[1]: v for k, v in
            snap["paddle_tpu_serving_lane_steps_total"]["values"].items()}
    return uses, snap["paddle_tpu_serving_forwards_total"]["values"][""]


@pytest.mark.parametrize("chunk_size", [8, 64])
def test_the_uses_sum_to_the_rows_of_every_forward(model, on, chunk_size):
    eng = ContinuousBatchingEngine(model, max_batch=3, max_len=64,
                                   block_size=8, chunk_size=chunk_size)
    rng = np.random.default_rng(0)
    asked = [(n, m) for n, m in ((5, 9), (30, 4), (12, 13), (3, 2), (20, 6))]
    for n, m in asked:
        eng.submit(rng.integers(1, 100, n).astype(np.int32), max_new_tokens=m)
    while eng.num_active or eng.num_pending:
        eng.step()
    uses, forwards = _lanes()
    assert set(uses) <= {"granted", "held", "empty"}
    assert sum(uses.values()) == eng.max_batch * forwards
    # every prompt chunk and every token after a request's first (which its
    # last chunk gives) was granted a row once: a burst grants no more than
    # the tokens a request still wants
    chunks = monitor.snapshot()["metrics"][
        "paddle_tpu_serving_chunked_prefill_depth"]["values"][""]["sum"]
    assert chunks >= sum(-(-n // chunk_size) for n, _ in asked)
    assert uses["granted"] == sum(m for _, m in asked) - len(asked) + chunks
    assert uses.get("empty", 0) > 0


def test_nothing_is_counted_while_the_monitor_is_off(model):
    monitor.disable()
    monitor.reset()
    eng = ContinuousBatchingEngine(model, max_batch=2, max_len=64,
                                   block_size=8, chunk_size=8)
    eng.submit(np.arange(1, 10, dtype=np.int32), max_new_tokens=5)
    while eng.num_active or eng.num_pending:
        eng.step()
    assert "paddle_tpu_serving_lane_steps_total" not in str(
        {k: v for k, v in monitor.snapshot()["metrics"].items()
         if v.get("values")})
