"""The serving programs of the models that were served before the serving
block learnt recurrent layers lower to the text they had: a Llama-shaped model
(bfloat16/float32 pools and int8 pools) and a MiMo-V2-shaped one, the mixed
step and the decode burst each.

The digests are of ``jax.jit(...).lower(...).as_text()`` (StableHLO, no
locations) at tiny sizes, weights and pools as shapes; they were read from the
tree BEFORE recurrent layers came (PR 35's) and are equal on this one. A change
that is meant to alter these programs updates the digest it alters, and says
so; one that is not has left them alone.
"""
import hashlib
import os
import sys

import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "benchmarks")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from paddle_tpu.models.serving import ContinuousBatchingEngine  # noqa: E402

PINS = {
    "llama": ("1bb25ada74c6c66c", "767f4a8e4a8fd4cb"),
    "llama-int8": ("f6ee04e3cab45503", "f6e7d7fa95f9ecb7"),
    "mimo": ("77fdf965d88234f2", "991274bb9bf7cc47"),
}


def _digests(eng):
    lanes = eng.max_step_tokens
    sds = jax.ShapeDtypeStruct

    def shapes(tree):
        return jax.tree.map(lambda a: sds(a.shape, a.dtype), tree)

    mixed = jax.jit(eng._inner.build_mixed_step()).lower(
        sds((2, lanes), jnp.int32), shapes(eng._pools), shapes(eng._tables()),
        sds((lanes,), jnp.int32), sds((lanes,), jnp.bool_),
        sds((lanes,), jnp.bool_), shapes(eng._inner.weights)).as_text()
    burst = jax.jit(eng._inner.build_decode_burst(4)).lower(
        sds((2, eng.max_batch), jnp.int32), shapes(eng._pools),
        shapes(eng._tables()), shapes(eng._inner.weights)).as_text()
    return tuple(hashlib.sha256(t.encode()).hexdigest()[:16]
                 for t in (mixed, burst))


def _llama(**engine):
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    model = LlamaForCausalLM(LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128))
    model.eval()
    return ContinuousBatchingEngine(model, max_batch=3, max_len=64,
                                    block_size=8, chunk_size=8, **engine)


def _mimo():
    from builders import mimo_v2_flash as B
    from test_mimo_v2_serving import CFG

    model = B.construct(CFG)
    model.eval()
    return ContinuousBatchingEngine(model, **CFG["engine"])


@pytest.mark.parametrize("name,build", [
    ("llama", _llama), ("llama-int8", lambda: _llama(kv_cache_dtype="int8")),
    ("mimo", _mimo)])
def test_a_model_without_recurrent_layers_lowers_to_the_program_it_had(
        name, build):
    assert _digests(build()) == PINS[name]
