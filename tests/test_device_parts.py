"""The device parts of the serving programs (``monitor/parts.py``): every part
of ``catalog.PARTS`` is a named scope in the compiled programs of the tiny
Llama, MiMo, Olmo-Hybrid and Ouro engines and no other scope is; the map of
instructions to parts is built at ``monitor.enable()`` from the executables
that run (compiling nothing), at a dispatch while the monitor is on, and never
while it is off; the rules that give an instruction its part, on HLO text."""
import collections
import gc
import hashlib
import os
import re
import sys

import numpy as np
import pytest

import jax
from jax import monitoring

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "benchmarks"), os.path.dirname(__file__)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from paddle_tpu import monitor  # noqa: E402
from paddle_tpu.monitor import catalog, parts  # noqa: E402
from paddle_tpu.models.serving import ContinuousBatchingEngine  # noqa: E402

# what jax itself puts on an op_name path: jit(...), vmap(...), loops and
# their bodies, closed calls, einsum specs
JAX_OWN = re.compile(r"[(),]|->|^(while|body|cond|closed_call)$")


def _llama():
    from test_serving_programs_pinned import _llama as build

    return build()


def _mimo():
    from test_serving_programs_pinned import _mimo as build

    return build()


def _olmo():
    from test_serving_programs_pinned import _olmo as build

    return build()


def _ouro():
    from builders import ouro as B
    from test_ouro_serving import CFG

    model = B.construct(CFG)
    model.eval()
    return ContinuousBatchingEngine(model, **CFG["engine"])


ENGINES = {"llama": _llama, "mimo": _mimo, "olmo": _olmo, "ouro": _ouro}


def _serve(eng, seed=0):
    rng = np.random.default_rng(seed)
    for n in (5, 17, 30):
        eng.submit(rng.integers(1, 100, n).astype(np.int32), max_new_tokens=9)
    while eng.num_active or eng.num_pending:
        eng.step()


def _texts(eng):
    """The optimized HLO of the engine's two programs, as they run."""
    return [eng._jit_cache[kind].as_text() for kind in ("step", "burst")]


@pytest.fixture(scope="module")
def scopes():
    """``{model: Counter of op_name path components}`` of the compiled
    programs of each tiny engine, the primitive left out."""
    out = {}
    for name, build in ENGINES.items():
        eng = build()
        _serve(eng)
        seen = collections.Counter()
        for text in _texts(eng):
            for m in re.finditer(r'op_name="([^"]*)"', text):
                for path in m.group(1).split(";"):
                    seen.update(path.split("/")[:-1])
        out[name] = seen
    return out


@pytest.fixture
def fresh_monitor():
    monitor.disable()
    monitor.reset()
    yield
    monitor.disable()
    monitor.reset()


def test_every_part_is_a_scope_of_some_served_model(scopes):
    used = set().union(*scopes.values())
    assert set(catalog.PARTS) <= used, set(catalog.PARTS) - used


@pytest.mark.parametrize("model,own", [
    ("llama", set()), ("mimo", {"router", "experts"}),
    ("olmo", {"recurrent"}), ("ouro", set())])
def test_each_model_shows_the_parts_it_has(scopes, model, own):
    common = {"embed", "tables", "attn_proj", "kv_write", "paged_attention",
              "mlp", "head", "sample"}
    assert common | own <= set(scopes[model])
    assert not ({"router", "experts", "recurrent"} - own) & set(scopes[model])


@pytest.mark.parametrize("model", sorted(ENGINES))
def test_no_scope_outside_the_catalog(scopes, model):
    ours = {c for c in scopes[model]
            if not JAX_OWN.search(c) and not c.startswith("jit")}
    assert ours <= set(catalog.PARTS), ours - set(catalog.PARTS)


def test_a_free_string_is_refused():
    with pytest.raises(KeyError):
        parts.scope("held_experts")
    assert parts.scope("sample") is not None


def test_a_looped_stack_lowers_to_the_program_it_had():
    """The scopes are metadata: Ouro's two programs keep their text (the
    digests of the tree before the scopes, read as
    tests/test_serving_programs_pinned.py reads the other four)."""
    from test_serving_programs_pinned import _digests

    assert _digests(_ouro()) == ("f6d7ff60fa1b774e", "ce76a56a2e5c849e")


def _compiles():
    n = [0]

    def on(event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            n[0] += 1
    monitoring.register_event_duration_secs_listener(on)
    return n


def test_nothing_is_mapped_while_off_and_enable_maps_what_was_compiled(
        fresh_monitor):
    compiles = _compiles()
    eng = _llama()
    _serve(eng)
    assert monitor.device_parts() == {}
    before = compiles[0]
    monitor.enable()
    maps = monitor.device_parts()
    # found in jit's caches: the executables that ran, none compiled again
    assert compiles[0] == before
    modules = collections.Counter(k.split(":")[0] for k in maps)
    assert modules["jit_serving_mixed_step"] >= 1
    assert modules["jit_serving_decode_burst"] >= 1
    assert modules["jit__compose_tokens"] >= 1
    found = {p for m in maps.values() for _, p in m.values()}
    assert found - {None} <= set(catalog.PARTS)
    assert {"attn_proj", "paged_attention", "sample", "head"} <= found
    assert all(re.fullmatch(r"[a-z0-9]+\[[0-9,]*\]|\(.*\)", shape)
               for m in maps.values() for shape, _ in m.values())
    # a plain dict held by the monitor, readable after the engine is freed
    del eng
    gc.collect()
    assert monitor.device_parts() == maps
    snap = str(monitor.snapshot())      # never part of a snapshot
    assert not any(key in snap for key in maps)
    monitor.reset()
    assert monitor.device_parts() == {}


def test_a_program_compiled_while_on_is_mapped_at_its_dispatch(
        fresh_monitor):
    monitor.enable()
    eng = _mimo()
    _serve(eng)
    modules = {k.split(":")[0] for k in monitor.device_parts()}
    assert {"jit_serving_mixed_step", "jit_serving_decode_burst"} <= modules
    found = {p for m in monitor.device_parts().values()
             for _, p in m.values()}
    assert {"router", "experts"} <= found


def test_the_enable_hook_holds_its_engine_weakly(fresh_monitor):
    import weakref

    eng = _llama()
    _serve(eng)
    ref = weakref.ref(eng)
    del eng
    gc.collect()
    assert ref() is None
    monitor.enable()                    # a dead engine's hook is skipped


# -- the rules, on HLO text ---------------------------------------------------

HLO = """HloModule jit_step, entry_computation_layout={(f32[4]{0})->f32[4]{0}}

%fused_a (p0: f32[4]) -> f32[4] {
  %p0 = f32[4]{0} parameter(0)
  %m.1 = f32[4]{0} multiply(%p0, %p0), metadata={op_name="jit(step)/mlp/mul"}
  %m.2 = f32[4]{0} add(%m.1, %p0), metadata={op_name="jit(step)/mlp/add"}
  ROOT %m.3 = f32[4]{0} exp(%m.2), metadata={op_name="jit(step)/head/exp"}
}

%body (b: (f32[4], f32[4])) -> (f32[4], f32[4]) {
  %b = (f32[4]{0}, f32[4]{0}) parameter(0)
  %x = f32[4]{0} get-tuple-element(%b), index=1
  %copy.9 = f32[4]{0:T(128)} copy(%x)
  %fusion.2 = f32[4]{0:T(128)S(1)} fusion(%copy.9), kind=kLoop, calls=%fused_a
  %i = f32[4]{0} get-tuple-element(%b), index=0
  ROOT %t = (f32[4]{0}, f32[4]{0}) tuple(%i, %fusion.2)
}

%cond (c: (f32[4], f32[4])) -> pred[] {
  %c = (f32[4]{0}, f32[4]{0}) parameter(0)
  ROOT %lt = pred[] constant(true)
}

ENTRY %main (a: f32[4], w: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0), metadata={op_name="a"}
  %w = f32[4]{0} parameter(1), metadata={op_name="w[\'layers\'][0][\'up\']"}
  %fusion.1 = f32[4]{0} fusion(%a), kind=kLoop, calls=%fused_a, metadata={op_name="jit(step)/sample/argmax;jit(step)/sample/x"}
  %copy-start.3 = (f32[4]{0}, f32[4]{0}, u32[]) copy-start(%fusion.1), metadata={op_name="jit(step)/embed/gather"}
  %copy-done.3 = f32[4]{0} copy-done(%copy-start.3)
  %copy.7 = f32[4]{0:T(128)} copy(%w), metadata={op_name="w[\'layers\'][0][\'up\']"}
  %init = (f32[4]{0}, f32[4]{0}) tuple(%copy-done.3, %copy.7)
  %while.5 = (f32[4]{0}, f32[4]{0}) while(%init), condition=%cond, body=%body, metadata={op_name="jit(step)/while"}
  %copy-start.4 = (f32[4]{0}, f32[4]{0}, u32[]) copy-start(%w), cross_program_prefetch_index=0
  %copy-done.4 = f32[4]{0} copy-done(%copy-start.4)
  ROOT %out = f32[4]{0} get-tuple-element(%while.5), index=1
}
"""


def test_part_of_takes_the_innermost_declared_part():
    assert parts.part_of("jit(f)/experts/router/jit(_where)/select_n") \
        == "router"
    assert parts.part_of("jit(f)/while/body/mlp/dot_general") == "mlp"
    assert parts.part_of("jit(f)/held_experts/dot_general") is None
    assert parts.part_of("jit(f)/mlp/mul;jit(f)/reshape") == "mlp"
    assert parts.part_of("jit(f)/mlp/mul;jit(f)/head/mul") is None


def test_the_rules_give_each_running_instruction_its_part():
    got = parts.parse(HLO)
    # what runs: the entry, the loop's body and condition; never a fusion's
    # own instructions
    assert "m.1" not in got and "p0" not in got
    assert got["fusion.1"] == ("f32[4]", "sample")      # its own op_name
    assert got["fusion.2"] == ("f32[4]", "mlp")         # most of its body
    assert got["copy-done.3"] == ("f32[4]", "embed")    # its start's
    assert got["copy.9"] == ("f32[4]", "mlp")           # its reader's
    # a weight copied into a loop's carry: what the body reads it with
    assert got["copy.7"] == ("f32[4]", "mlp")
    # a prefetch nothing in the program reads: its weight's other readers
    assert got["copy-done.4"] == ("f32[4]", "mlp")
    assert got["while.5"] == ("(f32[4],f32[4])", None)  # its own: none
    assert got["lt"][1] is None and got["init"][1] is None


def test_a_map_is_recorded_only_while_the_monitor_is_on(fresh_monitor):
    assert parts.record(HLO) is None and monitor.device_parts() == {}
    monitor.enable()
    key = parts.record(HLO)
    digest = hashlib.sha1(HLO.encode()).hexdigest()[:12]
    assert key == f"jit_step:{digest}"
    assert monitor.device_parts()[key]["fusion.1"] == ("f32[4]", "sample")
    assert parts.build_seconds() > 0
