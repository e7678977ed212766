"""BENCHMARK.json against the contract's rules that need no chip, and the
harness's promise that a new cell is new files and new entries only."""
import json
import os
import re
import shutil

import pytest

import _bench_util as U

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
BENCH = U.benchmark_json()


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_keys_and_sizes():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(U.ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51
    assert 1 <= len(BENCH["command"]) <= 32 and all(map(_line, BENCH["command"]))
    assert 1 <= len(BENCH["paths"]) <= 16
    for p in BENCH["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", p)
        assert not p.startswith("/") and ".." not in p.split("/")
        assert os.path.isdir(os.path.join(U.ROOT, p))
    # the full check fits: 2 + 14 x 24 runs of run_seconds + 60, 2 x 90 a cell
    cells = 24
    assert (2 + 14 * cells) * (BENCH["run_seconds"] + 60) + cells * 180 + 1200 \
        <= 43200


def test_names_units_and_entries_keep_to_the_allowed_characters():
    names = []
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"])
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])
        names.append(c["name"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
        names.append(w["name"])
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert _line(m["layer"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        names.append(m["name"])
    assert len(names) == len(set(names))
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 4)


def _config_file(entry, root=U.ROOT):
    with open(os.path.join(root, entry["file"])) as f:
        return json.load(f)


def test_every_configuration_has_a_cell_and_a_file_of_its_own():
    used = {w["config"] for w in BENCH["workloads"]}
    files = set()
    for c in BENCH["configs"]:
        assert c["name"] in used
        assert c["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
        assert c["file"] not in files
        files.add(c["file"])
        cfg = _config_file(c)
        assert cfg["source"] == c["source"]
        assert cfg["bytes"]["parameters"] > 0
        assert all(isinstance(cfg.get(k), str) for k in
                   ("driver", "builder", "reference"))     # no default for any


# -- how a configuration may be cut (the model-configs guide, section 4) -------
# The kind of a reduced key is told by its name. Any other key is a width.
DEPTH = {"num_hidden_layers"}
EXPERTS = {"n_routed_experts", "num_experts", "num_local_experts"}
VOCABULARY = {"vocab_size"}
LEAST_EXPERTS, LEAST_VOCABULARY_SHARE = 8, 8              # an eighth


def _whole(x):
    return isinstance(x, int) and not isinstance(x, bool)


def _only_depth_experts_and_vocabulary(reduced, cfg):
    return [f"{k}: neither depth, experts held nor vocabulary rows: a width"
            for k in reduced if k not in DEPTH | EXPERTS | VOCABULARY]


def _published_values_are_larger(reduced, cfg):
    faults = [] if cfg.get("reduced") == reduced else \
        [f"BENCHMARK.json reduces {reduced}, the file {cfg.get('reduced')}"]
    for k in reduced:
        here, published = cfg.get(k), cfg.get("published", {}).get(k)
        if not _whole(published):
            faults.append(f"{k}: the file's 'published' does not give it")
        elif not _whole(here) or not 0 < here < published:
            faults.append(f"{k}: {here!r} held, {published} published")
    return faults


def _floors(reduced, cfg):
    faults = []
    for k in reduced:
        here, published = cfg.get(k), cfg.get("published", {}).get(k)
        if k in EXPERTS and _whole(here) and here < LEAST_EXPERTS:
            faults.append(f"{k}: {here} experts held, under {LEAST_EXPERTS}")
        if k in VOCABULARY and _whole(here) and _whole(published) \
                and here * LEAST_VOCABULARY_SHARE < published:
            faults.append(f"{k}: {here} of {published} rows, under an eighth")
    return faults


def _share_names_its_deployment(reduced, cfg):
    said = cfg.get("deployment")
    faults = [] if isinstance(said, str) and said.strip() else \
        ["no 'deployment' sentence"]
    shared = [k for k in reduced if k in EXPERTS | VOCABULARY]
    if not shared:
        return faults
    chips = cfg.get("chips_sharing_a_layer")
    if not _whole(chips) or chips < 2:
        return faults + [f"{shared} reduced, 'chips_sharing_a_layer' {chips!r}"]
    for k in shared:
        here, published = cfg.get(k), cfg.get("published", {}).get(k)
        if k in EXPERTS and _whole(here) and _whole(published) \
                and here * chips != published:
            faults.append(f"{k}: {here} held x {chips} chips != {published}")
    return faults


CUT_RULES = {f.__name__.lstrip("_"): f for f in (
    _only_depth_experts_and_vocabulary, _published_values_are_larger, _floors,
    _share_names_its_deployment)}


@pytest.mark.parametrize("rule", sorted(CUT_RULES))
@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_a_configuration_is_cut_only_as_the_guide_cuts_it(config, rule):
    entry = next(c for c in BENCH["configs"] if c["name"] == config)
    assert CUT_RULES[rule](entry["reduced"], _config_file(entry)) == []


# a share of a deployment that the rules admit: depth, 16 of 256 experts on
# each of 16 chips, an eighth of the vocabulary
SHARE = {"num_hidden_layers": 7, "n_routed_experts": 16, "vocab_size": 12800,
         "intermediate_size": 2048,
         "reduced": ["num_hidden_layers", "n_routed_experts", "vocab_size"],
         "published": {"num_hidden_layers": 48, "n_routed_experts": 256,
                       "vocab_size": 102400},
         "chips_sharing_a_layer": 16,
         "deployment": "one of 16 chips that share each expert layer"}


def _planted(tmp_path, change):
    """A throw-away configuration file, read back as the rules read one."""
    cfg = json.loads(json.dumps(SHARE))
    change(cfg)
    (tmp_path / "planted.json").write_text(json.dumps(cfg))
    return cfg["reduced"], _config_file({"file": "planted.json"}, str(tmp_path))


@pytest.mark.parametrize("rule", sorted(CUT_RULES))
def test_a_share_of_depth_experts_and_vocabulary_is_admitted(tmp_path, rule):
    assert CUT_RULES[rule](*_planted(tmp_path, lambda cfg: None)) == []


def _a_width(cfg):
    cfg["reduced"].append("intermediate_size")
    cfg["published"]["intermediate_size"] = 16384


def _four_experts(cfg):
    cfg.update(n_routed_experts=4, chips_sharing_a_layer=64)


def _a_sixteenth_of_the_vocabulary(cfg):
    cfg["vocab_size"] = 6400


def _no_published_value(cfg):
    del cfg["published"]["n_routed_experts"]


def _sixteen_experts_on_eight_chips(cfg):
    cfg["chips_sharing_a_layer"] = 8


def _no_chips_stated(cfg):
    del cfg["chips_sharing_a_layer"]


def _nothing_left_out(cfg):
    cfg["num_hidden_layers"] = cfg["published"]["num_hidden_layers"]


def _another_list_in_the_file(cfg):
    # BENCHMARK.json's entry (the list as it was) says more than the file does
    cfg["reduced"] = cfg["reduced"][:1]


@pytest.mark.parametrize("change,refused_by", [
    (_a_width, "only_depth_experts_and_vocabulary"),
    (_four_experts, "floors"),
    (_a_sixteenth_of_the_vocabulary, "floors"),
    (_no_published_value, "published_values_are_larger"),
    (_sixteen_experts_on_eight_chips, "share_names_its_deployment"),
    (_no_chips_stated, "share_names_its_deployment"),
    (_nothing_left_out, "published_values_are_larger"),
], ids=lambda x: x.__name__.lstrip("_") if callable(x) else x)
def test_a_planted_bad_cut_is_refused(tmp_path, change, refused_by):
    reduced, cfg = _planted(tmp_path, change)
    assert CUT_RULES[refused_by](reduced, cfg) != []
    # and by that rule alone: each fault is one rule's to catch
    assert all(rule(reduced, cfg) == [] for name, rule in CUT_RULES.items()
               if name != refused_by)


def test_a_file_that_reduces_another_list_than_its_entry_is_refused(tmp_path):
    _, cfg = _planted(tmp_path, _another_list_in_the_file)
    assert _published_values_are_larger(SHARE["reduced"], cfg) != []


def test_every_file_a_cell_names_exists():
    for w in BENCH["workloads"]:
        cfg_entry = next(c for c in BENCH["configs"] if c["name"] == w["config"])
        with open(os.path.join(U.ROOT, cfg_entry["file"])) as f:
            cfg = json.load(f)
        with open(os.path.join(U.BENCH, "traffic", w["traffic"] + ".json")) as f:
            traffic = json.load(f)
        for kind, name in (("drivers", cfg["driver"]),
                           ("builders", cfg["builder"]),
                           ("reference", cfg["reference"]),
                           ("generators", traffic["generator"])):
            assert os.path.isfile(os.path.join(U.BENCH, kind, name + ".py"))
        assert os.path.isfile(os.path.join(U.BENCH, "limits", w["name"] + ".json"))
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        with open(os.path.join(U.BENCH, "metrics", m["name"] + ".json")) as f:
            spec = json.load(f)
        assert os.path.isfile(os.path.join(U.BENCH, "readers",
                                           spec["reader"] + ".py"))


def test_metrics_and_cells_line_up():
    cells = {w["name"] for w in BENCH["workloads"]}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]

    def reports(metric, cell):
        return "workloads" not in metric or cell in metric["workloads"]

    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert set(m.get("workloads", [])) <= cells
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        for cell in m.get("workloads", cells):
            assert reports(e2e[m["moves"]], cell), (m["name"], cell)
    for cell in cells:
        assert any(reports(m, cell) for m in BENCH["end_to_end"]
                   if m["name"] != "setup_s")
        assert any(reports(m, cell) for m in BENCH["per_layer"])
    # a roofline share comes with the whole step's mfu moving the same metric
    for m in BENCH["per_layer"]:
        if m["name"].endswith("_roofline"):
            assert any("mfu" in re.split(r"[._]", o["name"]) and
                       o["moves"] == m["moves"] for o in BENCH["per_layer"])


def test_nothing_under_benchmarks_imports_the_old_bench_files():
    banned = re.compile(r"^\s*(from|import)\s+(bench|bench_common|bench_suite|"
                        r"chip_smoke)\b", re.M)
    for base, _, files in os.walk(U.BENCH):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(base, name)) as f:
                    assert not banned.search(f.read()), name


def test_the_runner_names_no_configuration_traffic_driver_or_metric():
    with open(os.path.join(U.BENCH, "run.py")) as f:
        text = f.read()
    listed = [w["name"] for w in BENCH["workloads"]] \
        + [c["name"] for c in BENCH["configs"]] \
        + [w["traffic"] for w in BENCH["workloads"]] \
        + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]
           if m["name"] != "setup_s"]
    for kind in ("drivers", "builders", "generators", "readers", "reference",
                 "flops"):
        listed += [n[:-3] for n in os.listdir(os.path.join(U.BENCH, kind))
                   if n.endswith(".py")]
    for name in listed:
        assert not re.search(r"[\"']%s[\"']" % re.escape(name), text), name


@pytest.fixture
def copied_tree(tmp_path):
    """BENCHMARK.json and the benchmark's directories alone, in a new place."""
    shutil.copy(os.path.join(U.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(U.BENCH, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return tmp_path


def test_without_the_program_it_fails_and_prints_no_result(copied_tree):
    cell = BENCH["workloads"][0]["name"]
    rc, result, out, err = U.run_cell(cell, 1, 1, root=str(copied_tree),
                                      extra_env={"PYTHONPATH": ""})
    assert rc != 0 and result is None
    assert not any(line.startswith("{") and '"correct"' in line
                   for line in out.splitlines())


def test_without_a_tpu_it_fails_and_prints_no_result():
    cell = BENCH["workloads"][0]["name"]
    rc, result, out, err = U.run_cell(cell, 1, 1, rehearse=False)
    assert rc != 0 and out.strip() == "" and "--rehearse" in err


def test_a_new_cell_is_new_files_and_entries_only(copied_tree):
    """A throw-away configuration, traffic mix, metric, limits file and
    ``workloads`` entry run without editing any file that exists."""
    b = copied_tree / "benchmarks"
    before = {p: p.read_bytes() for p in b.rglob("*") if p.is_file()}
    cfg = json.loads((b / "configs" / "deepseek-llm-7b.json").read_text())
    cfg["rehearse"]["intermediate_size"] = 96
    cfg["source"] = "https://example.invalid/throw-away"
    (b / "configs" / "throwaway.json").write_text(json.dumps(cfg))
    traffic = json.loads((b / "traffic" / "chat-backlog.json").read_text())
    traffic["rehearse"]["backlog_requests"] = 1200
    (b / "traffic" / "throwaway-mix.json").write_text(json.dumps(traffic))
    shutil.copy(b / "limits" / "deepseek-7b-serve-offline.json",
                b / "limits" / "throwaway-cell.json")
    (b / "metrics" / "finished_tokens_per_s.throwaway.json").write_text(
        json.dumps({"reader": "token_rate",
                    "params": {"of": "requests_finished"}}))
    bench = json.loads((copied_tree / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "throwaway", "source": cfg["source"],
                             "file": "benchmarks/configs/throwaway.json",
                             "reduced": ["num_hidden_layers"], "why": "test"})
    bench["workloads"].append({"name": "throwaway-cell", "config": "throwaway",
                               "traffic": "throwaway-mix", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "serve_tokens_per_s":
            m["workloads"].append("throwaway-cell")
    bench["per_layer"].append({"name": "finished_tokens_per_s.throwaway",
                               "unit": "tokens/s", "better": "higher",
                               "source": "host_clock",
                               "layer": "serving scheduler",
                               "moves": "serve_tokens_per_s",
                               "workloads": ["throwaway-cell"]})
    (copied_tree / "BENCHMARK.json").write_text(json.dumps(bench))
    env = {"PYTHONPATH": U.ROOT}
    rc, res, out, err = U.run_cell("throwaway-cell", 7, 2, root=str(copied_tree),
                                   extra_env=env)
    assert rc == 0, err[-2000:]
    assert res["correct"] is True and set(res["metrics"]) == {
        "serve_tokens_per_s", "setup_s"}
    rc, res, out, err = U.run_cell("throwaway-cell", 8, 4, trace=1,
                                   root=str(copied_tree), extra_env=env)
    assert rc == 0, err[-2000:]
    assert set(res["metrics"]) == {"finished_tokens_per_s.throwaway"}
    assert all(p.read_bytes() == data for p, data in before.items())


def test_a_model_of_another_class_cut_to_a_chips_share_is_new_files_only(
        copied_tree):
    """A throw-away builder, reference and FLOP module (copies under other
    names), and a configuration that holds an eighth of the vocabulary as one
    of 8 chips sharing a layer, run untraced and traced with every file that
    exists byte for byte as it was."""
    b = copied_tree / "benchmarks"
    before = {p: p.read_bytes() for p in b.rglob("*") if p.is_file()}
    for kind, new in (("builders", "throwaway_model"),
                      ("reference", "throwaway_reference"),
                      ("flops", "throwaway_flops")):
        shutil.copy(b / kind / "llama.py", b / kind / (new + ".py"))
    cfg = json.loads((b / "configs" / "deepseek-llm-7b.json").read_text())
    cfg.update(source="https://example.invalid/throw-away",
               builder="throwaway_model", reference="throwaway_reference",
               vocab_size=12800, reduced=["num_hidden_layers", "vocab_size"],
               chips_sharing_a_layer=8)
    cfg["published"]["vocab_size"] = 102400
    cfg["rehearse"]["vocab_size"] = 128
    (b / "configs" / "throwaway.json").write_text(json.dumps(cfg))
    assert all(rule(cfg["reduced"], cfg) == [] for rule in CUT_RULES.values())
    shutil.copy(b / "traffic" / "chat-backlog.json",
                b / "traffic" / "throwaway-mix.json")
    shutil.copy(b / "limits" / "deepseek-7b-serve-offline.json",
                b / "limits" / "throwaway-cell.json")
    clones = {"serve_step_mfu.sat": "throwaway_mfu",
              "paged_attention_roofline.sat": "throwaway_roofline",
              "batch_occupancy.sat": "batch_occupancy.throwaway"}
    for like, name in clones.items():
        spec = (b / "metrics" / (like + ".json")).read_text()
        (b / "metrics" / (name + ".json")).write_text(
            spec.replace('"llama"', '"throwaway_flops"'))
    bench = json.loads((copied_tree / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "throwaway", "source": cfg["source"],
                             "file": "benchmarks/configs/throwaway.json",
                             "reduced": cfg["reduced"], "why": "test"})
    bench["workloads"].append({"name": "throwaway-cell", "config": "throwaway",
                               "traffic": "throwaway-mix", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "serve_tokens_per_s":
            m["workloads"].append("throwaway-cell")
    bench["per_layer"] += [
        dict(m, name=clones[m["name"]], workloads=["throwaway-cell"])
        for m in bench["per_layer"] if m["name"] in clones]
    (copied_tree / "BENCHMARK.json").write_text(json.dumps(bench))
    env = {"PYTHONPATH": U.ROOT}
    rc, res, out, err = U.run_cell("throwaway-cell", 2 ** 31 + 9, 2,
                                   root=str(copied_tree), extra_env=env)
    assert rc == 0, err[-2000:]
    assert res["correct"] is True and set(res["metrics"]) == {
        "serve_tokens_per_s", "setup_s"}
    rc, res, out, err = U.run_cell("throwaway-cell", 10, 4, trace=1,
                                   root=str(copied_tree), extra_env=env)
    assert rc == 0, err[-2000:]
    # without a published peak the mfu and the roofline have nothing to read
    assert res["correct"] is True and set(res["metrics"]) == {
        "batch_occupancy.throwaway"}
    assert all(p.read_bytes() == data for p, data in before.items())


def test_a_configuration_that_names_no_builder_is_an_error(copied_tree):
    path = copied_tree / "benchmarks" / "configs" / "deepseek-llm-7b.json"
    cfg = json.loads(path.read_text())
    del cfg["builder"]
    path.write_text(json.dumps(cfg))
    rc, result, out, err = U.run_cell("deepseek-7b-serve-offline", 1, 1,
                                      root=str(copied_tree),
                                      extra_env={"PYTHONPATH": U.ROOT})
    assert rc != 0 and result is None and "'builder'" in err
    assert '"correct"' not in out


def test_only_builders_references_and_flop_counts_name_a_model():
    """Outside ``builders/``, ``reference/``, ``flops/`` and ``weights.py``
    nothing under ``benchmarks/`` imports a model's module of the program or
    names a model class: a cell's model is its builder's to know."""
    import paddle_tpu.models as models

    own = {"serving", "paged_kv", "radix_cache", "spec_decode"}   # the engine's
    modules = sorted(n[:-3] for n in os.listdir(os.path.dirname(models.__file__))
                     if n.endswith(".py") and n[:-3] not in own | {"__init__"})
    assert "llama" in modules
    banned = re.compile(
        r"models\.(%s)\b|models\s+import\s+(%s)\b|\b(Llama|GPT|Gpt|Bert|BERT)[A-Z]\w*"
        % ("|".join(modules), "|".join(modules)))
    checked = 0
    for base, dirs, files in os.walk(U.BENCH):
        rel = os.path.relpath(base, U.BENCH)
        if rel.split(os.sep)[0] in ("builders", "reference", "flops"):
            continue
        for name in files:
            if rel == "." and name == "weights.py" or name.endswith(".pyc"):
                continue
            with open(os.path.join(base, name)) as f:
                found = banned.search(f.read())
            assert not found, (os.path.join(rel, name), found.group(0))
            checked += 1
    assert checked > 40
