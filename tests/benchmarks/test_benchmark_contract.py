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


def test_every_configuration_has_a_cell_and_a_file_with_only_depth_reduced():
    used = {w["config"] for w in BENCH["workloads"]}
    files = set()
    for c in BENCH["configs"]:
        assert c["name"] in used
        assert c["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
        assert c["file"] not in files
        files.add(c["file"])
        with open(os.path.join(U.ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
        assert c["reduced"] == ["num_hidden_layers"]
        assert cfg["num_hidden_layers"] < cfg["published"]["num_hidden_layers"]
        assert cfg["bytes"]["parameters"] > 0


def test_every_file_a_cell_names_exists():
    for w in BENCH["workloads"]:
        cfg_entry = next(c for c in BENCH["configs"] if c["name"] == w["config"])
        with open(os.path.join(U.ROOT, cfg_entry["file"])) as f:
            cfg = json.load(f)
        with open(os.path.join(U.BENCH, "traffic", w["traffic"] + ".json")) as f:
            traffic = json.load(f)
        for kind, name in (("drivers", cfg["driver"]),
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
    for kind in ("drivers", "generators", "readers", "reference", "flops"):
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
