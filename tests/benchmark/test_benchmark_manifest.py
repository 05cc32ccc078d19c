"""BENCHMARK.json against the contract's limits, and every file it names:
they load and cross-refer.  The same checks pass on a temporary copy to
which a configuration, traffic mixes, cells and a per-layer metric were
added as files and entries, with every file that was there left as it
was."""

import filecmp
import json
import re
from pathlib import Path

import pytest

import bench_tiny
from benchmark.lib import manifest

ROOT = Path(__file__).resolve().parents[2]
MAN = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTHS = re.compile(r"hidden_size|intermediate|latent|state|proj|_dim$|_rank$|"
                    r"head_dim|n_embd|ffn|experts_per_tok", re.I)
FORBIDDEN = re.compile(r"gpt-?oss|gemma|llama|qwen3\.5", re.I)


def test_top_level_keys_and_limits():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert MAN["command"][:2] == ["python3", "benchmark/run.py"]
    assert MAN["paths"] == ["benchmark", "tests/benchmark"]
    assert isinstance(MAN["run_seconds"], int) and 1 <= MAN["run_seconds"] <= 51
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    n = len(MAN["workloads"])
    runs = 2 + 14 * 24                # the limit is what fits with 24 cells
    assert runs * (MAN["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert 1 <= n <= 24 and 1 <= len(MAN["configs"]) <= 24


def check_config(root, man, cfg):
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(cfg["name"]) and len(cfg["why"]) <= 200
    assert not FORBIDDEN.search(cfg["name"] + cfg["source"])
    assert cfg["file"].startswith("benchmark/")
    body = json.loads((root / cfg["file"]).read_text())
    assert body["source"] == cfg["source"]
    assert body["reduced"] == cfg["reduced"] and len(cfg["reduced"]) <= 16
    for key in cfg["reduced"]:
        assert NAME.match(key) and key in body
        assert not WIDTHS.search(key), f"{key} is a width"
    assert isinstance(body["assumed"], dict)
    assert (root / "benchmark" / "reference"
            / f"{body['reference']}.py").exists()
    assert any(w["config"] == cfg["name"] for w in man["workloads"])


@pytest.mark.parametrize("cfg", MAN["configs"], ids=lambda c: c["name"])
def test_config_entry_and_file(cfg):
    assert cfg["source"].startswith("https://")
    check_config(ROOT, MAN, cfg)


def test_config_files_are_distinct():
    files = [c["file"] for c in MAN["configs"]]
    assert len(set(files)) == len(files)


def check_cell(root, cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert cell["chips"] in (1, 4) and 1 <= len(cell["why"]) <= 200
    loaded = manifest.load_cell(root, cell["name"])
    assert loaded.traffic["driver"] in ("train_steps", "open_loop",
                                        "closed_loop")
    names = {m["name"] for m in loaded.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert loaded.per_layer and set(loaded.readers) == {
        m["name"] for m in loaded.per_layer}
    assert all(callable(r) for r in loaded.readers.values())
    for m in loaded.per_layer:      # what it moves is reported here too
        assert m["moves"] in names


@pytest.mark.parametrize("cell", MAN["workloads"], ids=lambda w: w["name"])
def test_cell_loads_with_all_its_files(cell):
    check_cell(ROOT, cell)


def test_cells_are_unique_and_few_take_four_chips():
    pairs = [(w["config"], w["traffic"]) for w in MAN["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(w["chips"] == 4 for w in MAN["workloads"])
    assert four <= max(1, len(pairs) // 4)


def check_metric(root, man, m):
    e2e = m in man["end_to_end"]
    keys = {"name", "unit", "better", "source"} | (
        {"bound"} if e2e else {"layer", "moves"})
    assert keys <= set(m) <= keys | {"workloads"}
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    cells = {w["name"] for w in man["workloads"]}
    assert set(m.get("workloads", cells)) <= cells
    if e2e:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    else:
        assert m["moves"] in {x["name"] for x in man["end_to_end"]}
        # the metric's own file says how it is read and nothing that
        # BENCHMARK.json owns: a cell added to the metric edits no file
        spec = json.loads((root / "benchmark" / "layer_metrics"
                           / f"{m['name']}.json").read_text())
        assert set(spec) == {"name", "reader", "what"}
        assert spec["name"] == m["name"] and "." in spec["reader"]
        if "roofline" in m["name"]:
            assert m["unit"] == "%"


@pytest.mark.parametrize("m", MAN["end_to_end"] + MAN["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_entry(m):
    check_metric(ROOT, MAN, m)


def test_metric_names_are_unique_and_setup_is_there():
    names = [m["name"] for m in MAN["end_to_end"] + MAN["per_layer"]]
    assert len(set(names)) == len(names)
    setup = [m for m in MAN["end_to_end"] if m["name"] == "setup_s"]
    assert setup and setup[0]["bound"] <= 0.1 and "workloads" not in setup[0]


def test_every_layer_metric_file_is_listed():
    listed = {m["name"] for m in MAN["per_layer"]}
    on_disk = {p.stem for p in
               (ROOT / "benchmark" / "layer_metrics").glob("*.json")}
    assert on_disk == listed


def test_a_copy_with_added_files_and_entries_passes_the_same_checks(tmp_path):
    """What a later PR does: a configuration, a mix, a cell and a metric
    come as new files and new entries; cells join a metric's ``workloads``
    in ``BENCHMARK.json``; no file that was there changes."""
    copy = bench_tiny.make_copy(tmp_path)
    man = json.loads((copy / "BENCHMARK.json").read_text())
    assert len(man["workloads"]) > len(MAN["workloads"])
    assert len(man["configs"]) > len(MAN["configs"])
    assert len(man["per_layer"]) == len(MAN["per_layer"]) + 1
    for cfg in man["configs"]:
        check_config(copy, man, cfg)
    for cell in man["workloads"]:
        check_cell(copy, cell)
    for m in man["end_to_end"] + man["per_layer"]:
        check_metric(copy, man, m)

    def unchanged(rel):
        cmp = filecmp.dircmp(ROOT / rel, copy / rel, ignore=["__pycache__"])
        assert not cmp.diff_files and not cmp.left_only, (
            rel, cmp.diff_files, cmp.left_only)
        for sub in cmp.common_dirs:
            unchanged(Path(rel) / sub)
    unchanged("benchmark")
    # entries that were there keep every key but a longer `workloads`
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        for was, now in zip(MAN[kind], man[kind]):
            assert {k: v for k, v in now.items() if k != "workloads"} == \
                {k: v for k, v in was.items() if k != "workloads"}
            assert now.get("workloads", [])[:len(was.get("workloads", []))] \
                == was.get("workloads", [])
