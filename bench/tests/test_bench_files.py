"""The benchmark's data files: every cell names an existing
configuration, traffic mix, driver and metric readers; names and units
use only the allowed characters; ``BENCHMARK.json`` agrees with the
files; and a new cell is a new file that the harness picks up."""
import glob
import json
import os
import shutil

import pytest

from bench import harness

BENCH = harness.BENCH
ROOT = harness.ROOT
CELLS = sorted(os.path.basename(p)[:-5]
               for p in glob.glob(os.path.join(BENCH, "workloads", "*.json")))


def _benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_resolve(name):
    cell = harness.load_cell(name)
    assert harness.NAME_RE.match(cell.name)
    assert cell.spec["config"] == cell.config["name"]
    assert cell.spec["traffic"] == cell.traffic["name"]
    assert cell.chips in (1, 4)
    driver = harness.load_module("drivers", cell.traffic["driver"])
    for fn in ("setup", "window", "release", "check"):
        assert callable(getattr(driver, fn))
    assert "setup_s" in cell.end_to_end
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for metric in cell.per_layer:
        assert callable(harness.load_module("metrics", metric).read)
    why = cell.spec["why"]
    assert 1 <= len(why) <= 200 and "\n" not in why and "\t" not in why


def _one_line(text, most=200):
    return 1 <= len(text) <= most and "\n" not in text and "\t" not in text


def test_benchmark_json_matches_the_files():
    bm = _benchmark()
    assert set(bm) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert bm["paths"] == ["bench"]
    assert bm["command"] == ["python3", "bench/run.py"]
    assert isinstance(bm["run_seconds"], int) and 1 <= bm["run_seconds"] <= 51
    assert sorted(w["name"] for w in bm["workloads"]) == CELLS
    configs = {c["name"]: c for c in bm["configs"]}
    assert len(configs) == len(bm["configs"])
    for c in bm["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"] == f"bench/configs/{c['name']}.json"
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert (c["reduced"], c["source"]) == (cfg["reduced"], cfg["source"])
        assert _one_line(c["why"]) and _one_line(c["source"])
    used = {w["config"] for w in bm["workloads"]}
    assert used == set(configs)
    for w in bm["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        cell = harness.load_cell(w["name"])
        assert (w["config"], w["traffic"], w["chips"], w["why"]) == (
            cell.spec["config"], cell.spec["traffic"], cell.chips,
            cell.spec["why"])
        assert harness.NAME_RE.match(w["traffic"])
        assert "setup_s" in cell.end_to_end and len(cell.end_to_end) >= 2
        assert cell.per_layer
    names = [m["name"] for m in bm["end_to_end"] + bm["per_layer"]]
    assert len(names) == len(set(names))
    for m in bm["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bm["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert _one_line(m["layer"])
        for cell_name in m.get("workloads", []):
            assert m["moves"] in harness.load_cell(cell_name).end_to_end
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert harness.NAME_RE.match(m["name"])
        assert harness.UNIT_RE.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= set(CELLS)


def test_a_new_cell_file_is_picked_up(tmp_path):
    """A later cell is new files plus new entries in BENCHMARK.json: no
    file under bench/ changes."""
    root = tmp_path / "bench"
    shutil.copytree(BENCH, root, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    spec = json.loads((root / "workloads" / "char.hbm-stream.json")
                      .read_text())
    spec["name"] = "char.hbm-small"
    (root / "workloads" / "char.hbm-small.json").write_text(json.dumps(spec))
    bm = _benchmark()
    bm["workloads"].append({k: spec[k] for k in
                            ("name", "config", "traffic", "chips", "why")})
    for m in bm["end_to_end"] + bm["per_layer"]:
        if "char.hbm-stream" in m.get("workloads", []):
            m["workloads"].append("char.hbm-small")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))
    cell = harness.load_cell("char.hbm-small", root=str(root))
    want = harness.load_cell("char.hbm-stream")
    assert cell.name == "char.hbm-small"
    assert (cell.config, cell.traffic) == (want.config, want.traffic)
    assert (cell.end_to_end, cell.per_layer) == (want.end_to_end,
                                                  want.per_layer)
    after = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()
             and p.name != "char.hbm-small.json"}
    assert after == before


def test_unknown_names_are_refused():
    with pytest.raises(harness.BenchError):
        harness.load_cell("no.such-cell")
    with pytest.raises(harness.BenchError):
        harness.load_cell("../configs/qwen2-1.5b")
    with pytest.raises(harness.BenchError):
        harness.load_module("metrics", "no_such_metric")
