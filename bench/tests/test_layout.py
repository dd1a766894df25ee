"""Everything a cell names is found by name, and BENCHMARK.json keeps the
shape later PRs add to."""
import json
import re
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_every_cell_has_its_files():
    configs = {c["name"]: c for c in SPEC["configs"]}
    for w in SPEC["workloads"]:
        assert (ROOT / configs[w["config"]]["file"]).is_file()
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
        assert (ROOT / "bench" / "checks" / f"{w['name']}.json").is_file()


def test_every_per_layer_metric_has_a_reader():
    for m in SPEC["per_layer"]:
        assert (ROOT / "bench" / "layer_metrics" / f"{m['name']}.py").is_file()


def test_names_and_moves():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    cells = {w["name"] for w in SPEC["workloads"]}
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for x in SPEC[group]:
            assert NAME.match(x["name"]), x["name"]
    for m in SPEC["per_layer"]:
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in moved.get("workloads", cells), (m["name"], cell)


def test_config_files_state_no_cut():
    for c in SPEC["configs"]:
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["reduced"] == c["reduced"] == []
        assert conf["source"] == c["source"]
