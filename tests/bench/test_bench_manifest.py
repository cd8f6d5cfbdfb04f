"""BENCHMARK.json keeps to the benchmark's contract, and every name in it
finds its file."""
import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
MAN = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


def test_top_level_keys_and_limits():
    assert set(MAN) == KEYS
    assert 1 <= MAN["run_seconds"] <= 51
    assert MAN["command"][1].startswith("bench/")
    assert all(len(MAN[k]) >= 1 for k in KEYS - {"command", "run_seconds"})
    for p in MAN["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/") and ".." not in p
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def _entries():
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in MAN[kind]:
            yield kind, e


@pytest.mark.parametrize("kind,entry", list(_entries()),
                         ids=lambda x: x if isinstance(x, str) else x["name"])
def test_entry_names_and_keys(kind, entry):
    allowed = {
        "configs": {"name", "source", "file", "reduced", "why"},
        "workloads": {"name", "config", "traffic", "chips", "why"},
        "end_to_end": {"name", "unit", "better", "bound", "source",
                       "workloads"},
        "per_layer": {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"},
    }[kind]
    assert set(entry) <= allowed
    assert NAME.match(entry["name"])
    for k in ("config", "traffic"):
        if k in entry:
            assert NAME.match(entry[k])
    for k in entry.get("reduced", []):
        assert NAME.match(k)
    if "unit" in entry:
        assert UNIT.match(entry["unit"])
        assert entry["better"] in ("lower", "higher")
    for k in ("why", "layer", "source"):
        if k in entry:
            assert 1 <= len(entry[k]) <= 200 and "\n" not in entry[k]
            assert "\t" not in entry[k]


def test_names_unique_and_files_exist():
    for kind in ("configs", "workloads"):
        names = [e["name"] for e in MAN[kind]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in MAN["end_to_end"] + MAN["per_layer"]]
    assert len(metrics) == len(set(metrics))
    for m in metrics:
        assert (ROOT / "bench" / "metrics" / f"{m}.py").is_file()
    for c in MAN["configs"]:
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["source"] == c["source"]
        assert sorted(conf["reduced"]) == sorted(c["reduced"])
    for w in MAN["workloads"]:
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
        assert w["chips"] in (1, 4)
    assert sum(w["chips"] == 4 for w in MAN["workloads"]) <= max(
        1, len(MAN["workloads"]) // 2)


def test_every_cell_reports_what_it_needs():
    cells = [w["name"] for w in MAN["workloads"]]

    def reports(metric, cell):
        return cell in metric.get("workloads", cells)

    e2e = {m["name"]: m for m in MAN["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in MAN["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for cell in cells:
        mine = [n for n, m in e2e.items() if reports(m, cell)]
        assert "setup_s" in mine and len(mine) >= 2
        assert any(reports(m, cell) for m in MAN["per_layer"])
    layers = {}
    for m in MAN["per_layer"]:
        assert m["moves"] in e2e
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert reports(e2e[m["moves"]], cell), (m["name"], cell)
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
        layers.setdefault(m["layer"], []).append(m["name"])
