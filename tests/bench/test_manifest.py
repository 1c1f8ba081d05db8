"""BENCHMARK.json and the files it names: every cell, configuration,
driver and metric reader resolves by name, and every entry keeps to the
benchmark's naming and shape rules."""
import importlib
import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
CELL_FILES = sorted((ROOT / "bench" / "workloads").glob("*.json"))
CONFIG_FILES = sorted((ROOT / "bench" / "configs").glob("*.json"))


def _line(s, n=200):
    return isinstance(s, str) and 1 <= len(s) <= n and "\n" not in s \
        and "\t" not in s


def test_top_level_shape():
    assert set(MANIFEST) == TOP_KEYS
    assert len(json.dumps(MANIFEST)) <= 64 * 1024
    assert 1 <= len(MANIFEST["paths"]) <= 16
    for p in MANIFEST["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (ROOT / p).is_dir()
    cmd = MANIFEST["command"]
    assert 1 <= len(cmd) <= 32 and all(_line(w) for w in cmd)
    for w in cmd[1:]:
        assert any(w == p or w.startswith(p + "/")
                   for p in MANIFEST["paths"])
    assert isinstance(MANIFEST["run_seconds"], int)
    assert 1 <= MANIFEST["run_seconds"] <= 51


def test_names_units_and_keys():
    seen = set()
    for group, keys in (("configs", {"name", "source", "file", "reduced",
                                     "why"}),
                        ("workloads", {"name", "config", "traffic", "chips",
                                       "why"}),
                        ("end_to_end", {"name", "unit", "better", "bound",
                                        "source"}),
                        ("per_layer", {"name", "unit", "better", "source",
                                       "layer", "moves"})):
        for e in MANIFEST[group]:
            assert set(e) - {"workloads"} == keys, e
            assert NAME.match(e["name"]), e["name"]
            assert (group, e["name"]) not in seen
            seen.add((group, e["name"]))
            if "unit" in e:
                assert UNIT.match(e["unit"]) and e["better"] in (
                    "lower", "higher")
            for k in ("why", "layer", "source"):
                assert k not in e or _line(e[k]), (k, e)
    for e in MANIFEST["end_to_end"]:
        assert e["source"] in ("host_clock", "device_trace")
        assert 0.01 <= e["bound"] <= 0.25
    for e in MANIFEST["per_layer"]:
        assert e["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert _line(e["layer"])


def test_every_cell_and_config_file_is_listed_and_resolves():
    cells = {c["name"]: c for c in MANIFEST["workloads"]}
    configs = {c["name"]: c for c in MANIFEST["configs"]}
    assert {f.stem for f in CELL_FILES} >= set(cells)
    assert {f.stem for f in CONFIG_FILES} >= set(configs)
    pairs = set()
    for name, c in cells.items():
        wl = json.loads((ROOT / "bench" / "workloads"
                         / f"{name}.json").read_text())
        assert wl["config"] == c["config"] and wl["chips"] == c["chips"]
        assert wl["why"] == c["why"] and _line(c["why"])
        assert c["chips"] in (1, 4) and NAME.match(c["traffic"])
        assert (c["config"], c["traffic"]) not in pairs
        pairs.add((c["config"], c["traffic"]))
        mod = importlib.import_module(f"bench.drivers.{wl['driver']}")
        assert hasattr(mod.Driver, "unit")
        assert wl["limits"] and all(v >= 0 for v in wl["limits"].values())
    for name, c in configs.items():
        assert c["file"] == f"bench/configs/{name}.json"
        assert any(c["file"].startswith(p + "/") for p in MANIFEST["paths"])
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == name and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"] and _line(c["source"])
        assert any(x["config"] == name for x in cells.values())
    four = sum(c["chips"] == 4 for c in cells.values())
    assert four <= max(1, len(cells) // 2)


def _reports(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def test_every_metric_has_a_reader_and_matches_its_cells():
    from bench.run import metric_reader
    cells = [c["name"] for c in MANIFEST["workloads"]]
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for cell in cells:
        assert sum(_reports(m, cell) for m in e2e.values()) >= 2
        assert any(_reports(m, cell) for m in MANIFEST["per_layer"])
    layers = {}
    for m in MANIFEST["per_layer"]:
        assert callable(metric_reader(m["name"]))
        moved = e2e[m["moves"]]
        reporting = [c for c in cells if _reports(moved, c)]
        assert set(m.get("workloads", reporting)) <= set(reporting), m
        if m["unit"] == "%" and (m["name"].endswith("_roofline")
                                 or "mfu" in m["name"]):
            assert m["better"] == "higher"
        layers.setdefault(m["layer"], []).append(m["name"])
    assert "kernels" in layers and "device" in layers


@pytest.mark.parametrize("cell", [c["name"] for c in MANIFEST["workloads"]])
def test_cell_reports_the_end_to_end_metric_its_driver_counts(cell):
    from bench.run import cell_metrics, driver_class, load_cell
    wl, _ = load_cell(cell)
    unit = driver_class(wl["driver"]).unit
    names = {m["name"] for m in cell_metrics(MANIFEST, cell, False)}
    assert names == {"setup_s", f"{unit}_per_s"}


def test_run_seconds_fit_a_full_check():
    per_run = MANIFEST["run_seconds"] + 60
    cells = 24
    assert (2 + 14 * cells) * per_run + cells * 2 * 90 + 1200 <= 43200
