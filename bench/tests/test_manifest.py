"""BENCHMARK.json keeps to the benchmark's contract: names, units, keys,
the cells' metrics, the share of four-chip cells and the time budget."""
import json
import re

import pytest

from bench.tests.conftest import REPO

MANIFEST = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {
    "configs": ({"name", "source", "file", "reduced", "why"}, set()),
    "workloads": ({"name", "config", "traffic", "chips", "why"}, set()),
    "end_to_end": ({"name", "unit", "better", "bound", "source"},
                   {"workloads"}),
    "per_layer": ({"name", "unit", "better", "source", "layer", "moves"},
                  {"workloads"}),
}


def line(text):
    return isinstance(text, str) and 1 <= len(text) <= 200 \
        and "\n" not in text and "\t" not in text


def reported(cell):
    return {m["name"] for m in MANIFEST["end_to_end"]
            if cell in m.get("workloads", [cell])}


def test_top_level_and_size():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert len((REPO / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    assert 1 <= len(MANIFEST["command"]) <= 32
    assert all(line(w) for w in MANIFEST["command"])
    for p in MANIFEST["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p)
        assert not p.startswith("/") and ".." not in p.split("/")


@pytest.mark.parametrize("section", sorted(KEYS))
def test_entries(section):
    need, extra = KEYS[section]
    names = [e["name"] for e in MANIFEST[section]]
    assert len(names) == len(set(names))
    for e in MANIFEST[section]:
        assert need <= set(e) <= need | extra, e["name"]
        assert NAME.match(e["name"]), e["name"]
        if "unit" in e:
            assert UNIT.match(e["unit"]) and e["better"] in ("lower",
                                                             "higher")
        for key in ("why", "layer", "source"):
            if key in e:
                assert line(e[key]), (e["name"], key)


def test_configs_files_and_use():
    used = {w["config"] for w in MANIFEST["workloads"]}
    files = [c["file"] for c in MANIFEST["configs"]]
    assert len(files) == len(set(files))
    for c in MANIFEST["configs"]:
        assert c["name"] in used
        assert any(c["file"].startswith(p + "/") for p in MANIFEST["paths"])
        body = json.loads((REPO / c["file"]).read_text())
        assert body["name"] == c["name"] and body["reduced"] == c["reduced"]
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])


@pytest.mark.parametrize("config", MANIFEST["configs"],
                         ids=lambda c: c["name"])
def test_config_has_tiny_sizes(config):
    """The CPU tests run a configuration at the sizes of its own ``tiny``
    object, never at its full size."""
    body = json.loads((REPO / config["file"]).read_text())
    tiny = body.get("tiny")
    assert isinstance(tiny, dict) and tiny, \
        f"{config['name']}: no tiny sizes for the CPU tests"
    assert set(tiny) <= set(body) - {"tiny"}, config["name"]


def test_cells():
    configs = {c["name"] for c in MANIFEST["configs"]}
    cells = MANIFEST["workloads"]
    pairs = {(w["config"], w["traffic"]) for w in cells}
    assert len(pairs) == len(cells)
    four = sum(w["chips"] == 4 for w in cells)
    assert four <= max(1, len(cells) // 2)
    for w in cells:
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert NAME.match(w["traffic"])
        mix = json.loads((REPO / "bench" / "traffic"
                          / f"{w['traffic']}.json").read_text())
        assert (REPO / "bench" / "drivers"
                / f"{mix['driver']}.py").exists()
        e2e = reported(w["name"])
        assert "setup_s" in e2e and len(e2e) >= 2
        assert any(w["name"] in m.get("workloads", [])
                   for m in MANIFEST["per_layer"])


def test_end_to_end_bounds_and_sources():
    for m in MANIFEST["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


@pytest.mark.parametrize("metric", MANIFEST["per_layer"],
                         ids=lambda m: m["name"])
def test_per_layer_metric(metric):
    assert metric["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
    assert (REPO / "bench" / "metrics" / f"{metric['name']}.py").exists()
    assert metric["moves"] in {m["name"] for m in MANIFEST["end_to_end"]}
    for cell in metric.get("workloads", []):
        assert metric["moves"] in reported(cell), cell
    if "roofline" in metric["name"]:
        assert metric["unit"] == "%"


def test_layers_are_named_alike():
    """Metrics of one module share its layer name letter for letter."""
    layers = {m["layer"] for m in MANIFEST["per_layer"]}
    assert all(line(x) for x in layers)
    lowered = {x.lower().strip() for x in layers}
    assert len(lowered) == len(layers)


def test_time_budget_holds_for_24_cells():
    s = MANIFEST["run_seconds"]
    assert isinstance(s, int) and 1 <= s <= 51
    cells = 24
    assert (2 + 14 * cells) * (s + 60) + cells * 2 * 90 + 1200 <= 43200
