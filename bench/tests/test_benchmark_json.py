"""BENCHMARK.json names only what the harness can find by name."""

import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in BENCH[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])


def test_every_cell_finds_its_files():
    from bench.run import load_cell

    for w in BENCH["workloads"]:
        assert len(w["why"]) <= 200
        cell = load_cell(w["name"], ROOT)
        assert cell.per_layer and len(cell.end_to_end) >= 2
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_every_per_layer_metric_has_a_reader(metric):
    import importlib

    assert callable(importlib.import_module(f"bench.metrics.{metric}").read)


@pytest.mark.parametrize("conf", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_states_its_cut(conf):
    spec = json.loads((ROOT / conf["file"]).read_text())
    assert spec["reduced"] == conf["reduced"]
    assert spec["source"] == conf["source"]
    for key in conf["reduced"]:
        assert spec["published"][key] != spec[key]
