"""BENCHMARK.json names only things the harness can find, and keeps to the
shape of the benchmark's contract."""

import json
import re

from bench import run as R

SPEC = json.loads((R.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_keys_and_names():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    names = [c["name"] for c in SPEC["configs"]] + [w["name"] for w in SPEC["workloads"]] \
        + [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(set(names)) == len(names)
    assert all(NAME.match(n) for n in names)
    assert all(UNIT.match(m["unit"]) for m in SPEC["end_to_end"] + SPEC["per_layer"])
    assert 1 <= SPEC["run_seconds"] <= 51


def test_every_cell_resolves_by_name():
    for w in SPEC["workloads"]:
        cell = R.Cell(SPEC, w["name"])
        e2e = {m["name"] for m in cell.end_to_end()}
        assert "setup_s" in e2e and len(e2e) >= 2, w["name"]
        layer = cell.per_layer()
        assert layer, w["name"]
        for m in layer:
            assert (R.BENCH / "layer_metrics" / f"{m['name']}.py").exists()
            assert m["moves"] in e2e, (w["name"], m["name"])


def test_bounds_and_sources():
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert [m["bound"] for m in SPEC["end_to_end"] if m["name"] == "setup_s"] == [0.25]
    for m in SPEC["per_layer"]:
        assert "bound" not in m
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")


def test_configs_list_their_cuts():
    for c in SPEC["configs"]:
        cfg = json.loads((R.ROOT / c["file"]).read_text())
        assert cfg["reduced"] == c["reduced"]
        assert set(cfg["limits"])
        assert (R.BENCH / "configs" / f"{c['name']}.py").exists()
