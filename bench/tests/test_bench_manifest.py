"""BENCHMARK.json against the benchmark's contract: names and units use only
the allowed characters; every per-layer metric's ``moves`` is reported by
every cell that reports the metric; every file a cell needs exists; the run
length fits a full check."""

import json
import re

import pytest

from benchkit import BENCH, REPO

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    return json.loads((REPO / "BENCHMARK.json").read_text())


def test_top_level_keys(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["paths"] == ["bench"]
    assert manifest["command"][1] == "bench/run.py"
    assert (REPO / "BENCHMARK.json").stat().st_size <= 64 * 1024


def test_names_and_units(manifest):
    names = []
    for c in manifest["configs"]:
        names.append(c["name"])
        assert all(NAME.match(k) for k in c["reduced"])
    for w in manifest["workloads"]:
        names.append(w["name"])
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        names.append(m["name"])
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    assert all(NAME.match(n) for n in names), names
    kinds = [manifest["configs"], manifest["workloads"],
             manifest["end_to_end"] + manifest["per_layer"]]
    for group in kinds:
        assert len({x["name"] for x in group}) == len(group)


def test_moves_is_reported_where_the_metric_is(manifest):
    cells = [w["name"] for w in manifest["workloads"]]
    e2e = {m["name"]: set(m.get("workloads", cells))
           for m in manifest["end_to_end"]}
    assert "setup_s" in e2e
    for m in manifest["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.25
    layers = {}
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e, m
        where = set(m.get("workloads", cells))
        assert where <= e2e[m["moves"]], (m["name"], where - e2e[m["moves"]])
        layers.setdefault(m["layer"], []).append(m["name"])
        assert "\n" not in m["layer"] and "\t" not in m["layer"]
    for cell in cells:
        assert any(cell in s for n, s in e2e.items() if n != "setup_s")
        assert any(cell in set(m.get("workloads", cells))
                   for m in manifest["per_layer"])


def test_every_file_a_cell_names_exists(manifest):
    for c in manifest["configs"]:
        f = REPO / c["file"]
        assert f.is_file() and f.with_suffix(".py").is_file()
        assert (BENCH / "reference" / f"{c['name']}.py").is_file()
        cfg = json.loads(f.read_text())
        assert set(c["reduced"]) == set(cfg["reduced"])
    for w in manifest["workloads"]:
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        limits = json.loads(
            (BENCH / "limits" / f"{w['name']}.json").read_text())
        assert limits["limits"] and \
            set(limits["limits"]) <= {"loss", "grad", "change"}
    for m in manifest["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
    peaks = json.loads((BENCH / "peaks.json").read_text())
    assert "TPU v5 lite" in peaks["devices"]


def test_run_length_fits_a_full_check_of_24_cells(manifest):
    s = manifest["run_seconds"]
    assert isinstance(s, int) and 1 <= s <= 51
    assert (2 + 14 * 24) * (s + 60) + 24 * 2 * 90 + 1200 <= 43200
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, len(manifest["workloads"]) // 2)
