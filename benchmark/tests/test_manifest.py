"""BENCHMARK.json and the data files under benchmark/ against the
contract's limits, before any chip call: PR 23 died on one string."""

import glob
import json
import os
import re

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")
LINE = re.compile(r"[\x20-\x7e]{1,200}")  # one line, printable ASCII, no tab
PATH = re.compile(r"[A-Za-z0-9_.\-/]{1,200}")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
READERS = {"span": "program_span", "counter": "program_counter",
           "trace": "device_trace"}
# shapes of the deployment, which `reduced` may never name
WIDTHS = {"n", "f", "signatures", "operation", "guarantees", "block"}


def _json(*parts):
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def manifest():
    return _json(REPO, "BENCHMARK.json")


def _files(kind):
    return {os.path.basename(p)[:-5]: _json(p)
            for p in glob.glob(os.path.join(BENCH, kind, "*.json"))}


def test_top_level(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    assert manifest["paths"] == ["benchmark"]
    assert 1 <= len(manifest["command"]) <= 32
    for word in manifest["command"]:
        assert LINE.fullmatch(word) and not word.startswith("/")
        assert ".." not in word.split("/")
    assert manifest["command"][1].startswith("benchmark/")
    rs = manifest["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    # a full check with the full 24 cells fits the driver's budget
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_configs(manifest):
    configs = manifest["configs"]
    assert 1 <= len(configs) <= 24
    names = [c["name"] for c in configs]
    assert len(set(names)) == len(names)
    assert len({c["file"] for c in configs}) == len(configs)
    used = {w["config"] for w in manifest["workloads"]}
    for c in configs:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.fullmatch(c["name"]) and c["name"] in used
        assert LINE.fullmatch(c["source"]), len(c["source"])
        assert LINE.fullmatch(c["why"]), len(c["why"])
        assert PATH.fullmatch(c["file"]) and c["file"].startswith("benchmark/")
        doc = _json(REPO, c["file"])
        assert doc["name"] == c["name"] and doc["source"] == c["source"]
        assert doc["platform"] == "tpu"
        assert len(c["reduced"]) <= 16
        assert sorted(c["reduced"]) == sorted(doc["reduced"])
        for key in c["reduced"]:
            assert NAME.fullmatch(key) and key in doc
            assert key not in WIDTHS and not key.endswith(("_dim", "_rank"))
        assert doc["guarantees"] and all(LINE.fullmatch(g) for g in doc["guarantees"])
        assert doc["n"] == 3 * doc["f"] + 1


def test_workloads(manifest):
    cells = manifest["workloads"]
    assert 1 <= len(cells) <= 24
    configs = {c["name"] for c in manifest["configs"]}
    assert len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    four = sum(1 for w in cells if w["chips"] == 4)
    assert four <= max(1, len(cells) // 2)
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        for key in ("name", "config", "traffic"):
            assert NAME.fullmatch(w[key]), w[key]
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert LINE.fullmatch(w["why"]), len(w["why"])
        doc = _json(BENCH, "workloads", w["name"] + ".json")
        for key in ("name", "config", "traffic", "chips", "why"):
            assert doc[key] == w[key], (w["name"], key)
        config = _json(BENCH, "configs", doc["config"] + ".json")
        assert doc["in_flight"] <= config["keys"]
        assert doc["loop"] == "closed"


def test_every_workload_file_names_a_config_file():
    configs = _files("configs")
    for name, doc in _files("workloads").items():
        assert doc["name"] == name and NAME.fullmatch(name)
        assert doc["config"] in configs
        assert LINE.fullmatch(doc["why"])
    for name, doc in configs.items():
        assert doc["name"] == name and NAME.fullmatch(name)
        assert LINE.fullmatch(doc["source"]), len(doc["source"])


def test_metrics(manifest):
    e2e = manifest["end_to_end"]
    layer = manifest["per_layer"]
    assert 1 <= len(e2e) <= 16 and 1 <= len(layer) <= 128
    names = [m["name"] for m in e2e + layer]
    assert len(set(names)) == len(names)
    cells = {w["name"] for w in manifest["workloads"]}
    reported = {}  # end-to-end metric -> cells that report it
    for m in e2e:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in {"host_clock", "device_trace"}
        assert 0.01 <= m["bound"] <= 0.25
        reported[m["name"]] = set(m.get("workloads", cells))
    assert reported["setup_s"] == cells
    files = _files("metrics")
    assert sorted(files) == sorted(m["name"] for m in layer)
    for m in layer:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert LINE.fullmatch(m["layer"])
        doc = files[m["name"]]
        for key in ("name", "unit", "better", "layer", "moves"):
            assert doc[key] == m[key], (m["name"], key)
        assert READERS[doc["source"].split(":")[0]] == m["source"]
        assert doc.get("cells") == m.get("workloads")
        # `moves` is an end-to-end metric that each of its cells reports
        assert set(m.get("workloads", cells)) <= reported[m["moves"]]
    for m in e2e + layer:
        assert NAME.fullmatch(m["name"]) and UNIT.fullmatch(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert set(m.get("workloads", cells)) <= cells
    for cell in cells:  # setup_s, another end-to-end metric, a per-layer one
        assert sum(1 for m in e2e if cell in m.get("workloads", cells)) >= 2
        assert any(cell in m.get("workloads", cells) for m in layer)


def test_rehearsal_files_are_not_in_the_manifest(manifest):
    assert "rehearsal" not in {w["name"] for w in manifest["workloads"]}
    assert "rehearsal-n4" not in {c["name"] for c in manifest["configs"]}
    assert _json(BENCH, "configs", "rehearsal-n4.json")["platform"] == "cpu"
    assert _json(BENCH, "workloads", "rehearsal.json")["config"] == "rehearsal-n4"


def test_files_under_paths_are_named_from_a_names_characters():
    for root, dirs, files in os.walk(BENCH):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for name in files + dirs:
            assert re.fullmatch(r"[A-Za-z0-9_.-]+", name), os.path.join(root, name)


def test_peaks_table():
    doc = _json(BENCH, "peaks.json")
    assert doc["source"] == "Google Cloud documentation, TPU v5e"
    assert doc["peaks"]["TPU v5 lite"] == {
        "bf16_tflop_per_s": 197, "int8_top_per_s": 393,
        "hbm_gbyte_per_s": 819, "hbm_gbyte": 16}
    import run

    assert run.device_peaks({"device_kind": "TPU v5 lite"})["hbm_gbyte"] == 16
    with pytest.raises(SystemExit, match="not in peaks.json"):
        run.device_peaks({"device_kind": "TPU v9 imagined"})
