"""Metric files added after PR 33, on a program that lacks their
instrument: the parent's runs are made under the PR's benchmark files, and
a reader that finds nothing leaves its metric out of the line (PR 29 was
refused `benchmark_breaks_parent`)."""

import glob
import json
import os

import pytest

import run

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ADDED = ["distinct_keys_per_device_pass"]
CELLS = ["n64-inflight128", "n16-inflight512", "n16-inflight8",
         "n64-inflight8", "n64-clients1000"]
DIVISORS = {"committed": 4000.0, "device_items": 600000, "device_passes": 300,
            "verified_items": 610000}


def _spec(name):
    with open(os.path.join(BENCH, "metrics", name + ".json")) as fh:
        return json.load(fh)


def _seen(verify):
    return {"spans": {}, "counters": {"verify": verify, "wire": {},
                                      "clients": {}, "replicas": {}},
            "trace": {}, "divisors": DIVISORS}


@pytest.mark.parametrize("name", ADDED)
def test_a_program_without_the_counter_leaves_the_metric_out(name):
    # PR 33's surface, as the parent reports it
    parent = {"device_pass_items": 600000, "device_passes": 300,
              "device_shapes.native_prep_items": 600000,
              "device_shapes.post_warm_compiles": 0}
    assert run.read_metric(_spec(name), _seen(parent)) is None
    assert run.read_metric(_spec(name), _seen({})) is None


def test_distinct_keys_per_device_pass_is_the_quotient():
    spec = _spec("distinct_keys_per_device_pass")
    seen = _seen({"device_shapes.pass_distinct_keys": 81000})
    assert run.read_metric(spec, seen) == pytest.approx(270.0)
    seen["divisors"] = {**DIVISORS, "device_passes": 0}
    assert run.read_metric(spec, seen) is None  # no pass in the window


@pytest.mark.parametrize("name", ADDED)
def test_added_metric_has_the_accepted_form(name):
    """native_prep_item_share.json's keys (accepted with null on the
    parent's side, ledger PR 33), a counter source, no `cells`."""
    spec = _spec(name)
    assert set(spec) <= {"name", "unit", "better", "layer", "moves", "source",
                         "per", "scale"}
    kind, _, rest = spec["source"].partition(":")
    assert kind == "counter" and rest.startswith("verify.device_shapes.")
    assert spec["per"] in DIVISORS and "cells" not in spec


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reads_the_added_metrics(cell):
    names = [s["name"] for s in run.metric_specs(cell)]
    for name in ADDED:
        assert name in names
    assert os.path.exists(os.path.join(BENCH, "workloads", cell + ".json"))


def test_no_metric_file_uses_a_statistic_the_summary_lacks():
    for path in glob.glob(os.path.join(BENCH, "metrics", "*.json")):
        with open(path) as fh:
            source = json.load(fh)["source"]
        kind, _, rest = source.partition(":")
        assert kind in ("span", "counter", "trace"), path
        if kind == "span":
            assert rest.rpartition(":")[2] in ("mean", "sum", "p50", "p90", "p99")
