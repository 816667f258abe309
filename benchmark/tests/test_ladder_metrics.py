"""PR 36's data files: the deployment pbft-n16-clients4096, its cell, and
the three metrics of the table-free verify program. In the form of
test_added_metrics.py: on a program that lacks the instrument (the
parent, whose runs are made under this PR's benchmark files) a reader
finds nothing and leaves its metric out of the line."""

import json
import os

import pytest

import run

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COUNTED = ["ladder_item_share", "ladder_items_per_device_pass"]
SPAN = "ladder_round_trip_mean_ms"
NEW_CELL = "n16-clients4096"
STANDING = ["n64-inflight128", "n16-inflight512", "n16-inflight8",
            "n64-inflight8", "n64-clients1000"]
DIVISORS = {"committed": 14000.0, "device_items": 300000, "device_passes": 250,
            "verified_items": 303000}


def _json(*parts):
    with open(os.path.join(BENCH, *parts)) as fh:
        return json.load(fh)


def _spec(name):
    return _json("metrics", name + ".json")


def _seen(verify, spans=None):
    return {"spans": spans or {}, "counters": {"verify": verify, "wire": {},
                                               "clients": {}, "replicas": {}},
            "trace": {}, "divisors": DIVISORS}


@pytest.mark.parametrize("name", COUNTED + [SPAN])
def test_a_program_without_the_ladder_leaves_the_metric_out(name):
    # PR 35's surface, as the parent reports it
    parent = {"device_pass_items": 300000, "device_passes": 250,
              "device_shapes.native_prep_items": 300000,
              "device_shapes.pass_distinct_keys": 50000,
              "device_shapes.overcap_fallback_items": 120000}
    spans = {"verify.device": {"count": 250, "mean": 40.0}}
    assert run.read_metric(_spec(name), _seen(parent, spans)) is None
    assert run.read_metric(_spec(name), _seen({})) is None


def test_the_counted_metrics_are_the_quotients():
    seen = _seen({"device_shapes.ladder_items": 129000})
    assert run.read_metric(_spec("ladder_item_share"), seen) == pytest.approx(43.0)
    assert run.read_metric(
        _spec("ladder_items_per_device_pass"), seen) == pytest.approx(516.0)
    seen["divisors"] = {**DIVISORS, "device_items": 0, "device_passes": 0}
    for name in COUNTED:  # no pass in the window
        assert run.read_metric(_spec(name), seen) is None


@pytest.mark.parametrize("name", COUNTED)
def test_a_population_that_fits_its_bank_reads_zero_not_nothing(name):
    """The five standing cells: the counter is there and did not move, so
    the line carries 0 and a change that sent tabled keys down the ladder
    would show."""
    assert run.read_metric(
        _spec(name), _seen({"device_shapes.ladder_items": 0})) == 0


def test_the_round_trip_is_the_spans_mean_and_absent_where_no_ladder_ran():
    spec = _spec(SPAN)
    ran = {"verify.ladder": {"count": 240, "mean": 61.5, "p50": 64.0}}
    assert run.read_metric(spec, _seen({}, ran)) == pytest.approx(61.5)
    idle = {"verify.ladder": {"count": 0, "mean": 0.0}}
    assert run.read_metric(spec, _seen({}, idle)) is None


@pytest.mark.parametrize("name", COUNTED + [SPAN])
def test_the_metric_has_the_accepted_form(name):
    spec = _spec(name)
    assert set(spec) <= {"name", "unit", "better", "layer", "moves", "source",
                         "per", "scale", "cells"}
    assert spec["name"] == name
    kind, _, rest = spec["source"].partition(":")
    if name == SPAN:
        assert (kind, rest) == ("span", "verify.ladder:mean")
        assert spec["cells"] == [NEW_CELL] and spec["layer"] == "kernel"
        assert spec["moves"] == "commit_latency_p50_ms"
    else:
        assert (kind, rest) == ("counter", "verify.device_shapes.ladder_items")
        assert spec["per"] in DIVISORS and "cells" not in spec
        assert spec["layer"] == "verifier host side"
        assert spec["moves"] == "committed_req_per_s"


@pytest.mark.parametrize("cell", STANDING + [NEW_CELL])
def test_which_cells_read_which_metric(cell):
    names = [s["name"] for s in run.metric_specs(cell)]
    for name in COUNTED:
        assert name in names
    # a span metric is listed only where its stage has something to read
    assert (SPAN in names) == (cell == NEW_CELL)


def test_the_cell_and_its_deployment_as_the_issue_names_them():
    cell = _json("workloads", NEW_CELL + ".json")
    assert {k: cell[k] for k in (
        "name", "config", "traffic", "chips", "loop", "in_flight",
        "warmup_seconds", "trace_seconds", "gets", "drain_timeout_s")} == {
        "name": NEW_CELL, "config": "pbft-n16-clients4096",
        "traffic": "inflight4096", "chips": 1, "loop": "closed",
        "in_flight": 4096, "warmup_seconds": 20.0, "trace_seconds": 6.0,
        "gets": 64, "drain_timeout_s": 60.0}
    config = _json("configs", cell["config"] + ".json")
    assert (config["n"], config["f"], config["clients"], config["block"]) == (
        16, 5, 4096, 100)
    assert config["n"] == 3 * config["f"] + 1
    assert cell["in_flight"] == config["clients"] <= config["keys"] == 8192
    assert config["view_timeout_s"] == config["request_timeout_s"] == 60.0
    assert sorted(config["reduced"]) == [
        "clients", "keys", "message_delay_ms", "processes"]
    assert len(config["source"]) <= 200 and len(cell["why"]) <= 200
    # everything else is the accepted n=16 deployment's
    base = _json("configs", "pbft-n16-ed25519.json")
    for key in ("signatures", "transport", "app", "operation", "guarantees",
                "checkpoint_interval", "watermark_window", "speculative",
                "kernel_check", "platform"):
        assert config[key] == base[key], key
    assert {k: v for k, v in config["verifier"].items()
            if k not in ("key_bank", "signing_keys")} == {
        k: v for k, v in base["verifier"].items()
        if k not in ("key_bank", "signing_keys")}


def test_the_bank_the_file_states_is_the_bank_the_program_builds():
    """1,814 keys: 45% of a v5e's bytes_limit in 4 MiB tables; and more
    than half the signers are past it, which is what defines the cell."""
    from simple_pbft_tpu.crypto import tpu_verifier as tv

    config = _json("configs", "pbft-n16-clients4096.json")
    signing = config["n"] + config["clients"]
    assert config["verifier"]["signing_keys"] == signing == 4112
    cap = tv.bank_capacity(signing + 32, 16_909_336_064)
    assert cap == config["verifier"]["key_bank"] == 1814
    assert cap * tv.KEY_BYTES == 7_608_467_456
    assert (signing - cap) / config["clients"] > 0.5
