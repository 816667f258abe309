"""The three generic readers: a per-layer metric is a data file, and a
reader that finds nothing returns nothing."""

import pytest

import run
import stages

SEEN = {
    "spans": {"verify.host_prep": {"count": 4, "mean": 2.5, "p50": 3.0},
              "phase.commit": {"count": 0, "mean": 0.0, "p50": 0.0}},
    "counters": {"verify": {"device_pass_items": 900, "device_shapes.post_warm_compiles": 0},
                 "clients": {"spec_accepted": 30}},
    "trace": {"kernel_ms_per_pass": 9.03},
    "divisors": {"committed": 40, "device_items": 900, "device_passes": 0},
}


@pytest.mark.parametrize("spec, want", [
    ({"source": "span:verify.host_prep:p50"}, 3.0),
    ({"source": "span:verify.host_prep:sum", "per": "device_items", "scale": 1000},
     1000 * 10.0 / 900),
    ({"source": "counter:clients.spec_accepted", "per": "committed", "scale": 100}, 75.0),
    ({"source": "counter:verify.device_shapes.post_warm_compiles"}, 0),
    ({"source": "trace:kernel_ms_per_pass"}, 9.03),
    # nothing to read: an empty span, an absent stage, key, field or divisor
    ({"source": "span:phase.commit:mean"}, None),
    ({"source": "span:no.such:mean"}, None),
    ({"source": "counter:verify.no_such"}, None),
    ({"source": "counter:nowhere.key"}, None),
    ({"source": "trace:kernel_items_per_s"}, None),
    ({"source": "counter:verify.device_pass_items", "per": "device_passes"}, None),
    ({"source": "counter:verify.device_pass_items", "per": "no_such"}, None),
])
def test_read_metric(spec, want):
    got = run.read_metric({"name": "m", **spec}, SEEN)
    assert got == (pytest.approx(want) if want is not None else None)


def test_an_unknown_reader_is_an_error():
    with pytest.raises(SystemExit, match="no reader"):
        run.read_metric({"name": "m", "source": "oracle:anything"}, SEEN)


def test_metric_specs_follow_cells(tmp_path, monkeypatch):
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "a.json").write_text('{"name": "a"}')
    (tmp_path / "metrics" / "b.json").write_text('{"name": "b", "cells": ["x"]}')
    monkeypatch.setattr(run, "HERE", str(tmp_path))
    assert [s["name"] for s in run.metric_specs("x")] == ["a", "b"]
    assert [s["name"] for s in run.metric_specs("y")] == ["a"]


def test_window_arithmetic():
    assert stages.percentile([1, 2, 3, 4], 0.95) == 4
    assert stages.percentile(list(range(1, 101)), 0.95) == 95
    # (submitted, answered, ok); the window is [10, 13)
    records = [(9.0, 11.0, True),    # half of its life inside: 0.5
               (10.0, 10.1, True), (10.2, 10.4, True), (11.0, 11.3, True),
               (12.0, 12.5, False),  # answered, but not "ok": no work
               (12.9, 13.4, True),   # a fifth of its life inside: 0.2
               (13.0, 13.1, True),   # submitted after the window
               (8.0, 9.9, True)]     # acknowledged before it
    got = stages.window_metrics(records, 10.0, 13.0)
    assert (got["attempted"], got["failed"], got["acknowledged"]) == (5, 1, 4)
    assert got["committed"] == pytest.approx(3.7)
    assert got["metrics"]["committed_req_per_s"] == (pytest.approx(3.7 / 3), "req/s")
    # over the four acknowledged puts submitted inside: 100, 200, 300, 500 ms
    assert got["metrics"]["commit_latency_p50_ms"][0] == pytest.approx(250.0)
    assert got["metrics"]["commit_latency_p95_ms"][0] == pytest.approx(500.0)
    assert set(stages.window_metrics([], 0.0, 1.0)["metrics"]) == {
        "committed_req_per_s"}
    # lock step: blocks of 4 acknowledged together every 2 s read 2 req/s
    # wherever the window's edges fall, not 4/3 or 8/3 by the edge's luck
    blocks = [(2.0 * k, 2.0 * k + 2.0, True) for k in range(10) for _ in range(4)]
    for t0 in (4.0, 4.7, 5.9):
        got = stages.window_metrics(blocks, t0, t0 + 3.0)
        assert got["metrics"]["committed_req_per_s"][0] == pytest.approx(2.0)
    assert stages._flatten({"a": 1, "b": {"c": 2.5, "d": "x", "e": True}}) == {
        "a": 1, "b.c": 2.5}
    assert stages.counter_deltas({"s": {"a": 1}}, {"s": {"a": 4, "b": 2}}) == {
        "s": {"a": 3, "b": 2}}
