"""The collector's policy (ISSUE 37; simple_pbft_tpu/heap.py): settle and
release, the count that nests them, the two call sites, what the policy
leaves alone under the sim, ``gc.full`` beside ``gc.pause``, the heartbeat's
``gc_frozen`` gauge, and the benchmark's engagement counter."""

import asyncio
import gc
import importlib.util
import json
import os
import re

import pytest

from simple_pbft_tpu import clock, heap, spans
from simple_pbft_tpu.committee import LocalCommittee
from simple_pbft_tpu.sim import Scenario, run_scenario
from simple_pbft_tpu.telemetry import LoopLagGauge
from test_loop_stages import ScriptedClock

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(ROOT, "simple_pbft_tpu")
# gc.get_freeze_count() of a process that is not settled: 0 after an
# unfreeze, and the 375 objects CPython 3.12 itself keeps in the permanent
# generation once a full collection has run
IDLE = 1000


def run(coro, timeout=60):
    return asyncio.run(asyncio.wait_for(coro, timeout))


def _release_all() -> None:
    while heap._settled:
        heap.release_heap()


@pytest.fixture(autouse=True)
def unsettled():
    """Every test starts and ends with no settle outstanding, whatever an
    earlier test of this worker left behind, and with the thresholds the
    interpreter had."""
    _release_all()
    found = gc.get_threshold()
    yield
    _release_all()
    gc.set_threshold(*found)


@pytest.fixture
def fresh_spans():
    spans.configure("test")
    yield
    spans.configure("")


# ---------------------------------------------------------------------------
# the pair
# ---------------------------------------------------------------------------


def test_settle_then_release_restores_thresholds_and_unfreezes():
    gc.set_threshold(701, 11, 12)
    assert gc.get_freeze_count() < IDLE
    heap.settle_heap()
    assert gc.get_threshold() == (heap.YOUNG_THRESHOLD, 11, 12)
    assert gc.get_freeze_count() > 10_000  # the interpreter, pytest, jax
    heap.release_heap()
    assert gc.get_threshold() == (701, 11, 12)
    assert gc.get_freeze_count() < IDLE


def test_young_threshold_is_one_constant_between_ten_and_a_hundred_thousand():
    assert 10_000 <= heap.YOUNG_THRESHOLD <= 100_000
    before = gc.get_threshold()
    heap.settle_heap()
    # full collections stay on: thresholds 1 and 2 are the ones found
    assert gc.get_threshold()[1:] == before[1:]
    assert gc.isenabled()


def test_second_settle_is_counted_not_repeated(monkeypatch):
    heap.settle_heap()
    frozen = gc.get_freeze_count()
    calls = []
    monkeypatch.setattr(heap.gc, "collect", lambda *a: calls.append("collect"))
    monkeypatch.setattr(heap.gc, "freeze", lambda: calls.append("freeze"))
    heap.settle_heap()
    assert calls == []
    assert gc.get_freeze_count() == frozen
    monkeypatch.undo()
    heap.release_heap()  # the inner one: still settled
    assert gc.get_freeze_count() == frozen
    assert gc.get_threshold()[0] == heap.YOUNG_THRESHOLD
    heap.release_heap()  # the last one unfreezes
    assert gc.get_freeze_count() < IDLE
    assert gc.get_threshold()[0] != heap.YOUNG_THRESHOLD


def test_release_without_a_settle_does_nothing():
    before = gc.get_threshold()
    heap.release_heap()
    heap.release_heap()
    assert gc.get_threshold() == before
    heap.settle_heap()  # and the count did not go under zero
    assert gc.get_freeze_count() > 0
    heap.release_heap()
    assert gc.get_freeze_count() < IDLE


def test_what_is_allocated_after_the_settle_is_still_collected():
    class Node:
        pass

    heap.settle_heap()
    a, b = Node(), Node()
    a.other, b.other = b, a  # a cycle only the collector can free
    del a, b
    assert gc.collect() >= 2


# ---------------------------------------------------------------------------
# the call sites
# ---------------------------------------------------------------------------


def test_committee_start_settles_and_stop_releases():
    async def scenario():
        com = LocalCommittee.build(n=4, clients=1)
        assert gc.get_freeze_count() < IDLE
        com.start()
        try:
            frozen = gc.get_freeze_count()
            young = gc.get_threshold()[0]
            assert await com.clients[0].submit("put k v") == "ok"
            gauge = com.node_telemetry("r0").snapshot()["loop_lag"]
        finally:
            await com.stop()
        return frozen, young, gauge

    found = gc.get_threshold()
    frozen, young, gauge = run(scenario())
    assert frozen > 10_000
    assert young == heap.YOUNG_THRESHOLD
    # the heartbeat's snapshot reads it; a frozen object that dies by
    # reference count leaves the permanent generation, so the gauge sinks
    assert 0.9 * frozen < gauge["gc_frozen"] <= frozen
    assert gc.get_freeze_count() < IDLE
    assert gc.get_threshold() == found


def test_two_committees_settle_once_and_the_last_stop_releases():
    async def scenario():
        one = LocalCommittee.build(n=4, clients=1)
        two = LocalCommittee.build(n=4, clients=1)
        one.start()
        frozen = gc.get_freeze_count()
        two.start()
        # counted, not repeated: what two built is not frozen (a frozen
        # object that dies leaves the count, so it can only sink)
        assert gc.get_freeze_count() <= frozen
        await one.stop()
        await one.stop()  # a second stop of one releases nothing of two's
        still = gc.get_freeze_count()
        await two.stop()
        return frozen, still

    frozen, still = run(scenario())
    assert frozen >= still > 0.9 * frozen
    assert gc.get_freeze_count() < IDLE
    assert heap._settled == 0


def test_node_and_committee_are_the_only_callers_and_heap_the_only_policy():
    """One function pair, two call sites, one file that touches the
    collector's settings (the issue's grep)."""
    policy = re.compile(r"gc\.(freeze|unfreeze|set_threshold)")
    callers, setters = set(), set()
    for base, _dirs, files in os.walk(PACKAGE):
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(base, name)
            with open(path) as fh:
                src = fh.read()
            rel = os.path.relpath(path, PACKAGE)
            if policy.search(src):
                setters.add(rel)
            if "settle_heap()" in src and rel != "heap.py":
                callers.add(rel)
                assert "release_heap()" in src, rel
    assert setters == {"heap.py"}
    assert callers == {"committee.py", "node.py"}


def test_the_sim_commits_the_same_trace_with_the_policy_as_without(
        monkeypatch):
    sc = Scenario(seed=11, n=4, requests=8, horizon=10.0, probes=2,
                  gen=dict(crashes=1, partition_windows=1, drop_windows=1))
    settled = []
    real = heap.settle_heap

    def spy():
        real()
        settled.append(gc.get_freeze_count())

    monkeypatch.setattr(heap, "settle_heap", spy)
    with_policy = run_scenario(sc)
    assert settled and settled[0] > 0  # the sim's committee passed there
    assert gc.get_freeze_count() < IDLE
    # the test's seam, not a switch of the program's
    monkeypatch.setattr(heap, "settle_heap", lambda: None)
    without = run_scenario(sc)
    assert with_policy.ok and without.ok
    assert with_policy.fingerprint == without.fingerprint
    assert with_policy.committed == without.committed


# ---------------------------------------------------------------------------
# what shows that it is on
# ---------------------------------------------------------------------------


def test_a_full_collection_counts_in_both_and_a_young_one_in_gc_pause_alone(
        fresh_spans):
    prev = clock.install(ScriptedClock([1.0, 1.25, 2.0, 2.5, 3.0, 3.125]))
    try:
        for generation in (2, 0, 1):
            spans._on_gc("start", {"generation": generation})
            spans._on_gc("stop", {"generation": generation})
    finally:
        clock.install(prev)
    stages = spans.stage_summaries()
    pause, full = stages[spans.GC_PAUSE], stages[spans.GC_FULL]
    assert pause["count"] == 3 and pause["n"] == 3  # generations summed
    assert pause["sum"] == pytest.approx(875.0)
    assert full["count"] == 1
    assert full["sum"] == full["max"] == pytest.approx(250.0)


def test_the_installed_hook_splits_real_collections(fresh_spans):
    spans.watch_gc(True)
    try:
        gc.collect(0)
        gc.collect(0)
        gc.collect()
    finally:
        spans.watch_gc(False)
    stages = spans.stage_summaries()
    assert stages[spans.GC_PAUSE]["count"] >= 3
    assert stages[spans.GC_FULL]["count"] == 1
    assert stages[spans.GC_FULL]["sum"] <= stages[spans.GC_PAUSE]["sum"]


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_frozen_gauge_in_the_heartbeat_snapshot_and_in_pbft_top_loop():
    assert LoopLagGauge().snapshot()["gc_frozen"] < IDLE  # the policy is off
    heap.settle_heap()
    frozen = LoopLagGauge().snapshot()["gc_frozen"]
    assert frozen == gc.get_freeze_count() > 10_000
    pbft_top = _load_tool("pbft_top")
    stages = {"loop.ingest": {"sum": 60.0}, "loop.route": {"sum": 20.0},
              "loop.offcpu": {"sum": 20.0}}
    snap = {"spans": {"stages": stages}}
    assert pbft_top.loop_cell(snap, None) == "ingest60 off20"  # older nodes
    snap["loop_lag"] = {"gc_frozen": 412_345}
    assert pbft_top.loop_cell(snap, None) == "ingest60 off20 fz412k"
    snap["loop_lag"] = {"gc_frozen": 375}  # what an interpreter starts with
    assert pbft_top.loop_cell(snap, None) == "ingest60 off20 fz0k"


# ---------------------------------------------------------------------------
# the benchmark's engagement counter (data only)
# ---------------------------------------------------------------------------


def test_gc_collections_per_req_reads_the_accumulator_the_hook_fills():
    with open(os.path.join(
            ROOT, "benchmark", "metrics", "gc_collections_per_req.json")) as fh:
        spec = json.load(fh)
    kind, stage, stat = spec["source"].split(":")
    assert kind == "span"
    assert stage == spans.GC_PAUSE  # every generation, not gc.full
    assert stat in spans.Accum().summary()
    assert stat == "count"
    assert spec["per"] == "committed" and "scale" not in spec
    assert spec["unit"] == "collections/req" and spec["better"] == "lower"
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        manifest = json.load(fh)
    entry = [m for m in manifest["per_layer"] if m["name"] == spec["name"]]
    assert entry == [{
        "name": "gc_collections_per_req", "unit": spec["unit"],
        "better": "lower", "source": "program_span",
        "layer": spec["layer"], "moves": spec["moves"],
    }]
    names = [m["name"] for m in manifest["per_layer"]]
    assert names.index(spec["name"]) > names.index("ladder_round_trip_mean_ms")
    pause = [m for m in manifest["per_layer"]
             if m["name"] == "gc_pause_us_per_req"][0]
    assert (entry[0]["layer"], entry[0]["moves"]) == (
        pause["layer"], pause["moves"])
