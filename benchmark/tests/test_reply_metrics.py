"""The reply-batching metrics: two data files whose counters the client
keeps from its first frame, read by the generic counter reader; and a
program without those counters (the parent) leaves them out, unraised."""

import asyncio
import json
import os

import pytest

import run
import stages

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAMES = {"reply_frames_per_req": "clients.reply_frames",
         "batched_replies_per_req": "clients.reply_entries_batched"}


class NoService:
    def snapshot(self):
        return {}


def _specs():
    by_name = {s["name"]: s for s in run.metric_specs("n16-inflight8")}
    return {name: by_name[name] for name in NAMES}


def test_files_load_for_every_cell_and_name_their_counters():
    specs = _specs()
    for cell in ("n64-inflight128", "n16-inflight512", "n16-inflight8"):
        assert set(NAMES) <= {s["name"] for s in run.metric_specs(cell)}
    for name, counter in NAMES.items():
        spec = specs[name]
        assert spec["source"] == "counter:" + counter
        assert spec["per"] == "committed" and "cells" not in spec
        assert spec["layer"] == "client"
        assert spec["moves"] == "committed_req_per_s"
    assert specs["reply_frames_per_req"]["better"] == "lower"
    assert specs["batched_replies_per_req"]["better"] == "higher"
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as fh:
        layer = json.load(fh)["per_layer"]
    # appended at the end of the list, in this order
    assert [m["name"] for m in layer[-2:]] == list(NAMES)


def test_counter_surfaces_expose_them_from_the_first_frame():
    """0 before any reply (so a cell that batches nothing reports 0, not
    nothing), frames of both kinds after pipelined puts, and per request
    through the reader."""
    from simple_pbft_tpu.committee import LocalCommittee

    async def scenario():
        com = LocalCommittee.build(n=4, clients=2)
        before = stages.counter_surfaces(com, NoService())
        for counter in NAMES.values():
            surface, _, key = counter.partition(".")
            assert before[surface][key] == 0
        com.start()
        try:
            # c0 pipelines eight puts, c1 has one in flight at a time
            async def one_by_one():
                for i in range(4):
                    assert await com.clients[1].submit(f"put b{i} v") == "ok"

            results = await asyncio.gather(
                one_by_one(),
                *(com.clients[0].submit(f"put a{i} v") for i in range(8)))
            assert results[1:] == ["ok"] * 8
            await asyncio.sleep(0.1)
        finally:
            await com.stop()
        after = stages.counter_surfaces(com, NoService())
        return com, stages.counter_deltas(before, after)

    com, counters = asyncio.run(asyncio.wait_for(scenario(), 60))
    seen = {"spans": {}, "counters": counters, "trace": {},
            "divisors": {"committed": 12}}
    specs = _specs()
    n_frames = counters["clients"]["reply_frames"]
    n_batched = counters["clients"]["reply_entries_batched"]
    assert run.read_metric(specs["reply_frames_per_req"], seen) == (
        pytest.approx(n_frames / 12))
    assert run.read_metric(specs["batched_replies_per_req"], seen) == (
        pytest.approx(n_batched / 12))
    sent = sum(r.metrics["replies_sent"] + r.metrics["spec_replies_sent"]
               for r in com.replicas)
    assert 0 < n_batched <= sent
    assert n_frames == counters["replicas"]["reply_frames_sent"]
    assert n_frames + n_batched > sent  # a batch is a frame too
    assert n_frames < sent              # and saves frames
    # only the pipelined client was sent batches
    assert com.clients[1].metrics["reply_entries_batched"] == 0
    assert com.clients[0].metrics["reply_entries_batched"] == n_batched
    assert "replybatch.sent_msgs" in counters["wire"]


def test_a_program_without_the_counters_reports_neither():
    """The parent's clients keep no such key: the reader returns nothing
    and the line leaves the metric out."""
    seen = {"spans": {}, "trace": {}, "divisors": {"committed": 40},
            "counters": {"clients": {"spec_accepted": 30}, "replicas": {},
                         "wire": {}, "verify": {}}}
    for spec in _specs().values():
        assert run.read_metric(spec, seen) is None
