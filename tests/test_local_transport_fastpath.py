"""The in-process transport's one-call delivery and drain.

``LocalNetwork._deliver_many`` pays a broadcast's bookkeeping once and
``LocalEndpoint.recv_many`` a drain's. Both must be invisible to
everything that reads the network: the frames each node receives and
their order, the ``FaultPlan``'s draws (seeded repros replay them), the
delivery tap, and the wire ledgers. The reference below is the
per-destination path written out plainly — one delivery decision per
destination, each copy accounted on its own — and a seeded storm must
leave both in the same state."""

from __future__ import annotations

import asyncio
import random
import time

import pytest

from simple_pbft_tpu import spans, trace
from simple_pbft_tpu.crypto.signer import Signer
from simple_pbft_tpu.faults import ByzantineTransport, ShapedTransport
from simple_pbft_tpu.messages import Checkpoint, Commit, Prepare, Request
from simple_pbft_tpu.sim import sim_run
from simple_pbft_tpu.transport.base import Transport
from simple_pbft_tpu.transport.local import FaultPlan, LocalNetwork

IDS = [f"r{i}" for i in range(16)]
OPS = 300


def _reference_deliver(net: LocalNetwork, src: str, dst: str,
                       raw: bytes) -> None:
    """One destination's delivery decision and accounting."""
    src_wire = net.wire_accts.get(src)
    kind = src_wire.kind_of(raw) if src_wire is not None else ""
    q = net.queues.get(dst)
    if q is None:
        if src_wire is not None:
            src_wire.account_lost("no_route", raw)
        net._trace(src, dst, kind, len(raw), "no_route")
        return
    f = net.faults
    if (src, dst) in f.partitions or f.rng.random() < f.drop_rate:
        net.dropped += 1
        if src_wire is not None:
            src_wire.account_lost("net_dropped", raw)
        net._trace(src, dst, kind, len(raw), "dropped")
        return
    copies = 2 if f.rng.random() < f.duplicate_rate else 1
    lo, hi = f.delay_range
    item = (time.perf_counter(), raw)
    dst_wire = net.wire_accts.get(dst)
    net._trace(src, dst, kind, len(raw), "deliver")
    for _ in range(copies):
        delay = f.rng.uniform(lo, hi) if hi > 0 else 0.0
        if delay > 0:
            asyncio.get_running_loop().call_later(delay, q.put_nowait, item)
        else:
            q.put_nowait(item)
        net.delivered += 1
        if src_wire is not None:
            src_wire.account_send(dst, raw, kind=kind)
        if dst_wire is not None:
            dst_wire.account_recv(raw, kind=kind)


def _ops(seed: int):
    """A seeded mix of broadcasts and unicasts of real frames, some to an
    id no node has (the ``no_route`` bucket)."""
    rng = random.Random(seed)
    out = []
    for i in range(OPS):
        src = rng.choice(IDS)
        pick = rng.random()
        if pick < 0.4:
            msg = Prepare(view=0, seq=i, digest="ab" * 32, sender=src,
                          sig="cd" * 64)
        elif pick < 0.8:
            msg = Commit(view=0, seq=i, digest="ef" * 32, sender=src,
                         sig="01" * 64)
        elif pick < 0.9:
            msg = Checkpoint(seq=i, state_digest="23" * 32, sender=src,
                             sig="45" * 64)
        else:
            msg = Request(client_id="c0", timestamp=i, operation=f"put k{i} v",
                          sender="c0", sig="67" * 64)
        raw = msg.to_wire()
        if rng.random() < 0.8:
            out.append(("broadcast", src, raw, IDS + ["ghost"]))
        else:
            out.append(("send", src, raw, rng.choice(IDS + ["ghost"])))
    return out


def _plan(case: str, seed: int) -> FaultPlan:
    if case == "faults":
        return FaultPlan(drop_rate=0.2, duplicate_rate=0.1,
                         delay_range=(0, 0.002), seed=seed)
    plan = FaultPlan(seed=seed)
    if case == "partition":
        plan.cut("r1", "r2")
    return plan


def _storm(case: str, seed: int, fast: bool):
    plan = _plan(case, seed)
    net = LocalNetwork(plan)
    eps = {i: net.endpoint(i) for i in IDS}
    events = []
    if case == "tap":
        net.trace = lambda *ev: events.append(ev)
    got = {}

    def queued() -> int:
        h = spans.recorder()._hists.get(spans.TRANSPORT_QUEUE)
        return h.count if h is not None else 0

    async def main():
        for how, src, raw, where in _ops(seed):
            ep = eps[src]
            if fast:
                if how == "broadcast":
                    await ep.broadcast(raw, where)
                else:
                    await ep.send(where, raw)
            elif how == "broadcast":
                for dest in where:
                    if dest != src:
                        ep.metrics["sent"] += 1
                        _reference_deliver(net, src, dest, raw)
            else:
                ep.metrics["sent"] += 1
                _reference_deliver(net, src, where, raw)
        await asyncio.sleep(0.01)  # virtual time: every delayed copy lands
        before = queued()
        for i, ep in eps.items():
            if fast:
                got[i] = ep.recv_many()
            else:
                got[i] = []
                while (raw := ep.recv_nowait()) is not None:
                    got[i].append(raw)
        got["histogram"] = queued() - before

    sim_run(main())
    return {
        "frames": {i: got[i] for i in IDS},
        "histogram": got["histogram"],
        "events": events,
        "rng": plan.rng.getstate(),
        "per_kind": {i: net.wire_accts[i].per_kind() for i in IDS},
        "snapshot": {i: net.wire_accts[i].snapshot() for i in IDS},
        "metrics": {i: dict(eps[i].metrics) for i in IDS},
        "delivered": net.delivered,
        "dropped": net.dropped,
    }


@pytest.mark.parametrize("seed", [1, 20260415])
@pytest.mark.parametrize("case", ["clean", "faults", "partition", "tap"])
def test_one_call_delivery_matches_the_per_destination_path(case, seed):
    fast = _storm(case, seed, fast=True)
    ref = _storm(case, seed, fast=False)
    received = sum(len(v) for v in fast["frames"].values())
    assert received > OPS  # the storm delivered
    assert fast["histogram"] == received == ref["histogram"]
    assert fast["frames"] == ref["frames"]
    assert fast["events"] == ref["events"]
    assert fast["rng"] == ref["rng"]
    assert fast["per_kind"] == ref["per_kind"]
    assert fast["snapshot"] == ref["snapshot"]
    assert fast["metrics"] == ref["metrics"]
    assert (fast["delivered"], fast["dropped"]) == (
        ref["delivered"], ref["dropped"])
    if case == "tap":
        assert {ev[4] for ev in fast["events"]} == {"deliver", "no_route"}
    if case == "faults":
        assert fast["dropped"] > 0
    if case == "partition":
        assert "r2" not in fast["snapshot"]["r1"]["links"]
    # conservation: attempted = sent + lost, and sent == received
    total = {"sent": 0, "recv": 0, "lost": 0}
    for i in IDS:
        snap = fast["snapshot"][i]
        total["sent"] += snap["sent_msgs"]
        total["recv"] += snap["recv_msgs"]
        total["lost"] += snap["lost_msgs"]
        assert fast["metrics"][i]["recv"] == len(fast["frames"][i])
    assert total["sent"] == total["recv"] == received
    attempted = sum(fast["metrics"][i]["sent"] for i in IDS)
    duplicates = received - (attempted - total["lost"])
    assert duplicates >= 0 and (case == "faults" or duplicates == 0)


def _endpoints():
    net = LocalNetwork()
    return {i: net.endpoint(i) for i in ("a", "b")}


def test_recv_many_stops_at_its_limit_and_counts_what_it_took():
    async def main():
        eps = _endpoints()
        frames = [Prepare(view=0, seq=s, sender="a").to_wire()
                  for s in range(5)]
        b = eps["b"]
        assert b.recv_many() == []
        assert b.recv_many(3) == []
        for raw in frames:
            await eps["a"].send("b", raw)
        assert b.recv_many(0) == []
        assert b.recv_many(3) == frames[:3]
        assert b.metrics["recv"] == 3
        assert b.recv_many(10) == frames[3:]
        assert b.metrics["recv"] == 5
        assert b.recv_many() == []

    asyncio.run(main())


def test_recv_many_stamps_only_a_frame_that_carries_the_envelope(
        monkeypatch):
    docs = []
    monkeypatch.setattr(spans, "emit", docs.append)
    real_stamp = trace.recv_stamp
    stamped_calls = []

    def spy(node_id, raw):
        stamped_calls.append(raw)
        real_stamp(node_id, raw)

    monkeypatch.setattr(trace, "recv_stamp", spy)
    trace.configure(True)
    try:
        plain = Prepare(view=3, seq=11, digest="bb" * 32,
                        sender="a").to_wire()
        stamped = trace.stamp(plain, trace.PREPARE, 3, 11, "a")
    finally:
        trace.configure(False)
    assert stamped != plain

    async def main():
        eps = _endpoints()
        await eps["a"].send("b", plain)
        await eps["a"].send("b", stamped)
        await eps["a"].send("b", plain)
        return eps["b"].recv_many()

    assert asyncio.run(main()) == [plain, stamped, plain]
    assert stamped_calls == [stamped]
    edges = [d for d in docs if d.get("evt") == "edge"]
    assert len(edges) == 1
    assert edges[0]["src"] == "a" and edges[0]["node"] == "b"
    assert edges[0]["phase"] == "prepare" and edges[0]["seq"] == 11


class QueueTransport(Transport):
    """A transport with only ``recv_nowait``: what the default serves."""

    def __init__(self, node_id: str, frames) -> None:
        self.node_id = node_id
        self.frames = list(frames)

    def recv_nowait(self):
        return self.frames.pop(0) if self.frames else None


def _wrappers(inner):
    return {
        "shaped": ShapedTransport(inner),
        "byzantine": ByzantineTransport(inner, Signer("b", b"\x01" * 32)),
    }


@pytest.mark.parametrize("wrapper", ["shaped", "byzantine", "bare"])
@pytest.mark.parametrize("local", [True, False])
def test_recv_many_through_a_wrapper_gives_the_recv_nowait_frames(
        wrapper, local):
    frames = [Commit(view=0, seq=s, sender="a").to_wire() for s in range(7)]

    async def drained(by_many: bool):
        if local:
            eps = _endpoints()
            for raw in frames:
                await eps["a"].send("b", raw)
            inner = eps["b"]
        else:
            inner = QueueTransport("b", frames)
        t = inner if wrapper == "bare" else _wrappers(inner)[wrapper]
        if by_many:
            return t.recv_many(4) + t.recv_many() + t.recv_many()
        out = []
        while (raw := t.recv_nowait()) is not None:
            out.append(raw)
        return out

    many = asyncio.run(drained(True))
    assert many == asyncio.run(drained(False)) == frames
