"""The signing lane (``consensus/qc.py: QcSignLane``): certificate shares
signed on worker threads, a chunk per native call, instead of on the event
loop's thread.

The lane must do the same work as the loop did: every share byte for byte
what ``bls.sign`` gives, none skipped, none reused. Under simulation the
share is signed inline, so the simulator's traffic does not depend on
threads that run in wall time.
"""

import asyncio
import sys
import threading
import time

import pytest

from simple_pbft_tpu import native, spans
from simple_pbft_tpu.committee import LocalCommittee
from simple_pbft_tpu.consensus import qc as qc_mod
from simple_pbft_tpu.consensus.state import SendPrepare
from simple_pbft_tpu.crypto import bls
from simple_pbft_tpu.messages import Prepare, qc_payload
from simple_pbft_tpu.sim import Scenario, run_scenario

pytestmark = pytest.mark.skipif(
    not native.bls_available(), reason="the lane signs through bls381.cpp")

# (phase, view, seq, digest): a vote of each phase and a checkpoint
PAYLOADS = [("prepare", 3, 17, "a" * 64), ("commit", 3, 17, "a" * 64),
            ("checkpoint", 0, 64, "c" * 64)]


def _run(coro, timeout=240):
    return asyncio.run(asyncio.wait_for(coro, timeout))


@pytest.fixture(scope="module")
def sks():
    return [bls.keygen(bytes([i + 1]) * 32)[0] for i in range(32)]


def _counters() -> dict:
    snap = qc_mod.lane_snapshot() or {}
    return {k: snap.get(k, 0)
            for k in ("sign_shares", "sign_batches", "sign_inline")}


def _delta(before: dict) -> dict:
    return {k: v - before[k] for k, v in _counters().items()}


def _lane_shares(jobs):
    async def main():
        return await asyncio.gather(
            *(qc_mod.sign_share_async(sk, *p) for sk, p in jobs))

    return _run(main())


@pytest.mark.parametrize("pile", ["one", "32x3"])
def test_lane_shares_are_what_bls_sign_gives(sks, pile):
    jobs = ([(sks[0], PAYLOADS[0])] if pile == "one"
            else [(sk, p) for sk in sks for p in PAYLOADS])
    assert len(jobs) == 1 or len(jobs) > qc_mod.sign_workers()
    before = _counters()
    got = _lane_shares(jobs)
    want = [bls.sign(sk, qc_payload(*p)).hex() for sk, p in jobs]
    assert got == want
    assert got == [qc_mod.sign_share(sk, *p) for sk, p in jobs]
    moved = _delta(before)
    assert moved["sign_shares"] == len(jobs) and moved["sign_inline"] == 0
    assert 1 <= moved["sign_batches"] <= len(jobs)


def test_more_workers_than_cores_lose_no_share(sks):
    """Sixteen workers and a switch interval of a microsecond: every
    share comes back to its own future, and the counters add up."""
    lane = qc_mod.QcSignLane(workers=16)
    jobs = [(sk, qc_payload(*p)) for sk in sks for p in PAYLOADS] * 2
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        async def main():
            return await asyncio.gather(
                *(lane.submit(sk, payload) for sk, payload in jobs))

        got = _run(main(), timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert got == [bls.sign(sk, payload) for sk, payload in jobs]
    counted = lane.counters()
    assert counted["sign_shares"] == len(jobs)
    assert 1 <= counted["sign_batches"] <= len(jobs)


def test_lane_shares_match_the_pure_python_path(sks):
    got = _lane_shares([(sks[5], p) for p in PAYLOADS])
    for share, p in zip(got, PAYLOADS):
        point = bls.G1.mul_pt(bls.hash_to_g1(qc_payload(*p)), sks[5])
        assert bytes.fromhex(share) == bls._g1_to_bytes(point)


def test_sign_many_agrees_with_sign_item_for_item(sks):
    # 0 is degenerate and the native path refuses it; -1 and 2**256 do
    # not fit its 32 bytes: each must come back None, never garbage
    scalars = sks[:8] + [0, -1, 1 << 256, bls.R_ORDER + 5]
    msgs = [qc_payload(*PAYLOADS[i % 3]) for i in range(len(scalars))]
    many = native.bls_sign_many(scalars, msgs, bls.DST_SIG)
    one = [native.bls_sign(sk, m, bls.DST_SIG) for sk, m in zip(scalars, msgs)]
    assert many == one
    assert many[8:11] == [None, None, None] and None not in many[:8]
    # the refused items take bls.sign's own fallback
    assert bls.sign_many(scalars, msgs) == [
        bls.sign(sk, m) for sk, m in zip(scalars, msgs)]
    assert native.bls_sign_many([], [], bls.DST_SIG) == []


def test_a_committee_signs_its_shares_on_the_lane(monkeypatch):
    loop_thread = threading.get_ident()
    threads = set()
    real_record = spans.record

    def record(stage, dur, **kw):
        if stage == spans.QC_SIGN_SHARE:
            threads.add(threading.get_ident())
        real_record(stage, dur, **kw)

    monkeypatch.setattr(spans, "record", record)
    before = _counters()

    async def scenario():
        com = LocalCommittee.build(n=16, clients=1, qc_mode=True,
                                   view_timeout=60.0)
        com.clients[0].request_timeout = 60.0
        com.start()
        try:
            for i in range(3):
                assert await com.clients[0].submit(f"put s{i} {i}") == "ok"
            for _ in range(500):  # a reply may beat the commit certificate
                if all(r.executed_seq >= 3 for r in com.replicas):
                    break
                await asyncio.sleep(0.02)
        finally:
            await com.stop()
        return com

    com = _run(scenario())
    moved = _delta(before)
    assert moved["sign_batches"] > 0 and moved["sign_inline"] == 0
    assert moved["sign_shares"] >= 2 * com.replica("r0").cfg.quorum
    assert threads and loop_thread not in threads
    assert com.replica("r0").metrics["qcs_formed"] >= 6


def _hold_shares(monkeypatch, lane, gate):
    """Hold every share the lane signs until ``gate`` is set."""
    real_submit = lane.submit

    def held_submit(sk, payload):
        inner = real_submit(sk, payload)
        outer = asyncio.get_running_loop().create_future()

        async def release():
            share = await inner
            await gate.wait()
            outer.set_result(share)

        asyncio.ensure_future(release())
        return outer

    monkeypatch.setattr(lane, "submit", held_submit)


@pytest.mark.parametrize("meanwhile", ["in_view_change", "view_moved_on"])
def test_a_share_pending_into_a_view_change_does_not_leave(monkeypatch,
                                                           meanwhile):
    """A view change that begins, or begins and ends, while the share is
    on the lane: the old view's vote stays home."""
    com = LocalCommittee.build(n=4, clients=1, qc_mode=True)
    rep = com.replica("r1")
    sent = []

    async def send(dst, wire):
        sent.append(dst)

    monkeypatch.setattr(rep.transport, "send", send)
    counter = ("vote_suppressed_in_vc" if meanwhile == "in_view_change"
               else "vote_suppressed_stale_view")

    async def scenario():
        gate = asyncio.Event()
        _hold_shares(monkeypatch, qc_mod.sign_lane(), gate)
        act = SendPrepare(view=0, seq=1, digest="d" * 64)
        vote = asyncio.ensure_future(rep._send_vote(Prepare, "prepare", act))
        await asyncio.sleep(0.05)
        assert not vote.done()  # waiting for its share
        suppressed = rep.metrics[counter]
        if meanwhile == "in_view_change":
            rep.vc.in_view_change = True
        else:
            rep.view = 1
        gate.set()
        await vote
        return suppressed

    suppressed = _run(scenario())
    assert rep.metrics[counter] == suppressed + 1
    assert sent == []


def test_a_checkpoint_share_away_holds_its_replica_in_order(monkeypatch):
    """r1's checkpoint share at seq 2 is held on the lane while the next
    blocks commit. The committee goes on without r1; r1's route loop
    waits at the share, as it waited while the loop signed it, so its
    next blocks execute after its checkpoint and in order. Once the
    share returns r1 catches up, and its checkpoints agree with the
    others' and stabilize."""
    com = LocalCommittee.build(n=4, clients=1, qc_mode=True,
                               checkpoint_interval=2, view_timeout=60.0)
    com.clients[0].request_timeout = 60.0
    r0, r1 = com.replica("r0"), com.replica("r1")
    real_sign = qc_mod.sign_share_async
    held = {}

    async def scenario():
        gate, away = asyncio.Event(), asyncio.Event()

        async def sign_share_async(sk, phase, view, seq, digest):
            share = await real_sign(sk, phase, view, seq, digest)
            if sk == r1.bls_sk and phase == "checkpoint" and seq == 2:
                away.set()
                await gate.wait()
            return share

        monkeypatch.setattr(qc_mod, "sign_share_async", sign_share_async)
        com.start()
        try:
            for i in range(2):
                assert await com.clients[0].submit(f"put k{i} {i}") == "ok"
            await asyncio.wait_for(away.wait(), 30)
            for _ in range(500):
                if r0.checkpoint_digests.get(2) is not None:
                    break
                await asyncio.sleep(0.02)
            # r1's was cut before its share left, as when the loop signed
            held["cp2"] = (r1.checkpoint_digests.get(2),
                           r0.checkpoint_digests.get(2))
            for i in range(2, 5):
                assert await com.clients[0].submit(f"put k{i} {i}") == "ok"
            held["top"] = max(r.executed_seq for r in com.replicas)
            held["r1"] = r1.executed_seq
            gate.set()
            for _ in range(500):
                if all(r.stable_seq >= 4 for r in com.replicas):
                    break
                await asyncio.sleep(0.02)
        finally:
            gate.set()
            await com.stop()

    _run(scenario())
    assert held["top"] >= 4 and held["r1"] == 2
    assert r1.executed_seq == r0.executed_seq >= 4
    assert held["cp2"][0] is not None and held["cp2"][0] == held["cp2"][1]
    assert r1.checkpoint_digests[4] == r0.checkpoint_digests[4]
    assert all(r.stable_seq >= 4 for r in com.replicas)


def test_a_failed_chunk_resolves_every_share(monkeypatch, sks):
    """A chunk whose signing raises hands the error to every share it
    took, and one whose span recording raises still hands back its
    shares and keeps its worker: no vote waits forever, and the lane's
    gauges drain."""
    jobs = [(sk, qc_payload(*PAYLOADS[0])) for sk in sks[:6]]

    def broken_sign_many(sks_, msgs):
        raise ValueError("no signing today")

    async def submit_all(lane):
        return await asyncio.gather(
            *(lane.submit(sk, payload) for sk, payload in jobs),
            return_exceptions=True)

    lane = qc_mod.QcSignLane(workers=2)
    monkeypatch.setattr(bls, "sign_many", broken_sign_many)
    got = _run(submit_all(lane), timeout=60)
    assert all(isinstance(g, ValueError) for g in got)
    monkeypatch.undo()

    lane = qc_mod.QcSignLane(workers=2)

    def broken_record(*args, **kw):
        raise OSError("span sink gone")

    monkeypatch.setattr(spans, "record", broken_record)
    for _ in range(2):  # the workers outlive the fault
        got = _run(submit_all(lane), timeout=60)
        assert got == [bls.sign(sk, payload) for sk, payload in jobs]
    for _ in range(100):
        counted = lane.counters()
        if counted["sign_inflight"] == 0:
            break
        time.sleep(0.01)
    assert counted["sign_pending"] == 0 and counted["sign_inflight"] == 0


def test_the_simulator_signs_inline_and_replays_the_same_traffic(monkeypatch):
    calls = {"n": 0}
    real_sign = qc_mod.sign_share

    def sign_share(*args):
        calls["n"] += 1
        return real_sign(*args)

    monkeypatch.setattr(qc_mod, "sign_share", sign_share)
    sc = Scenario(seed=44, n=4, requests=4, horizon=5.0, probes=1,
                  qc_mode=True)
    before = _counters()
    a = run_scenario(sc)
    b = run_scenario(sc)
    moved = _delta(before)
    assert a.ok and b.ok, (a.failure, b.failure)
    assert a.committed > 0 and a.fingerprint == b.fingerprint
    assert a.app_digests == b.app_digests
    assert calls["n"] > 0 and moved == {
        "sign_shares": 0, "sign_batches": 0, "sign_inline": calls["n"]}


def test_the_watchdog_sees_shares_waiting_on_the_lane(monkeypatch, sks):
    """A share queued on the signing lane is work the committee owes:
    the stall watchdog counts it, as it counts the verify lane's."""
    from simple_pbft_tpu.telemetry import ProgressWatchdog

    class Idle:
        executed_seq = 0
        verifier = None

        def has_outstanding_work(self):
            return False

    class Telemetry:
        node_id = "stub"
        replica = Idle()

    lane = qc_mod.QcSignLane(workers=0)  # no thread: the share stays queued
    monkeypatch.setattr(qc_mod, "_sign_lane", lane)
    wd = ProgressWatchdog(Telemetry(), path=None, deadline=60.0)
    assert not wd._work_visible(Telemetry.replica)

    async def queue_one():
        lane.submit(sks[0], qc_payload(*PAYLOADS[0]))

    _run(queue_one())
    assert lane.counters()["sign_pending"] == 1
    assert wd._work_visible(Telemetry.replica)
