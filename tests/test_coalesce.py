"""VerifyService: the process-wide coalescing verify front.

Round-4 chip evidence showed n replicas each paying a full device round
trip per sweep, serialized (git 7f473af:bench_results/chip_r04.jsonl: n=16 TPU at
6.4 req/s vs CPU 422). The service folds every pending sweep into one
async device pass; these tests pin the coalescing, routing, ordering,
failure, and end-to-end consensus behavior with controllable fakes (the
real TpuVerifier path is covered by the committee test at the bottom).
"""

import asyncio
import threading
import time

import pytest

from simple_pbft_tpu.committee import LocalCommittee
from simple_pbft_tpu.crypto import ed25519_cpu as ref
from simple_pbft_tpu.crypto.coalesce import VerifyService
from simple_pbft_tpu.crypto.verifier import BatchItem


def run(coro, timeout=60):
    return asyncio.run(asyncio.wait_for(coro, timeout))


class FakeDevice:
    """Device verifier double: correct verdicts via a trivial predicate
    (sig == msg), with a gate so tests control when a pass completes."""

    def __init__(self, gate: bool = False):
        self.batches = []  # item counts per dispatch, in dispatch order
        self.device_calls = 0
        self.device_items = 0
        self.device_seconds = 0.0
        self._gate = threading.Event()
        if not gate:
            self._gate.set()

    def release(self):
        self._gate.set()

    def dispatch_batch(self, items):
        items = list(items)
        self.batches.append(len(items))
        self.device_calls += 1
        self.device_items += len(items)

        def finish():
            assert self._gate.wait(30), "test gate never released"
            return [it.sig == it.msg for it in items]

        return finish


class FakeCpu:
    def __init__(self):
        self.batches = []

    def verify_batch(self, items):
        self.batches.append(len(items))
        return [it.sig == it.msg for it in items]


def _items(n, tag=b"x", good=True):
    return [
        BatchItem(b"pk", tag + bytes([i % 251]), tag + bytes([i % 251]) if good else b"bad")
        for i in range(n)
    ]


def test_small_batch_takes_cpu_path():
    dev, cpu = FakeDevice(), FakeCpu()
    svc = VerifyService(dev, cpu=cpu, cpu_cutoff=64)
    out = svc.verify_batch(_items(10))
    assert out == [True] * 10
    assert cpu.batches == [10]
    assert dev.batches == []
    svc.close()


def test_large_batch_takes_device_path():
    dev, cpu = FakeDevice(), FakeCpu()
    svc = VerifyService(dev, cpu=cpu, cpu_cutoff=64)
    out = svc.verify_batch(_items(500))
    assert out == [True] * 500
    assert dev.batches == [500]
    assert cpu.batches == []
    svc.close()


def test_concurrent_submissions_coalesce_and_map_back():
    """While pass 1 is gated in flight, every later submission piles up
    and rides ONE second pass; each submitter gets exactly its own
    verdict slice (including its invalid rows)."""
    dev = FakeDevice(gate=True)
    svc = VerifyService(dev, cpu=FakeCpu(), cpu_cutoff=0)
    first = svc.submit(_items(100, tag=b"a"))
    # wait until the first dispatch is actually in flight
    for _ in range(200):
        if dev.batches:
            break
        time.sleep(0.005)
    assert dev.batches == [100]
    futs = [
        svc.submit(_items(40, tag=bytes([65 + k]), good=(k % 2 == 0)))
        for k in range(6)
    ]
    time.sleep(0.05)  # submissions must pile up behind the gated pass
    dev.release()
    assert first.result(10) == [True] * 100
    for k, f in enumerate(futs):
        expect = [k % 2 == 0] * 40
        assert f.result(10) == expect
    # everything after the gate landed in at most MAX_DEPTH passes
    assert len(dev.batches) <= 1 + VerifyService.MAX_DEPTH
    assert sum(dev.batches) == 100 + 6 * 40
    assert svc.max_coalesced >= 2 * 40
    svc.close()


def test_oversized_submission_split_by_max_batch():
    dev = FakeDevice()
    svc = VerifyService(dev, cpu=FakeCpu(), cpu_cutoff=0, max_batch=128)
    out = svc.verify_batch(_items(300))
    assert out == [True] * 300
    # one submission > max_batch is taken alone (dispatch_batch chunks
    # internally in the real verifier; the fake sees it whole)
    assert sum(dev.batches) == 300
    svc.close()


def test_device_failure_propagates_not_hangs():
    class BoomDevice(FakeDevice):
        def dispatch_batch(self, items):
            raise RuntimeError("device gone")

    svc = VerifyService(BoomDevice(), cpu=FakeCpu(), cpu_cutoff=0)
    with pytest.raises(RuntimeError, match="device gone"):
        svc.verify_batch(_items(10))
    svc.close()


def test_close_never_abandons_inflight_futures():
    """close() while a device pass is gated in flight: the completion
    thread must still resolve every dispatched future (the shutdown
    sentinel rides the FIFO behind all real finishers)."""
    dev = FakeDevice(gate=True)
    svc = VerifyService(dev, cpu=FakeCpu(), cpu_cutoff=0)
    fut = svc.submit(_items(80))
    for _ in range(200):
        if dev.batches:
            break
        time.sleep(0.005)
    late = svc.submit(_items(30))  # queued behind the gated pass
    svc.close()
    dev.release()
    assert fut.result(10) == [True] * 80
    assert late.result(10) == [True] * 30


def test_submit_after_close_answers_on_cpu():
    dev, cpu = FakeDevice(), FakeCpu()
    svc = VerifyService(dev, cpu=cpu, cpu_cutoff=0)
    svc.close()
    assert svc.submit(_items(5)).result(5) == [True] * 5
    assert cpu.batches == [5]


def test_committee_commits_through_coalescing_service():
    """End to end: an n=4 committee whose every replica fronts the SAME
    VerifyService (real Ed25519 on the CPU path — the routing, futures
    and async replica path are the production code under test)."""

    async def scenario():
        from simple_pbft_tpu.crypto.verifier import best_cpu_verifier

        svc = VerifyService(FakeDevice(), cpu=best_cpu_verifier())
        com = LocalCommittee.build(n=4, clients=1, verifier_factory=lambda: svc)
        com.start()
        try:
            results = await asyncio.gather(
                *(com.clients[0].submit(f"put k{i} v{i}") for i in range(12))
            )
            assert results == ["ok"] * 12
        finally:
            await com.stop()
            svc.close()
        digests = {r.app.state_digest() for r in com.replicas}
        assert len(digests) == 1
        # the replicas actually used the submit path (not _timed_verify)
        assert svc.cpu_passes + svc.device_passes > 0
        assert svc.coalesced_submissions > 0

    run(scenario())


def test_committee_commits_through_real_device_route():
    """The on-chip shape, end to end on the CPU backend: every replica
    fronts one service over a REAL TpuVerifier with the CPU path
    disabled, so every sweep rides an actual jitted device pass (tiny
    buckets keep XLA-CPU pass time sub-second). Pins the full chain the
    chip experiments run: replica -> submit -> coalesce -> dispatch ->
    finisher -> future -> quorum -> execute."""

    async def scenario():
        from simple_pbft_tpu.crypto.tpu_verifier import TpuVerifier

        dev = TpuVerifier(initial_keys=16)
        svc = VerifyService(dev, cpu_cutoff=0, max_batch=32)
        com = LocalCommittee.build(
            n=4, clients=1, verifier_factory=lambda: svc, max_batch=8
        )
        dev.warm_for_population(
            [kp.pub for kp in com.keys.values()], max_sweep=32
        )
        com.start()
        try:
            res = await asyncio.gather(
                *(com.clients[0].submit(f"put k{i} v{i}") for i in range(6))
            )
            assert res == ["ok"] * 6
        finally:
            await com.stop()
            svc.close()
        assert svc.device_passes > 0 and svc.cpu_passes == 0
        assert len({r.app.state_digest() for r in com.replicas}) == 1

    run(scenario(), timeout=300)


def test_failover_through_coalescing_service():
    """The storm-on-chip shape: the primary crashes while every replica
    fronts the SAME service over a real device route. View change —
    whose certificate verifies also ride the service — must elect a new
    primary and keep committing."""

    async def scenario():
        from simple_pbft_tpu.crypto.tpu_verifier import TpuVerifier

        dev = TpuVerifier(initial_keys=16)
        svc = VerifyService(dev, cpu_cutoff=0, max_batch=32)
        com = LocalCommittee.build(
            n=4,
            clients=1,
            verifier_factory=lambda: svc,
            max_batch=8,
            view_timeout=1.5,  # headroom: XLA-CPU device passes are slow
        )
        dev.warm_for_population(
            [kp.pub for kp in com.keys.values()], max_sweep=32
        )
        com.start()
        client = com.clients[0]
        client.request_timeout = 1.0
        try:
            assert await client.submit("put a 1") == "ok"
            com.replica("r0").kill()
            assert await client.submit("put b 2", retries=120) == "ok"
            survivors = [r for r in com.replicas if r.id != "r0"]
            assert all(r.view >= 1 for r in survivors)
            assert await client.submit("get a", retries=120) == "1"
        finally:
            await com.stop()
            svc.close()
        assert svc.device_passes > 0

    run(scenario(), timeout=300)


def test_bad_signature_still_rejected_through_service():
    """Byzantine semantics survive the coalescing front: a forged vote
    is dropped while the quorum still forms from valid ones."""

    async def scenario():
        from simple_pbft_tpu.crypto.verifier import best_cpu_verifier

        svc = VerifyService(FakeDevice(), cpu=best_cpu_verifier())
        com = LocalCommittee.build(n=4, clients=1, verifier_factory=lambda: svc)
        com.start()
        try:
            from simple_pbft_tpu.crypto.signer import Signer
            from simple_pbft_tpu.messages import Commit

            r0 = com.replica("r0")
            # forged commit vote: r2's key but claiming r1, on a
            # not-yet-quorate slot (votes for committed seqs drop
            # pre-verification as redundant)
            forged = Commit(view=0, seq=200, digest="f" * 64)
            Signer("r1", com.keys["r2"].seed).sign_msg(forged)
            forged.sender = "r1"
            await com.net.endpoint("r2").send("r0", forged.to_wire())
            assert await com.clients[0].submit("put k v") == "ok"
            for _ in range(100):  # poll: the verify may still be in flight
                if r0.metrics.get("bad_sig", 0) >= 1:
                    break
                await asyncio.sleep(0.1)
            assert r0.metrics.get("bad_sig", 0) >= 1
        finally:
            await com.stop()
            svc.close()

    run(scenario())


class SlowCpu:
    """CPU double whose pass time scales with batch size — makes the
    serialize-behind-a-big-pass failure observable in wall clock."""

    def __init__(self, per_item_s=0.0005):
        self.batches = []
        self.per_item_s = per_item_s

    def verify_batch(self, items):
        self.batches.append(len(items))
        time.sleep(len(items) * self.per_item_s)
        return [it.sig == it.msg for it in items]


def test_big_cpu_reroute_does_not_serialize_small_sweeps():
    """ADVICE r5 (ISSUE 3 satellite): a big pile forced onto the CPU
    (quarantine or depth-full) runs on its own thread, so a small
    quorum sweep submitted while the big pass churns answers in
    milliseconds instead of waiting out the whole pass."""
    dev = FakeDevice()
    svc = VerifyService(dev, cpu=SlowCpu(), cpu_cutoff=64)
    svc._quarantined_until = time.monotonic() + 60  # device benched
    big = svc.submit(_items(3000, tag=b"B"))  # ~1.5 s of CPU
    for _ in range(400):  # wait until the reroute thread owns the pile
        if svc.cpu_reroute_passes:
            break
        time.sleep(0.005)
    assert svc.cpu_reroute_passes == 1
    t0 = time.perf_counter()
    small = svc.submit(_items(10, tag=b"s"))
    assert small.result(10) == [True] * 10
    small_latency = time.perf_counter() - t0
    # the small sweep cleared while the big pass was still in flight
    assert not big.done()
    assert small_latency < 0.5
    assert big.result(30) == [True] * 3000
    svc.close()


def test_a_pile_admitted_as_small_is_routed_as_small():
    """The adaptive cutoff moves under the dispatcher (the completion
    thread writes its EMAs): a pile the gate admitted under one value is
    routed by that value. Re-read, a cutoff that had just shrunk sent 88
    items of a traced n64-inflight8 run to the reroute thread as a
    depth-full big pile, and cpu_reroute_items, which the benchmark holds
    to 0, counted a fallback that was never needed (ISSUE 34)."""
    dev, cpu = FakeDevice(gate=True), FakeCpu()
    svc = VerifyService(dev, cpu=cpu, cpu_cutoff=None)
    reads = []

    def cutoff():  # 100 to the gate, 10 to whoever reads after it
        reads.append(10 if reads else 100)
        return reads[-1]

    svc._cutoff = lambda: 100
    big = []
    for t in range(VerifyService.MAX_DEPTH):  # every device slot taken, none finishes
        big.append(svc.submit(_items(300, tag=bytes([t]))))
        for _ in range(400):  # one pile a pass: the next waits for this take
            if len(dev.batches) > t:
                break
            time.sleep(0.005)
    assert dev.batches == [300, 300]
    svc._cutoff = cutoff
    small = svc.submit(_items(50, tag=b"s"))
    assert small.result(10) == [True] * 50
    assert cpu.batches == [50]
    assert svc.cpu_reroute_items == 0 and svc.cpu_reroute_passes == 0
    assert svc.snapshot()["cpu_pass_items"] == 50
    dev.release()
    assert [f.result(10) for f in big] == [[True] * 300] * 2
    svc.close()


def test_a_stale_round_trip_estimate_is_probed_and_replaced():
    """Device passes alone sample the round trip, so an estimate that
    sends every pile to the CPU is never sampled again: one pass stalled
    for 1.5 s put it at 313 ms, the cutoff at 1,895 items, and a traced
    n16-inflight8 (piles of 130) ended with no device plane (ISSUE 34).
    Fresh, the high estimate routes the small pile to the CPU as before;
    stale, with nothing in flight, the pile is the probe and its round
    trip replaces the estimate; a pass in flight defers the probe."""
    dev, cpu = FakeDevice(), FakeCpu()
    svc = VerifyService(dev, cpu=cpu, cpu_cutoff=None)
    svc._rtt_ema = 0.313
    assert svc._cutoff() > 130
    assert svc.verify_batch(_items(130, tag=b"a")) == [True] * 130
    assert (cpu.batches, dev.batches, svc.rtt_probes) == ([130], [], 0)

    svc._rtt_sampled -= 2 * VerifyService.ESTIMATE_STALE_S
    assert svc.verify_batch(_items(130, tag=b"b")) == [True] * 130
    assert (cpu.batches, dev.batches, svc.rtt_probes) == ([130], [130], 1)
    assert svc.snapshot()["rtt_probes"] == 1
    assert svc.rtt_ms < 100 and svc._cutoff() < 130  # replaced, not averaged
    assert svc.verify_batch(_items(130, tag=b"c")) == [True] * 130
    assert (dev.batches, svc.rtt_probes) == ([130, 130], 1)  # a plain pass
    svc.close()

    dev, cpu = FakeDevice(gate=True), FakeCpu()
    svc = VerifyService(dev, cpu=cpu, cpu_cutoff=None)
    svc._cutoff = lambda: 200
    big = svc.submit(_items(300, tag=b"d"))
    for _ in range(400):
        if dev.batches:
            break
        time.sleep(0.005)
    svc._rtt_sampled -= 2 * VerifyService.ESTIMATE_STALE_S
    assert svc.submit(_items(130, tag=b"e")).result(10) == [True] * 130
    assert (cpu.batches, dev.batches, svc.rtt_probes) == ([130], [300], 0)
    dev.release()
    assert big.result(10) == [True] * 300
    svc.close()

    class BoomDevice(FakeDevice):  # a device that raises is probed once a period, not every pile
        def dispatch_batch(self, items):
            self.batches.append(len(items))
            raise RuntimeError("device gone")

    dev, cpu = BoomDevice(), FakeCpu()
    svc = VerifyService(dev, cpu=cpu, cpu_cutoff=None)
    svc._cutoff = lambda: 200
    svc._rtt_sampled -= 2 * VerifyService.ESTIMATE_STALE_S
    with pytest.raises(RuntimeError, match="device gone"):
        svc.verify_batch(_items(130, tag=b"f"))
    assert svc.verify_batch(_items(130, tag=b"g")) == [True] * 130
    assert (cpu.batches, dev.batches) == ([130], [130])
    svc.close()


class LadderHeavyDevice(FakeDevice):
    """A device whose passes wait `ladder_wait` seconds for table-free
    rows after the comb's verdicts, and say so as TpuVerifier does: on the
    monotonic counter `ladder_seconds`, in the finisher."""

    def __init__(self, comb_wait: float, ladder_wait: float):
        super().__init__()
        self.ladder_seconds = 0.0
        self._waits = (comb_wait, ladder_wait)

    def dispatch_batch(self, items):
        inner = super().dispatch_batch(items)

        def finish():
            time.sleep(sum(self._waits))
            self.ladder_seconds += self._waits[1]
            return inner()

        return finish


def test_ladder_heavy_passes_keep_piles_over_the_cutoff_on_the_device():
    """A deployment with more signers than tables (ISSUE 36): its passes
    take 60 ms where the comb alone would take 5, because 4 rows of 10
    run the ladder. The estimate the cutoff is made of leaves out what a
    pass waited for the ladder, so the cutoff stays where the comb puts it
    and a pile of a few hundred items still goes to the device; with the
    wait left in (a device that does not report it) the same traffic puts
    the cutoff over the pile and sends it to the CPU."""
    def drive(dev):
        cpu = FakeCpu()
        svc = VerifyService(dev, cpu=cpu, cpu_cutoff=None)
        svc._cpu_rate_ema = 25000.0
        svc._run_cpu = lambda batch, subs, stage=None: (
            cpu.batches.append(len(batch)),
            svc._resolve(subs, [it.sig == it.msg for it in batch]))
        for i in range(12):  # big piles: the EMA converges on their round trip
            assert svc.verify_batch(_items(1500, tag=b"p%d" % i)) == [True] * 1500
        cutoff = svc._cutoff()
        assert svc.verify_batch(_items(400, tag=b"q")) == [True] * 400
        svc.close()
        return cutoff, svc.rtt_ms, cpu.batches, dev.batches

    cutoff, rtt_ms, on_cpu, on_dev = drive(LadderHeavyDevice(0.005, 0.055))
    assert rtt_ms < 25 and cutoff < 400
    assert (on_cpu, on_dev) == ([], [1500] * 12 + [400])

    class Unreported(LadderHeavyDevice):
        ladder_seconds = property(lambda self: 0.0, lambda self, v: None)

    cutoff, rtt_ms, on_cpu, on_dev = drive(Unreported(0.005, 0.055))
    assert rtt_ms > 45 and cutoff > 400
    assert (on_cpu, on_dev) == ([400], [1500] * 12)


def test_cpu_reroute_resolves_submissions_progressively():
    """Chunked reroute: submissions coalesced into one rerouted take
    resolve in order as their chunk completes — the first submitter
    never waits for the last one's items."""
    from concurrent.futures import Future

    svc = VerifyService(FakeDevice(), cpu=SlowCpu(per_item_s=0.0001))
    svc.REROUTE_CHUNK = 64  # instance override: 4 chunks below
    order = []
    subs = []
    for k in range(4):
        fut = Future()
        fut.add_done_callback(lambda _f, k=k: order.append(k))
        subs.append((_items(64, tag=bytes([65 + k])), fut))
    svc._run_cpu_chunked(subs)
    assert order == [0, 1, 2, 3]
    assert svc.cpu_reroute_chunks == 4
    for _items_k, fut in subs:
        assert fut.result(0) == [True] * 64
    svc.close()
