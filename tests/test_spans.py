"""Critical-path span profiler + wedge autopsy (ISSUE 4 tentpole).

Acceptance pins: (1) a traced LocalCommittee run yields a
tools/critical_path.py decomposition whose per-slot stage sums reconcile
with the measured end-to-end commit latency within 15%; (2) an injected
device stall (faults.StallableDevice) produces an autopsy dump naming
the stalled stage. Satellites pinned here: event-loop lag gauge,
--trace-sample fraction mode + trace_dropped, SIGTERM-path final
autopsy through node._dump_final.
"""

import asyncio
import json
import os
import sys
import time

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))

import critical_path  # noqa: E402  (tools/ is not a package)

from simple_pbft_tpu import spans  # noqa: E402
from simple_pbft_tpu.committee import LocalCommittee  # noqa: E402
from simple_pbft_tpu.crypto.coalesce import VerifyService  # noqa: E402
from simple_pbft_tpu.faults import StallableDevice  # noqa: E402
from simple_pbft_tpu.telemetry import (  # noqa: E402
    LoopLagGauge,
    ProgressWatchdog,
    RequestTracer,
    diagnose_stall,
    resolve_sample_mod,
)


def run(coro, timeout=60):
    return asyncio.run(asyncio.wait_for(coro, timeout))


class EchoDevice:
    """Device double: verdict is sig == msg (the FakeDevice predicate)."""

    def __init__(self):
        self.device_calls = 0
        self.device_items = 0
        self.device_seconds = 0.0

    def dispatch_batch(self, items):
        items = list(items)
        self.device_calls += 1
        self.device_items += len(items)
        return lambda: [it.sig == it.msg for it in items]


class EchoCpu:
    def verify_batch(self, items):
        return [it.sig == it.msg for it in items]


class CpuDevice:
    """Real-crypto device double (the test_overload GatedCpuDevice shape
    minus the gate): StallableDevice supplies the stall, this supplies
    verdicts a real committee's signed traffic passes."""

    def __init__(self):
        from simple_pbft_tpu.crypto.verifier import best_cpu_verifier

        self._cpu = best_cpu_verifier()
        self.device_calls = 0
        self.device_items = 0
        self.device_seconds = 0.0

    def dispatch_batch(self, items):
        items = list(items)
        self.device_calls += 1
        self.device_items += len(items)
        return lambda: self._cpu.verify_batch(items)


# ---------------------------------------------------------------------------
# span recorder
# ---------------------------------------------------------------------------


def test_span_recorder_histograms_ring_and_sink(tmp_path):
    rec = spans.SpanRecorder(ring=4)
    rec.configure("t0", str(tmp_path / "t.spans.jsonl"))
    for i in range(6):
        rec.record("phase.prepare", 0.010, node="t0", view=0, seq=i + 1)
    rec.record("transport.queue", 0.002, n=17, persist=False)
    snap = rec.snapshot()
    assert snap["recorded"] == 7
    assert snap["stages"]["phase.prepare"]["count"] == 6
    assert snap["stages"]["transport.queue"]["count"] == 1  # hist: yes
    assert 8.0 < snap["stages"]["phase.prepare"]["p50"] < 16.0  # ms buckets
    # ring is bounded and excludes per-message persist=False stages —
    # an autopsy's recent window keeps the diagnostic pipeline spans
    recent = rec.recent()
    assert len(recent) == 4
    assert all(r["stage"] == "phase.prepare" for r in recent)
    assert recent[-1]["seq"] == 6
    rec.close()
    # sink got ONLY the persist=True spans, as parseable JSONL
    lines = [
        json.loads(ln)
        for ln in (tmp_path / "t.spans.jsonl").read_text().splitlines()
    ]
    assert len(lines) == 6
    assert all(ln["evt"] == "span" and ln["node"] == "t0" for ln in lines)
    assert lines[0]["dur_ms"] == pytest.approx(10.0, rel=0.01)


def test_verify_service_spans_cover_queue_and_device_paths():
    """The coalescing service's critical path attributes itself:
    admission-queue wait and device RTT for big piles, verify.cpu for
    size-routed small ones."""
    from simple_pbft_tpu.crypto.verifier import BatchItem

    base = spans.recorder().snapshot()["stages"]

    def count(stage):
        cur = spans.recorder().snapshot()["stages"].get(stage, {})
        return cur.get("count", 0) - (base.get(stage, {}).get("count", 0))

    svc = VerifyService(EchoDevice(), cpu=EchoCpu(), cpu_cutoff=8)
    items = [BatchItem(b"pk", bytes([i]), bytes([i])) for i in range(64)]
    assert svc.submit(items).result(10) == [True] * 64  # device (64 > 8)
    assert svc.submit(items[:4]).result(10) == [True] * 4  # cpu (4 <= 8)
    svc.close()
    assert count(spans.VERIFY_QUEUE) >= 2
    assert count(spans.VERIFY_DEVICE) >= 1
    assert count(spans.VERIFY_CPU) >= 1


# ---------------------------------------------------------------------------
# the verify round trip, split where it happens (ISSUE 38)
# ---------------------------------------------------------------------------

PASS_LEGS = (spans.VERIFY_DISPATCH, spans.VERIFY_FINISH_QUEUE,
             spans.VERIFY_RESULT_WAIT, spans.VERIFY_FINISH)


@pytest.fixture
def fresh_spans():
    spans.configure("test")
    yield
    spans._set_annotating(False)
    spans.configure("")


class SleepyDevice(EchoDevice):
    """EchoDevice whose dispatch takes ``d1`` seconds and whose finisher
    takes ``d2``, after waiting for ``gate`` where one is given."""

    def __init__(self, d1=0.0, d2=0.0, gate=None):
        super().__init__()
        self.d1, self.d2, self.gate = d1, d2, gate

    def dispatch_batch(self, items):
        time.sleep(self.d1)
        finish = super().dispatch_batch(items)

        def finisher():
            if self.gate is not None:
                assert self.gate.wait(30), "test gate never released"
            time.sleep(self.d2)
            return finish()

        return finisher


def _echo_items(n, tag):
    from simple_pbft_tpu.crypto.verifier import BatchItem

    return [BatchItem(b"pk", tag + bytes([i]), tag + bytes([i]))
            for i in range(n)]


@pytest.mark.parametrize("deadline", [None, 5.0])
def test_four_pass_legs_tile_the_device_round_trip(fresh_spans, deadline):
    d1, d2, passes = 0.004, 0.008, 4
    svc = VerifyService(SleepyDevice(d1, d2), cpu=EchoCpu(), cpu_cutoff=0,
                        dispatch_deadline=deadline)
    for k in range(passes):  # one pass each: the next waits for the last
        assert svc.submit(_echo_items(32, bytes([k]))).result(10) == [True] * 32
    svc.close()
    hists = spans.recorder()._hists
    assert hists[spans.VERIFY_DEVICE].count == passes
    for leg in PASS_LEGS:
        assert hists[leg].count == passes, leg
    legs_ms = sum(hists[leg].total for leg in PASS_LEGS)
    assert abs(legs_ms - hists[spans.VERIFY_DEVICE].total) < 1.0
    assert hists[spans.VERIFY_DISPATCH].vmin >= d1 * 1e3 * 0.99
    assert hists[spans.VERIFY_RESULT_WAIT].vmin >= d2 * 1e3 * 0.99


def test_the_way_back_is_recorded_per_resolved_submission(fresh_spans):
    """verify.return runs from the service's resolve to the replica's
    resume: a loop held 50 ms after the verdict lands reads it; an
    overload-rejected submission records nothing."""
    import threading

    from simple_pbft_tpu.crypto.coalesce import Overloaded

    gate = threading.Event()
    dev = SleepyDevice(gate=gate)
    svc = VerifyService(dev, cpu=EchoCpu(), cpu_cutoff=0, max_pending=100)
    com = LocalCommittee.build(n=4, clients=1, verifier_factory=lambda: svc)
    r0 = com.replicas[0]

    def returned():
        return spans.stage_summaries().get(spans.VERIFY_RETURN, {})

    async def scenario():
        loop = asyncio.get_running_loop()
        first = loop.create_task(r0._submit_verify(_echo_items(64, b"a")))
        await asyncio.sleep(0.05)  # dispatched; its finisher waits on the gate
        second = loop.create_task(r0._submit_verify(_echo_items(64, b"b")))
        await asyncio.sleep(0.05)  # pending behind it: 64 of the cap of 100
        assert dev.device_calls == 1
        with pytest.raises(Overloaded):
            await r0._submit_verify(_echo_items(64, b"c"))
        assert returned().get("count", 0) == 0
        gate.set()
        time.sleep(0.05)  # the loop is held while both verdicts land
        assert await first == [True] * 64
        assert await second == [True] * 64

    run(scenario())
    svc.close()
    assert returned()["count"] == 2
    assert returned()["min"] >= 45.0


def test_annotations_name_the_busy_legs_on_their_threads(
        fresh_spans, monkeypatch):
    import threading

    entered = []

    class Note:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            entered.append((self.name, threading.current_thread().name))
            return self

        def __exit__(self, *exc):
            pass

    monkeypatch.setattr(spans, "_TraceAnnotation", Note)
    spans._set_annotating(True)
    for deadline in (None, 5.0):
        svc = VerifyService(EchoDevice(), cpu=EchoCpu(), cpu_cutoff=0,
                            dispatch_deadline=deadline)
        assert svc.submit(_echo_items(16, b"n")).result(10) == [True] * 16
        svc.close()
    assert (spans.VERIFY_DISPATCH, "verify-dispatch") in entered
    assert (spans.VERIFY_RESULT_WAIT, "verify-complete") in entered
    assert (spans.VERIFY_RESULT_WAIT, "verify-finish") in entered
    assert not [e for e in entered if e[0] == spans.VERIFY_DEVICE]


SPLIT_METRICS = (
    "verify_dispatch_mean_ms", "verify_finish_queue_mean_ms",
    "verify_result_wait_mean_ms", "verify_finish_mean_ms",
    "verify_return_mean_ms", "verify_seat_wait_mean_ms",
)


@pytest.mark.parametrize("name", SPLIT_METRICS)
def test_each_split_metric_reads_a_stage_the_program_defines(name):
    root = os.path.join(os.path.dirname(__file__), "..")
    with open(os.path.join(root, "benchmark", "metrics", name + ".json")) as fh:
        spec = json.load(fh)
    kind, stage, stat = spec["source"].split(":")
    assert (kind, stat) == ("span", "mean")
    defined = {v for k, v in vars(spans).items()
               if k.isupper() and isinstance(v, str)}
    assert stage in defined
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        manifest = {m["name"]: m for m in json.load(fh)["per_layer"]}
    assert manifest[name]["layer"] == spec["layer"]


# ---------------------------------------------------------------------------
# acceptance: per-stage decomposition reconciles with commit latency
# ---------------------------------------------------------------------------


def test_slot_spans_reconcile_with_commit_latency(tmp_path):
    """The three phase.* spans tile pre-prepare -> execution, so the
    critical_path slot decomposition must agree with the replicas' own
    commit_ms histogram within 15% — the acceptance reconciliation."""

    async def scenario():
        spans.configure("recon", str(tmp_path / "recon.spans.jsonl"))
        com = LocalCommittee.build(n=4, clients=2)
        com.attach_tracers(sample_mod=1)
        com.start()
        try:
            for i in range(8):
                assert await com.clients[i % 2].submit(f"put k{i} {i}") == "ok"
            commit_means = [
                r.stats.commit_ms.summary()["mean"]
                for r in com.replicas
                if r.stats.commit_ms.count
            ]
            return sum(commit_means) / len(commit_means)
        finally:
            await com.stop()
            spans.recorder().close()

    commit_mean_ms = run(scenario())
    assert commit_mean_ms > 0
    loaded = critical_path.load_spans([str(tmp_path / "recon.spans.jsonl")])
    an = critical_path.analyze(loaded)
    assert an["slots_complete"] >= 8  # 8 blocks x 4 replicas, minus races
    # nonempty decomposition at every percentile, shares summing to ~1
    assert an["decomposition"]
    for d in an["decomposition"]:
        assert 0.99 < sum(d["shares"].values()) <= 1.01
    # the reconciliation: mean slot e2e vs mean measured commit latency
    assert an["slot_e2e_ms"]["mean"] == pytest.approx(
        commit_mean_ms, rel=0.15
    )


def test_critical_path_tool_renders_and_json(tmp_path):
    path = tmp_path / "x.spans.jsonl"
    with open(path, "w") as fh:
        for seq in range(1, 11):
            for stage, dur in (
                ("phase.prepare", 6.0), ("phase.commit", 3.0),
                ("phase.execute", 1.0),
            ):
                fh.write(json.dumps({
                    "evt": "span", "stage": stage, "node": "r0",
                    "view": 0, "seq": seq, "dur_ms": dur * seq,
                    "t_mono": float(seq),
                }) + "\n")
        fh.write("{torn line\n")  # must be skipped, not fatal
    loaded = critical_path.load_spans([str(path)])
    assert len(loaded) == 30
    an = critical_path.analyze(loaded, pcts=[50.0, 99.0])
    assert an["slots_complete"] == 10
    d99 = an["decomposition"][-1]
    assert d99["shares"]["phase.prepare"] == pytest.approx(0.6, abs=0.01)
    text = critical_path.render(an)
    assert "commit-path decomposition" in text
    assert "phase.prepare" in text
    json.dumps(an)  # --json output is serializable


# ---------------------------------------------------------------------------
# acceptance: injected device stall -> autopsy naming the stalled stage
# ---------------------------------------------------------------------------


def test_device_stall_produces_autopsy_naming_stage(tmp_path):
    """A 10 s-class silent device (faults.StallableDevice, the r5 qc256
    shape) must produce an autopsy file whose suspect names the device
    stage — with the service's own watchdog disabled, exactly the
    configuration that used to wedge in silence."""

    async def scenario():
        dev = StallableDevice(CpuDevice())
        # dispatch_deadline=None: the ISSUE-1 failover is OFF, so the
        # stall persists and the PROGRESS watchdog is the only alarm
        svc = VerifyService(dev, cpu_cutoff=0, dispatch_deadline=None)
        com = LocalCommittee.build(
            n=4, clients=1, verifier_factory=lambda: svc, view_timeout=120.0
        )
        com.clients[0].request_timeout = 120.0
        com.start()
        wd = ProgressWatchdog(
            com.node_telemetry("r0"),
            path=str(tmp_path / "r0.autopsy.json"),
            deadline=1.5,
            interval=0.2,
        )
        wd.start()
        try:
            dev.stall()  # device accepts work and goes silent
            pump = asyncio.create_task(com.clients[0].submit("put k v"))
            for _ in range(200):  # until the watchdog fires
                if wd.dumps:
                    break
                await asyncio.sleep(0.1)
            assert wd.dumps == 1, "stall must dump exactly once"
            dev.release()
            assert await pump == "ok"  # the run RECOVERS after release
        finally:
            await wd.stop()
            await com.stop()
            svc.close()

    run(scenario(), timeout=90)
    doc = json.loads((tmp_path / "r0.autopsy.json").read_text())
    assert doc["evt"] == "autopsy"
    assert doc["node"] == "r0"
    # the verdict names the stalled stage: a dispatched-but-unanswered
    # device pass, aged past any healthy RTT
    assert doc["suspect"]["stage"] == "verify.device"
    assert "in flight" in doc["suspect"]["detail"]
    snap = doc["snapshot"]
    assert snap["verify"]["inflight_oldest_age_s"] >= 1.0
    assert snap["verify"]["inflight_passes"] >= 1
    # forensics ride along: stacks, instance table, recent spans
    assert doc["threads"]  # thread stacks (verify-dispatch et al.)
    assert any(t["stack"] for t in doc["tasks"])
    assert isinstance(doc["instances_inflight"], list)
    assert isinstance(doc["spans_recent"], list)


def test_watchdog_stays_quiet_when_idle_or_progressing(tmp_path):
    """No outstanding work = no stall (an idle committee must not dump);
    steady progress re-arms but never fires."""

    async def scenario():
        com = LocalCommittee.build(n=4, clients=1)
        com.start()
        wd = ProgressWatchdog(
            com.node_telemetry("r0"),
            path=str(tmp_path / "idle.autopsy.json"),
            deadline=0.3,
            interval=0.1,
        )
        wd.start()
        try:
            await asyncio.sleep(0.8)  # idle past the deadline: quiet
            assert wd.dumps == 0
            for i in range(3):  # progressing: quiet
                assert await com.clients[0].submit(f"put p{i} {i}") == "ok"
                await asyncio.sleep(0.2)
            assert wd.dumps == 0
        finally:
            await wd.stop()
            await com.stop()

    run(scenario())
    assert not (tmp_path / "idle.autopsy.json").exists()


def test_watchdog_rearms_after_stall_clears_without_commit(tmp_path):
    """A stall that ends by SHEDDING (no commit ever lands) must re-arm
    the watchdog: the next, distinct wedge still gets its autopsy —
    zero-diagnostic-output is the failure mode this subsystem exists to
    kill, including the second time."""

    class StubReplica:
        executed_seq = 0
        instances = {}
        verifier = object()  # no _pending_items/_inflight attrs
        busy = True

        def has_outstanding_work(self):
            return self.busy

    class StubTelemetry:
        node_id = "stub"
        replica = StubReplica()

        def snapshot(self):
            return {}

    tel = StubTelemetry()
    wd = ProgressWatchdog(
        tel, path=str(tmp_path / "stub.autopsy.json"), deadline=0.05
    )
    wd._check()  # baseline: registers executed_seq, starts the clock
    time.sleep(0.06)
    wd._check()  # stall 1 fires
    assert wd.dumps == 1
    time.sleep(0.06)
    wd._check()  # same stall: one dump per stall, no spam
    assert wd.dumps == 1
    tel.replica.busy = False
    wd._check()  # work cleared WITHOUT a commit: must re-arm
    tel.replica.busy = True
    time.sleep(0.06)
    wd._check()  # distinct stall 2 fires again
    assert wd.dumps == 2


def test_persisted_counter_stops_when_sink_degrades(tmp_path):
    """ENOSPC-style sink death must not keep inflating the on-disk span
    count, and the degradation is surfaced in the snapshot."""
    rec = spans.SpanRecorder()
    rec.configure("deg", str(tmp_path / "deg.spans.jsonl"))
    rec.record("phase.prepare", 0.001)
    assert rec.persisted == 1
    rec._sink._fh.close()  # next write raises -> sink degrades
    rec.record("phase.prepare", 0.001)
    snap = rec.snapshot()
    assert snap["recorded"] == 2  # in-memory surfaces keep going
    assert snap["persisted"] == 1  # only what actually landed on disk
    assert snap["sink_write_errors"] == 1
    rec.close()


def test_final_dump_path_writes_autopsy(tmp_path):
    """The SIGTERM/SIGINT (and fatal-exception) path: node._dump_final
    with a watchdog attached writes the full forensic dump, not just
    counter log lines — to a DISTINCT file, so a mid-run stall autopsy
    at the watchdog's own path survives the shutdown (ISSUE 4
    satellite)."""

    async def scenario():
        from simple_pbft_tpu.node import _dump_final

        com = LocalCommittee.build(n=4, clients=1)
        com.start()
        r0 = com.replica("r0")
        try:
            assert await com.clients[0].submit("put k v") == "ok"
            # settle past the speculative fast answer (ISSUE 15): the
            # final dump below must snapshot a COMMITTED request
            for _ in range(100):
                if r0.metrics.get("committed_requests"):
                    break
                await asyncio.sleep(0.05)
            wd = ProgressWatchdog(
                com.node_telemetry("r0"),
                path=str(tmp_path / "r0.autopsy.json"),
                deadline=9999.0,  # never fires on its own
            )
            wd.dump("simulated mid-run stall")  # the evidence to preserve
            _dump_final("r0", r0, r0.transport, watchdog=wd)
        finally:
            await com.stop()

    run(scenario())
    final = json.loads((tmp_path / "r0.final.autopsy.json").read_text())
    assert final["reason"].startswith("final dump")
    assert final["snapshot"]["replica"]["metrics"]["committed_requests"] >= 1
    # the stall autopsy was NOT overwritten by the shutdown snapshot
    stall = json.loads((tmp_path / "r0.autopsy.json").read_text())
    assert stall["reason"] == "simulated mid-run stall"


# ---------------------------------------------------------------------------
# satellites: loop-lag gauge, trace-sample fraction mode, trace_dropped
# ---------------------------------------------------------------------------


# the stall is the test subject: the loop sanitizer would (correctly)
# attribute it to this test — declared, not suppressed
@pytest.mark.sanitize_allow("loop")
def test_loop_lag_gauge_sees_a_blocked_loop():
    async def scenario():
        g = LoopLagGauge(interval=0.05)
        g.start()
        await asyncio.sleep(0.15)  # healthy baseline samples
        time.sleep(0.3)  # block the loop (the starved-core shape)
        await asyncio.sleep(0.1)  # let the gauge take the late sample
        snap = g.snapshot()
        await g.stop()
        assert snap["samples"] >= 2
        assert snap["max_ms"] >= 200.0  # the block is visible
        return snap

    run(scenario())


def test_loop_lag_in_snapshot_and_diagnose():
    async def scenario():
        com = LocalCommittee.build(n=4, clients=1)
        g = com.attach_loop_lag(interval=0.05)
        await asyncio.sleep(0.2)
        snap = com.node_telemetry("r0").snapshot()
        assert "loop_lag" in snap
        assert snap["loop_lag"]["samples"] >= 1
        await com.stop()
        assert com.lag_gauge is None  # stop() tears the gauge down
        assert g.snapshot()["samples"] >= 1

    run(scenario())
    # diagnose: a starved loop with no queued crypto blames event_loop
    verdict = diagnose_stall({
        "loop_lag": {"ema_ms": 500.0, "max_ms": 900.0},
        "replica": {"instances": 3},
    })
    assert verdict["stage"] == "event_loop"


def test_diagnose_stall_orders_causes():
    dev = {
        "verify": {"inflight_passes": 1, "inflight_oldest_age_s": 12.0,
                   "pending_items": 900},
        "qc_lane": {"pending": 5},
    }
    assert diagnose_stall(dev)["stage"] == "verify.device"
    assert diagnose_stall({"qc_lane": {"pending": 5}})["stage"] == "qc.pairing"
    assert diagnose_stall(
        {"replica": {"ready_holes": 2, "executed_seq": 7}}
    )["stage"] == "phase.execute"
    assert diagnose_stall({})["stage"] == "unknown"


def test_trace_sample_fraction_and_modulus():
    assert resolve_sample_mod(0) == 0  # off
    assert resolve_sample_mod(-1) == 0
    assert resolve_sample_mod(1.0) == 1  # full-fidelity debug mode
    assert resolve_sample_mod(0.25) == 4  # fraction -> modulus
    assert resolve_sample_mod(128) == 128  # historical modulus spelling
    assert resolve_sample_mod(64.0) == 64


def test_trace_dropped_counts_sampling_loss():
    t = RequestTracer("n0", sample_mod=2)
    kept = sum(
        1 for ts in range(64) if t.rid_if_sampled("c0", ts) is not None
    )
    assert kept + t.trace_dropped == 64
    assert t.trace_dropped > 0  # mod 2 drops roughly half
    full = RequestTracer("n1", sample_mod=1)
    for ts in range(16):
        assert full.rid_if_sampled("c0", ts)
    assert full.trace_dropped == 0  # full fidelity: zero loss, provably
