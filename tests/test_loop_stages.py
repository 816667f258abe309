"""Loop-held stages (ISSUE 26): self-time arithmetic, the nine stages and
the heartbeat on a live n=4 committee, the annotation flag, and what the
accumulators must leave alone (``recorded``/``persisted``)."""

import asyncio
import gc

import pytest

from simple_pbft_tpu import clock, spans
from simple_pbft_tpu.committee import LocalCommittee
from simple_pbft_tpu.crypto.coalesce import VerifyService
from simple_pbft_tpu.crypto.verifier import best_cpu_verifier


def run(coro, timeout=60):
    return asyncio.run(asyncio.wait_for(coro, timeout))


class ScriptedClock:
    """``now()`` reads the next of the scripted times: exact arithmetic."""

    simulated = False

    def __init__(self, times):
        self._times = iter(times)

    def now(self) -> float:
        return next(self._times)


@pytest.fixture
def fresh_spans():
    spans.configure("test")
    yield
    spans._set_annotating(False)
    spans.configure("")


def held_ms(stage: str) -> dict:
    return spans.stage_summaries()[stage]


# ---------------------------------------------------------------------------
# self time
# ---------------------------------------------------------------------------


def test_nested_sections_charge_self_time_exactly(fresh_spans):
    # times, in seconds: route opens at 1, vote 2..4, send opens at 5 with
    # execute 6..7 nested in it and closes at 9, route closes at 16
    prev = clock.install(ScriptedClock([1, 2, 4, 5, 6, 7, 9, 16]))
    try:
        with spans.held(spans.LOOP_ROUTE) as sec:
            with spans.held(spans.LOOP_SIGN_VOTE):
                pass
            with spans.held(spans.LOOP_SEND, 63):
                with spans.held(spans.LOOP_EXECUTE, 7):
                    pass
            sec.n = 5
    finally:
        clock.install(prev)
    assert held_ms(spans.LOOP_ROUTE) == {
        "count": 1, "mean": 9000.0, "max": 9000.0, "sum": 9000.0, "n": 5}
    assert held_ms(spans.LOOP_SIGN_VOTE)["sum"] == 2000.0
    assert held_ms(spans.LOOP_SEND) == {
        "count": 1, "mean": 3000.0, "max": 3000.0, "sum": 3000.0, "n": 63}
    assert held_ms(spans.LOOP_EXECUTE)["sum"] == 1000.0
    # the four self times tile route's 15 s on the wall
    assert sum(held_ms(s)["sum"] for s in (
        spans.LOOP_ROUTE, spans.LOOP_SIGN_VOTE, spans.LOOP_SEND,
        spans.LOOP_EXECUTE)) == 15000.0
    assert spans.recorder().held_seconds() == 15.0


def test_charge_is_taken_out_of_the_open_section(fresh_spans):
    prev = clock.install(ScriptedClock([10, 20]))
    try:
        with spans.held(spans.LOOP_EXECUTE, 128):
            spans.charge(spans.LOOP_SIGN_REPLY, 2.5, 22)
            spans.charge(spans.LOOP_SEND, 1.5, 22)
    finally:
        clock.install(prev)
    assert held_ms(spans.LOOP_EXECUTE)["sum"] == 6000.0
    assert held_ms(spans.LOOP_SIGN_REPLY) == {
        "count": 1, "mean": 2500.0, "max": 2500.0, "sum": 2500.0, "n": 22}
    assert held_ms(spans.LOOP_SEND)["sum"] == 1500.0


def test_repeated_sections_keep_count_mean_and_max(fresh_spans):
    prev = clock.install(ScriptedClock([0, 1, 5, 8, 8, 8.5]))
    try:
        for _ in range(3):
            with spans.held(spans.LOOP_INGEST, 10):
                pass
    finally:
        clock.install(prev)
    got = held_ms(spans.LOOP_INGEST)
    assert (got["count"], got["n"]) == (3, 30)
    assert got["sum"] == 4500.0 and got["max"] == 3000.0
    assert got["mean"] * got["count"] == got["sum"]  # unrounded


def test_begin_end_is_held_without_the_object(fresh_spans):
    # route (begin/end) 1..16 around send (held) 5..9 and a vote 2..4
    prev = clock.install(ScriptedClock([1, 2, 4, 5, 9, 16]))
    try:
        spans.begin(spans.LOOP_ROUTE)
        try:
            spans.begin(spans.LOOP_SIGN_VOTE)
            spans.end(spans.LOOP_SIGN_VOTE)
            with spans.held(spans.LOOP_SEND, 15):
                pass
        finally:
            spans.end(spans.LOOP_ROUTE, 7)
    finally:
        clock.install(prev)
    assert spans._open == []
    assert held_ms(spans.LOOP_ROUTE) == {
        "count": 1, "mean": 9000.0, "max": 9000.0, "sum": 9000.0, "n": 7}
    assert held_ms(spans.LOOP_SIGN_VOTE)["sum"] == 2000.0
    assert held_ms(spans.LOOP_SEND)["n"] == 15
    assert held_ms(spans.LOOP_SEND)["sum"] == 4000.0


def test_a_parked_section_is_not_charged_the_time_away(fresh_spans):
    """An await that can suspend under open sections (the QC lane's
    verdict) is parked: another task's section meanwhile nests in
    nothing, and the time away leaves the parked sections' self time."""
    # route opens 0, execute opens 1, parks 2; ingest of another task
    # 3..5; resumes 8; execute closes 9, route closes 10
    clk = ScriptedClock([0, 1, 2, 3, 5, 8, 9, 10])

    async def scenario():
        async def other():
            with spans.held(spans.LOOP_INGEST):
                assert len(spans._open) == 3  # alone on the stack
        with spans.held(spans.LOOP_ROUTE):
            with spans.held(spans.LOOP_EXECUTE):
                task = asyncio.ensure_future(other())
                with spans.parked():
                    await task  # suspends: other() runs in between
                assert len(spans._open) == 6

    prev = clock.install(clk)
    try:
        asyncio.run(scenario())
    finally:
        clock.install(prev)
    assert spans._open == []
    assert held_ms(spans.LOOP_INGEST)["sum"] == 2000.0
    assert held_ms(spans.LOOP_EXECUTE)["sum"] == 2000.0  # 8 less 6 away
    assert held_ms(spans.LOOP_ROUTE)["sum"] == 2000.0    # 10 less execute's 8


def test_a_torn_stack_is_dropped_and_logged_not_raised(fresh_spans, caplog):
    """Two tasks suspended inside sections without parking them (what the
    contract forbids) close across each other: nothing raises, nothing is
    charged to the wrong stage, and the next section starts clean."""
    spans.begin(spans.LOOP_SEND)       # task A, then "suspends"
    spans.begin(spans.LOOP_ROUTE)      # task B, then "suspends"
    with caplog.at_level("ERROR", logger="pbft.spans"):
        spans.end(spans.LOOP_SEND)     # A resumes: not the innermost
    spans.end(spans.LOOP_ROUTE)        # B resumes: the stack is gone
    assert spans._open == []
    assert "spanned an await" in caplog.text
    assert spans.LOOP_SEND not in spans.stage_summaries()
    assert spans.LOOP_ROUTE not in spans.stage_summaries()
    with spans.held(spans.LOOP_INGEST):
        pass
    assert held_ms(spans.LOOP_INGEST)["count"] == 1


@pytest.mark.parametrize("flavor", ["local", "tcp", "grpc"])
def test_no_transport_suspends_in_send_or_broadcast(flavor):
    """Sections span ``await transport.send/broadcast`` on the footing
    that they complete without suspending: one ``send(None)`` must run
    each coroutine to its end, to a peer, to self and to nobody."""
    async def scenario():
        # never started: a send to a peer goes as far as its outbox
        peers = {"a": ("127.0.0.1", 1), "b": ("127.0.0.1", 2)}
        if flavor == "local":
            from simple_pbft_tpu.transport.local import LocalNetwork

            net = LocalNetwork()
            net.endpoint("b")
            t = net.endpoint("a")
        elif flavor == "tcp":
            from simple_pbft_tpu.transport.tcp import TcpTransport

            t = TcpTransport("a", peers["a"], peers)
        else:
            pytest.importorskip("grpc")
            from simple_pbft_tpu.transport.grpc import GrpcTransport

            t = GrpcTransport("a", peers["a"], peers)
        try:
            for coro in (t.send("b", b"{}"), t.send("a", b"{}"),
                         t.send("nobody", b"{}"),
                         t.broadcast(b"{}", ["a", "b"])):
                with pytest.raises(StopIteration):
                    coro.send(None)  # anything yielded would be a suspension
        finally:
            stop = getattr(t, "stop", None)
            if stop is not None:
                await stop()

    run(scenario())


def test_an_exception_closes_its_sections(fresh_spans):
    with pytest.raises(ValueError):
        with spans.held(spans.LOOP_ROUTE):
            with spans.held(spans.LOOP_EXECUTE):
                raise ValueError("boom")
    assert spans._open == []
    assert held_ms(spans.LOOP_ROUTE)["count"] == 1
    assert held_ms(spans.LOOP_EXECUTE)["count"] == 1


# ---------------------------------------------------------------------------
# what the accumulators leave alone
# ---------------------------------------------------------------------------


def test_accumulators_stay_out_of_recorded_persisted_ring_and_file(
        fresh_spans, tmp_path):
    path = tmp_path / "t.spans.jsonl"
    spans.configure("t", str(path))
    spans.record(spans.PHASE_PREPARE, 0.004, node="r0", view=0, seq=1)
    before = (spans.snapshot()["recorded"], spans.snapshot()["persisted"],
              spans.recent(), path.read_text())
    with spans.held(spans.LOOP_ROUTE):
        spans.charge(spans.LOOP_SEND, 0.001, 3)
    spans.LoopBeat().tick(0.002)
    after = (spans.snapshot()["recorded"], spans.snapshot()["persisted"],
             spans.recent(), path.read_text())
    assert after == before == (1, 1, before[2], before[3])
    stages = spans.snapshot()["stages"]
    # beside the histogram stages, in their shape
    assert stages[spans.PHASE_PREPARE]["count"] == 1
    for stage in (spans.LOOP_ROUTE, spans.LOOP_SEND, spans.LOOP_LAG,
                  spans.LOOP_OFFCPU, spans.LOOP_UNATTRIBUTED):
        assert {"count", "mean", "max"} <= set(stages[stage])
        assert stages[stage]["count"] == 1
    assert stages[spans.LOOP_LAG]["sum"] == pytest.approx(2.0)
    assert list(stages) == sorted(stages)


def test_configure_resets_the_accumulators(fresh_spans):
    with spans.held(spans.LOOP_ROUTE):
        pass
    beat = spans.LoopBeat()
    for _ in range(200):
        with spans.held(spans.LOOP_INGEST):
            pass
    assert spans.recorder().held_seconds() > 0.0
    spans.configure("again")
    assert spans.stage_summaries() == {}
    assert spans.recorder().held_seconds() == 0.0
    # a heartbeat that straddles the reset charges what came after it
    with spans.held(spans.LOOP_SEND):
        pass
    beat.tick(0.0)
    got = spans.stage_summaries()
    assert got[spans.LOOP_UNATTRIBUTED]["sum"] >= 0.0
    assert got[spans.LOOP_OFFCPU]["sum"] >= 0.0
    assert set(got) == {spans.LOOP_SEND, spans.LOOP_LAG, spans.LOOP_OFFCPU,
                        spans.LOOP_UNATTRIBUTED}


def test_under_the_virtual_clock_stages_read_zero(fresh_spans):
    class Frozen:
        simulated = True

        def now(self) -> float:
            return 42.0

    prev = clock.install(Frozen())
    try:
        with spans.held(spans.LOOP_ROUTE):
            with spans.held(spans.LOOP_SEND):
                pass
        spans.LoopBeat().tick(0.0)
    finally:
        clock.install(prev)
    got = spans.stage_summaries()
    assert got[spans.LOOP_ROUTE]["count"] == 1
    assert got[spans.LOOP_ROUTE]["sum"] == got[spans.LOOP_SEND]["sum"] == 0.0
    # only the lag is kept: thread CPU time against virtual time is noise
    assert set(got) == {spans.LOOP_ROUTE, spans.LOOP_SEND, spans.LOOP_LAG}


# ---------------------------------------------------------------------------
# the annotation flag
# ---------------------------------------------------------------------------


class FakeAnnotation:
    built: list = []

    def __init__(self, name):
        FakeAnnotation.built.append(name)
        self.name = name
        self.open = False

    def __enter__(self):
        self.open = True
        return self

    def __exit__(self, *exc):
        self.open = False


def test_no_annotation_is_built_while_the_flag_is_off(
        fresh_spans, monkeypatch):
    FakeAnnotation.built = []
    monkeypatch.setattr(spans, "_TraceAnnotation", FakeAnnotation)
    assert spans.annotating() is False
    with spans.held(spans.LOOP_ROUTE):
        with spans.held(spans.LOOP_SEND):
            pass
    with spans.annotation(spans.VERIFY_HOST_PREP):
        pass
    gc.collect()
    assert FakeAnnotation.built == []

    spans._set_annotating(True)
    spans.watch_gc(True)
    try:
        with spans.held(spans.LOOP_ROUTE):
            with spans.held(spans.LOOP_SEND):
                pass
        with spans.annotation(spans.VERIFY_COLLECT) as note:
            assert note.open
        gc.collect()
    finally:
        spans.watch_gc(False)
        spans._set_annotating(False)
    assert FakeAnnotation.built[:3] == [
        spans.LOOP_ROUTE, spans.LOOP_SEND, spans.VERIFY_COLLECT]
    assert spans.GC_PAUSE in FakeAnnotation.built
    built = len(FakeAnnotation.built)
    with spans.held(spans.LOOP_ROUTE):
        pass
    assert len(FakeAnnotation.built) == built  # off again


def test_the_flag_follows_an_open_capture_and_names_the_stages(
        fresh_spans, tmp_path):
    """The real profiler: a capture opened through jax.profiler directly
    (as benchmark/run.py opens it) turns annotation on at the next
    heartbeat tick, and the stage names land in the trace's host plane."""
    import glob

    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    beat = spans.LoopBeat()
    beat.tick(0.0)
    assert spans.annotating() is False
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        beat.tick(0.0)
        assert spans.annotating() is True
        with spans.held(spans.LOOP_ROUTE):
            with spans.held(spans.LOOP_SIGN_VOTE):
                pass
        with spans.annotation(spans.VERIFY_HOST_PREP):
            pass
    finally:
        jax.profiler.stop_trace()
    beat.tick(0.0)
    assert spans.annotating() is False
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    names = {e.name for plane in ProfileData.from_file(path).planes
             if plane.name == "/host:CPU"
             for line in plane.lines for e in line.events}
    assert {spans.LOOP_ROUTE, spans.LOOP_SIGN_VOTE,
            spans.VERIFY_HOST_PREP} <= names


# ---------------------------------------------------------------------------
# a live committee
# ---------------------------------------------------------------------------

# accumulator updates of the nine stages per committed request at n=4 with
# blocks of up to 4: a count, so it holds on any machine. At n=4 a request
# costs about 20 (measured 19.6); the bound leaves room for sweeps that
# split differently, and fails if sections go back to one per message
UPDATES_PER_REQUEST_MAX = 40


def test_all_nine_stages_and_the_heartbeat_count_on_a_live_committee(
        fresh_spans):
    async def scenario():
        svc = VerifyService(_NoDevice(), cpu=best_cpu_verifier())
        com = LocalCommittee.build(
            n=4, clients=2, max_batch=4, verifier_factory=lambda: svc)
        com.start()
        assert com.lag_gauge is not None  # the heartbeat starts with it
        spans.configure("live")
        try:
            async def pump(i: int) -> None:
                for k in range(25):
                    assert await com.clients[i % 2].submit(
                        f"put k{i} v{k}") == "ok"

            await asyncio.gather(*(pump(i) for i in range(4)))
            for _ in range(200):  # past the speculative answers
                if all(r.executed_seq == com.replicas[0].executed_seq
                       and not r.ready for r in com.replicas):
                    break
                await asyncio.sleep(0.01)
            gc.collect()
            await asyncio.sleep(0.12)  # two heartbeat ticks at least
            committed = com.replicas[0].metrics["committed_requests"]
            return committed, spans.snapshot()
        finally:
            await com.stop()
            svc.close()

    committed, snap = run(scenario())
    stages = snap["stages"]
    assert committed == 100
    for stage in spans.LOOP_STAGES:
        assert stages[stage]["count"] > 0, stage
        assert stages[stage]["sum"] > 0.0, stage
    for stage in (spans.LOOP_LAG, spans.LOOP_OFFCPU, spans.LOOP_UNATTRIBUTED,
                  spans.GC_PAUSE, spans.GC_FULL):
        assert stages[stage]["count"] > 0, stage
    assert stages[spans.GC_PAUSE]["n"] >= 2  # generations: one full one
    # the full one is in both, so gc.full is a part of gc.pause
    assert stages[spans.GC_FULL]["count"] <= stages[spans.GC_PAUSE]["count"]
    assert 0.0 < stages[spans.GC_FULL]["sum"] <= stages[spans.GC_PAUSE]["sum"]
    # what each stage handled
    assert stages[spans.LOOP_EXECUTE]["n"] >= 2 * 4 * committed  # spec + final
    assert stages[spans.LOOP_CLIENT]["n"] >= 3 * committed  # 2f+1 replies each
    assert stages[spans.LOOP_SIGN_REPLY]["n"] >= 3 * committed
    assert stages[spans.LOOP_SEND]["n"] > stages[spans.LOOP_SEND]["count"]
    updates = sum(stages[s]["count"] for s in spans.LOOP_STAGES)
    assert updates / committed < UPDATES_PER_REQUEST_MAX
    # the histogram stages are where they were, and only they are counted
    assert stages[spans.PHASE_COMMIT]["count"] > 0
    assert snap["recorded"] == sum(
        s["count"] for name, s in stages.items() if "p50" in s)
    # stopping the committee took the gc hook out again
    assert spans._on_gc not in gc.callbacks


class _NoDevice:
    """The service's device seat; best_cpu_verifier takes every pile (the
    adaptive cutoff keeps small piles on the CPU), as in test_coalesce."""

    device_calls = device_items = 0
    device_seconds = 0.0

    def dispatch_batch(self, items):
        items = list(items)
        cpu = best_cpu_verifier()
        return lambda: cpu.verify_batch(items)
