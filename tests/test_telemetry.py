"""Telemetry plane (ISSUE 2 tentpole): unified snapshot schema, live
/metrics.json exposure mid-run, crash-surviving flight recorder, and
sampled phase-level request tracing that joins client and replica events
by request id."""

import asyncio
import json
import time

import pytest

from simple_pbft_tpu.committee import LocalCommittee
from simple_pbft_tpu.telemetry import (
    SCHEMA_VERSION,
    FlightRecorder,
    NodeTelemetry,
    RequestTracer,
    StatusServer,
    trace_sampled,
)


def run(coro, timeout=60):
    return asyncio.run(asyncio.wait_for(coro, timeout))


async def _http_get(port: int, path: str):
    """Raw HTTP/1.0 GET against the status server; returns (status, body)."""
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    writer.write(f"GET {path} HTTP/1.0\r\nHost: x\r\n\r\n".encode())
    await writer.drain()
    raw = await reader.read()
    writer.close()
    await writer.wait_closed()
    head, _, body = raw.partition(b"\r\n\r\n")
    status = int(head.split()[1])
    return status, body


# ---------------------------------------------------------------------------
# unified snapshot
# ---------------------------------------------------------------------------


def test_snapshot_schema_on_idle_node():
    """An IDLE node's snapshot carries the full stable schema — zeroed
    histograms included (the logutil satellite) — so consumers never
    key-error before traffic arrives."""

    async def scenario():
        com = LocalCommittee.build(n=4, clients=1)
        snap = com.node_telemetry("r0").snapshot()
        assert snap["schema"] == SCHEMA_VERSION
        assert snap["node"] == "r0"
        rep = snap["replica"]
        assert rep["view"] == 0 and rep["executed_seq"] == 0
        assert rep["is_primary"] is True  # r0 is view-0 primary
        # idle histograms: full zeroed schema, no KeyError
        for h in ("sweep_ms", "commit_ms", "spec_reply_ms", "sweep_size"):
            assert rep["stats"][h]["p99"] == 0.0
            assert rep["stats"][h]["count"] == 0
        # idle transport: the FULL shared counter schema, all zero
        # (ISSUE 12 satellite: local aligned with tcp/grpc), plus an
        # empty wire-accounting block
        from simple_pbft_tpu.transport.base import COUNTER_SCHEMA

        assert snap["transport"]["metrics"] == {k: 0 for k in COUNTER_SCHEMA}
        assert snap["transport"]["wire"]["sent_msgs"] == 0
        # plain CPU verifier: name only (nothing to overload)
        assert "name" in snap["verify"]
        # the whole snapshot is JSON-serializable (flight recorder / HTTP)
        json.dumps(snap)

    run(scenario())


def test_snapshot_absorbs_all_four_surfaces_after_traffic():
    async def scenario():
        com = LocalCommittee.build(n=4, clients=1)
        com.start()
        try:
            assert await com.clients[0].submit("put k v") == "ok"
            # the submit may resolve on the speculative fast path
            # (ISSUE 15): settle until r0's commit lands
            r0 = com.replica("r0")
            for _ in range(100):
                if r0.metrics.get("committed_requests"):
                    break
                await asyncio.sleep(0.05)
            snap = com.node_telemetry("r0").snapshot()
            rep = snap["replica"]
            assert rep["metrics"]["committed_requests"] == 1
            assert rep["executed_seq"] == 1
            assert rep["stats"]["commit_ms"]["count"] >= 1
            assert snap["transport"]["metrics"]["recv"] > 0
            cli = com.node_telemetry("c0").snapshot()
            assert cli["client"]["id"] == "c0"
            assert cli["client"]["inflight"] == 0
        finally:
            await com.stop()

    run(scenario())


# ---------------------------------------------------------------------------
# live HTTP exposure
# ---------------------------------------------------------------------------


async def _executed(com, seq: int = 1, timeout: float = 10.0) -> None:
    """Wait until every replica has executed ``seq``. The client accepts
    2f+1 SPECULATIVE replies before any replica has executed the block,
    so a test that reads execution state right after ``submit`` returns
    races the commit phase."""
    deadline = time.monotonic() + timeout
    while any(r.executed_seq < seq for r in com.replicas):
        assert time.monotonic() < deadline, [
            (r.id, r.executed_seq) for r in com.replicas]
        await asyncio.sleep(0.01)


def test_status_server_serves_metrics_mid_run():
    """Acceptance criterion: scraping a node's /metrics.json MID-RUN
    returns the unified snapshot — no shutdown required."""

    async def scenario():
        com = LocalCommittee.build(n=4, clients=1)
        com.start()
        srv = StatusServer(com.node_telemetry("r0"), port=0)
        await srv.start()
        try:
            assert await com.clients[0].submit("put k v") == "ok"
            await _executed(com)
            status, body = await _http_get(srv.bound_port, "/metrics.json")
            assert status == 200
            snap = json.loads(body)
            assert snap["schema"] == SCHEMA_VERSION
            assert snap["replica"]["metrics"]["committed_requests"] >= 1
            status, body = await _http_get(srv.bound_port, "/healthz")
            assert status == 200
            hz = json.loads(body)
            assert hz["ok"] is True and hz["node"] == "r0"
            status, _ = await _http_get(srv.bound_port, "/nope")
            assert status == 404
        finally:
            await srv.stop()
            await com.stop()

    run(scenario())


def test_healthz_reports_degraded_and_stopped():
    async def scenario():
        com = LocalCommittee.build(n=4, clients=1)
        com.start()
        r0 = com.replica("r0")
        srv = StatusServer(com.node_telemetry("r0"), port=0)
        await srv.start()
        try:
            r0.metrics["degraded_mode"] = 1
            _, body = await _http_get(srv.bound_port, "/healthz")
            assert json.loads(body)["degraded"] is True
            r0.kill()  # crash-stop: /healthz flips to 503, still serving
            status, body = await _http_get(srv.bound_port, "/healthz")
            assert status == 503
            assert json.loads(body)["ok"] is False
        finally:
            await srv.stop()
            await com.stop()

    run(scenario())


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------


def test_flight_recorder_timeline_survives_crash_stop(tmp_path):
    """The r5 lesson: a node that never shuts down cleanly must still
    leave a telemetry timeline. Lines are flushed per snapshot, so after
    kill() (crash-stop, no stop()/close()) the JSONL already on disk
    reconstructs the run."""

    async def scenario():
        com = LocalCommittee.build(n=4, clients=1)
        com.start()
        path = str(tmp_path / "r0.flight.jsonl")
        rec = FlightRecorder(
            com.node_telemetry("r0"), path, interval=0.05
        )
        rec.start()
        try:
            assert await com.clients[0].submit("put k v") == "ok"
            await asyncio.sleep(0.25)
            com.replica("r0").kill()  # SIGKILL stand-in: no clean shutdown
            await asyncio.sleep(0.1)
            # read WITHOUT stopping the recorder: what's on disk now is
            # exactly what a post-mortem of a dead process would find
            lines = [
                json.loads(ln)
                for ln in open(path).read().splitlines()
                if ln.strip()
            ]
            assert len(lines) >= 3
            assert all(ln["schema"] == SCHEMA_VERSION for ln in lines)
            assert all(ln["node"] == "r0" for ln in lines)
            # the timeline shows progress, then the crash-stop
            assert lines[-1]["replica"]["metrics"].get(
                "committed_requests", 0
            ) >= 1
            assert lines[-1]["replica"]["running"] is False
            # monotonic timestamps make deltas meaningful
            monos = [ln["t_mono"] for ln in lines]
            assert monos == sorted(monos)
        finally:
            await rec.stop()
            await com.stop()

    run(scenario())


# ---------------------------------------------------------------------------
# sampled phase-level request tracing
# ---------------------------------------------------------------------------


def test_trace_sampling_is_deterministic_and_proportional():
    assert trace_sampled("c0", 123, 1) is True
    assert trace_sampled("c0", 123, 0) is False
    # same decision everywhere, every time
    assert trace_sampled("c0", 999, 16) == trace_sampled("c0", 999, 16)
    hits = sum(1 for ts in range(4096) if trace_sampled("cX", ts, 16))
    assert 150 < hits < 370  # ~256 expected at 1/16


def test_trace_joins_client_and_replica_phases():
    """Acceptance criterion: a committed request's sampled trace yields
    the full per-phase lifecycle, joining client and replica events by
    request id, with monotonic per-phase timestamps."""

    async def scenario():
        com = LocalCommittee.build(n=4, clients=1)
        tracers = com.attach_tracers(sample_mod=1)  # trace everything
        com.start()
        try:
            assert await com.clients[0].submit("put traced v") == "ok"
            await _executed(com)
        finally:
            await com.stop()

        client_evs = tracers["c0"].recent()
        assert {e["phase"] for e in client_evs} >= {"submit", "accepted"}
        rids = {e["rid"] for e in client_evs}
        assert len(rids) == 1
        rid = rids.pop()
        assert rid.startswith("c0:")

        # primary (r0, view 0) stamps the whole replica-side lifecycle
        r0_evs = [e for e in tracers["r0"].recent() if e["rid"] == rid]
        phases = [e["phase"] for e in r0_evs]
        for ph in ("request", "pre_prepare", "prepare", "commit", "execute"):
            assert ph in phases, f"missing {ph} in {phases}"
        # per-phase latency decomposition: first stamp of each phase is
        # monotonic along the lifecycle
        order = ["request", "pre_prepare", "prepare", "commit", "execute"]
        t = [
            next(e["t_mono"] for e in r0_evs if e["phase"] == ph)
            for ph in order
        ]
        assert t == sorted(t)
        # slot ids ride along from pre_prepare on
        pp = next(e for e in r0_evs if e["phase"] == "pre_prepare")
        assert pp["view"] == 0 and pp["seq"] == 1
        assert len(pp["digest"]) == 64
        # a designated replier stamped the reply leg
        assert any(
            e["phase"] == "reply" and e["rid"] == rid
            for tr in tracers.values()
            for e in tr.recent()
        )
        # every node agreed on the sampling decision (same rid seen on
        # all replicas that executed the block)
        for node in ("r1", "r2", "r3"):
            assert any(
                e["rid"] == rid and e["phase"] == "execute"
                for e in tracers[node].recent()
            )

    run(scenario())


def test_trace_jsonl_sink_and_trace_endpoint(tmp_path):
    async def scenario():
        com = LocalCommittee.build(n=4, clients=1)
        tracers = com.attach_tracers(sample_mod=1, trace_dir=str(tmp_path))
        com.start()
        srv = StatusServer(com.node_telemetry("r0"), port=0)
        await srv.start()
        try:
            assert await com.clients[0].submit("put k v") == "ok"
            await _executed(com)
            status, body = await _http_get(srv.bound_port, "/trace.json")
            assert status == 200
            doc = json.loads(body)
            assert doc["node"] == "r0"
            assert any(e["phase"] == "execute" for e in doc["events"])
        finally:
            await srv.stop()
            await com.stop()
            for t in tracers.values():
                t.close()
        # file sink: line-flushed JSONL, one file per node, joinable
        r0_lines = [
            json.loads(ln)
            for ln in (tmp_path / "r0.trace.jsonl").read_text().splitlines()
        ]
        c0_lines = [
            json.loads(ln)
            for ln in (tmp_path / "c0.trace.jsonl").read_text().splitlines()
        ]
        assert {e["rid"] for e in r0_lines} & {e["rid"] for e in c0_lines}

    run(scenario())


def test_unsampled_requests_emit_nothing():
    async def scenario():
        com = LocalCommittee.build(n=4, clients=1)
        tracers = com.attach_tracers(sample_mod=0)  # sample nothing
        com.start()
        try:
            assert await com.clients[0].submit("put k v") == "ok"
        finally:
            await com.stop()
        assert all(not t.recent() for t in tracers.values())
        assert all(t.events_emitted == 0 for t in tracers.values())

    run(scenario())


# ---------------------------------------------------------------------------
# bench integration: start/end snapshots ride the record
# ---------------------------------------------------------------------------


def test_bench_committee_telemetry_aggregate():
    async def scenario():
        import bench_consensus

        com = LocalCommittee.build(n=4, clients=1)
        com.start()
        try:
            assert await com.clients[0].submit("put k v") == "ok"
            # settle past the speculative fast answer (ISSUE 15): the
            # aggregate must see every replica's commit applied
            await _executed(com)
            agg = bench_consensus._committee_telemetry(com)
            assert agg["schema"] == SCHEMA_VERSION
            assert agg["replicas_running"] == 4
            assert agg["exec_seq_min"] == agg["exec_seq_max"] == 1
            assert agg["replica_metrics"]["committed_requests"] == 4
            assert agg["transport"]["sent"] > 0
            json.dumps(agg)
        finally:
            await com.stop()

    run(scenario())
