"""Consensus-throughput benchmark: committed requests/s on a LocalCommittee.

BASELINE.md config ladder, measured end to end through the real stack
(signed wire messages, batch verification, ordered execution, replies):

  1. n=4  (f=1), CPU verify        — parity with the reference's run.bat
  2. n=16 (f=5), TPU batched verify (--verifier tpu)
  3. n=64, many concurrent clients, QC batching
  4. n=256, BLS aggregate quorum certificates (qc_mode: one pairing
     check per QC instead of 2f+1 signature checks; crypto/bls.py)
  5. n=64 view-change storm (--storm): crash the primary mid-load,
     measure failover + post-failover throughput.

The load is throughput-bound: `--outstanding` concurrent in-flight
requests are kept open per client (closed-loop with high concurrency),
so the committee pipelines many sequence numbers (the reference was
hard-serialized at one in-flight instance ≈ 0.3-0.5 req/s; SURVEY.md §6).

Prints ONE JSON line per config:
  {"config", "n", "committed_req_s", "p50_ms", "p99_ms", ...}

Platform selection follows --verifier and nothing else. ``tpu`` uses
JAX's default backend and requires it to BE a TPU: anywhere else the
script exits nonzero before building anything (a TpuVerifier on XLA:CPU
printing "verifier": "tpu" is the failure this guards). ``cpu`` and
``insecure`` select the CPU platform in-process, so on a machine with a
chip they leave it alone. Every record carries `platform`,
`device_kind` and `device_count` as JAX reports them. All n replicas and
the verifier live in this one process — the only arrangement in which a
committee can share a chip (see simple_pbft_tpu/launch.py).

Usage:
  python bench_consensus.py [--configs 1,2,3] [--verifier cpu|tpu]
      [--seconds 10] [--clients 8] [--outstanding 64] [--storm]
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import statistics
import sys
import time
from typing import List

import simple_pbft_tpu


def _emit(rec: dict) -> None:
    os.write(1, (json.dumps(rec) + "\n").encode())


def _start_watchdog(budget: float) -> None:
    """Hard wall-clock bound: dump every thread's stack to stderr and
    exit. A wedged scenario (e.g. a certificate-validation pile-up) must
    produce a diagnosable artifact, not an eternal process."""
    import faulthandler
    import threading
    import time as _t

    def fire():
        _t.sleep(budget)
        print(f"WATCHDOG: wall clock exceeded {budget:.0f}s", file=sys.stderr)
        faulthandler.dump_traceback(file=sys.stderr)
        _emit({"config": "watchdog-timeout", "budget_s": budget})
        os._exit(3)

    threading.Thread(target=fire, daemon=True).start()


from simple_pbft_tpu.client import SupersededError


def _committee_telemetry(com, service=None) -> dict:
    """Committee-wide aggregate of the unified telemetry plane
    (simple_pbft_tpu/telemetry.py): replica counters summed, transport
    counters summed, execution frontier spread, verify-service snapshot.
    Scraped at the start and end of the measurement window so every
    BENCH_*.json cell carries the telemetry that explains it."""
    from collections import defaultdict

    from simple_pbft_tpu.telemetry import SCHEMA_VERSION, wire_aggregate
    from simple_pbft_tpu.transport.base import wire_of

    agg, tx = defaultdict(int), defaultdict(int)
    wires = []
    for r in com.replicas:
        for k, v in r.metrics.items():
            agg[k] += v
        for k, v in getattr(r.transport, "metrics", {}).items():
            tx[k] += v
        w = wire_of(r.transport)
        if w is not None:
            wires.append(w.per_kind())
    exec_seqs = sorted(r.executed_seq for r in com.replicas)
    out = {
        "schema": SCHEMA_VERSION,
        "t_wall": round(time.time(), 3),
        "replicas_running": sum(1 for r in com.replicas if r._running),
        "exec_seq_min": exec_seqs[0] if exec_seqs else 0,
        "exec_seq_max": exec_seqs[-1] if exec_seqs else 0,
        "views": sorted({r.view for r in com.replicas}),
        "replica_metrics": dict(sorted(agg.items())),
        "transport": dict(sorted(tx.items())),
        # committee-wide per-kind msgs+bytes (ISSUE 12 wire accounting):
        # scraped at window start AND end so the record's wire block is a
        # pure measurement-window delta
        "wire_per_kind": wire_aggregate(wires),
    }
    if service is not None:
        out["verify"] = service.snapshot()
    return out


async def _pump(client, stop_at: float, latencies: List[float], errors: List[int]):
    """One closed-loop driver: keep exactly one request in flight, record
    per-request latency. Concurrency comes from running many of these.
    Retries are sized so total client patience (~(retries+1) x timeout)
    exceeds any plausible failover stall — a request abandoned by the
    pump vanishes from the latency distribution, silently flattering
    p99 exactly when the system was slowest."""
    i = 0
    # Patience must exceed the worst-case failover-plus-congestion
    # recovery or the sample is censored exactly when the system is
    # slowest: measured at n=64/QC on this one-core host, a view change
    # under chaos can take ~45 s to drain its queue backlog, and a
    # request committed at t+45 whose replies are still in flight is a
    # tail latency sample, not a timeout.
    # retry COUNT derived from the patience budget under the client's
    # backoff schedule (client.retries_for_patience): a fixed count
    # would mean minutes of tail patience now that retries back off
    retries = max(3, client.retries_for_patience(75.0))
    while time.perf_counter() < stop_at:
        t0 = time.perf_counter()
        try:
            await client.submit(
                f"put k{id(client) % 997}_{i % 64} {i}", retries=retries
            )
            # (completion time, latency): throughput is counted over the
            # measurement window only — a straggler finishing during the
            # drain tail must not deflate req/s by stretching `elapsed`
            latencies.append((time.perf_counter(), time.perf_counter() - t0))
        except (asyncio.TimeoutError, TimeoutError):
            errors.append(1)
        except SupersededError:
            # reply cache folded under a long storm before the client saw
            # f+1 matches: an explicit NACK, not a latency sample
            errors.append(1)
        i += 1


async def run_config(
    name: str,
    n: int,
    seconds: float,
    n_clients: int,
    outstanding: int,
    verifier: str,
    batch: int,
    storm: bool = False,
    qc_mode: bool = False,
    view_timeout: float = 0.0,
    chaos: dict = None,
    max_crashes: int = 3,
    fault_spec: str = None,
    verify_deadline: float = 60.0,
    verify_max_pending: int = 65536,
    status_port_base: int = 0,
    flight_dir: str = None,
    trace_sample: float = 0,
    stall_deadline: float = 30.0,
    device_profile: float = 0.0,
    speculative: bool = True,
) -> dict:
    from simple_pbft_tpu.committee import LocalCommittee
    from simple_pbft_tpu.crypto.coalesce import VerifyService
    from simple_pbft_tpu.crypto.tpu_verifier import TpuVerifier
    from simple_pbft_tpu.faults import (
        FaultInjector,
        FaultSchedule,
        SlowVerifier,
        StallableDevice,
    )
    from simple_pbft_tpu.transport.local import FaultPlan

    # deterministic fault schedule (simple_pbft_tpu/faults.py): the
    # chaos-on-TPU cell and the crash-count-matched storm A/B both key
    # off --fault-schedule so a run's faults are a pure function of its
    # seed — reproducible, host-independent, diffable between A/B arms
    schedule = None
    if isinstance(fault_spec, FaultSchedule):
        # --replay: the EXACT recorded schedule (rebuilt from a ledger
        # line's faults block via FaultSchedule.from_summary), never a
        # re-parse — replay must not depend on generate()'s dealing
        schedule = fault_spec
    elif fault_spec:
        schedule = FaultSchedule.parse(
            fault_spec, horizon=seconds,
            replica_ids=[f"r{i}" for i in range(n)],
        )

    factory = None
    slow_wrap = None
    n_keys = n + n_clients + 8  # committee + clients + headroom
    if verifier == "insecure":
        from simple_pbft_tpu.crypto.verifier import InsecureVerifier

        factory = InsecureVerifier
    if (
        schedule
        and verifier in ("cpu", "insecure")
        and any(e.kind == "slow_verifier" for e in schedule.events)
    ):
        from simple_pbft_tpu.crypto.verifier import (
            InsecureVerifier,
            best_cpu_verifier,
        )

        # one shared slow-armable wrapper so the injector has a single
        # seam; sharing a CPU verifier across replicas is safe (stateless
        # beyond the process-wide row cache, which is already shared)
        slow_wrap = SlowVerifier(
            InsecureVerifier() if verifier == "insecure"
            else best_cpu_verifier()
        )
        factory = lambda: slow_wrap  # noqa: E731
    if verifier == "tpu":
        simple_pbft_tpu.enable_jit_cache()
        # initial_keys pins every replica's key-table SHAPE to the final
        # key population: the jit signature includes that shape, so a
        # bank growing under live traffic means fresh 40-150 s compiles
        # serialized under the device lock mid-benchmark (measured: an
        # n=16 run burning its whole 120 s client patience compiling,
        # zero commits). Size once; warm at that exact shape below.
        #
        # ONE verifier shared by every replica: the committee shares one
        # key population, and per-replica banks would upload n copies of
        # the same table to one chip (n=64 at cap 128 is ~537 MB per
        # bank — 34 GB across replicas, over any single chip's HBM).
        # TpuVerifier is thread-safe (bank lock + device lock), exactly
        # for this shape of sharing. The VerifyService in front of it is
        # the round-5 architecture fix: every replica's sweep submits a
        # future and the service folds all pending work into ONE async
        # device pass (double-buffered), with a CPU path for tiny piles
        # — n sequential device round trips per round becomes ~1
        # (crypto/coalesce.py).
        shared_verifier = TpuVerifier(initial_keys=n_keys)
        device = shared_verifier
        if schedule is not None:
            # stall-injectable device front (faults.StallableDevice):
            # dispatches stay fast, finishers block while stalled — the
            # exact silent-device shape the service watchdog guards
            device = StallableDevice(shared_verifier)
        # overload resilience (ISSUE 1): bounded admission + the
        # dispatch-deadline watchdog with CPU failover + quarantine.
        # --verify-deadline 0 disables the watchdog (pre-ISSUE-1 shape).
        service = VerifyService(
            device,
            max_pending=verify_max_pending,
            dispatch_deadline=verify_deadline if verify_deadline > 0 else None,
        )
        factory = lambda: service  # noqa: E731

    plan = None
    if chaos:
        plan = FaultPlan(
            drop_rate=chaos["drop"],
            delay_range=(0.0, chaos["delay"]),
            duplicate_rate=chaos["dup"],
            seed=chaos["seed"],
        )
    # Degraded-mode (storm/chaos) failover timer: 3 s is right when
    # verify is a local CPU call. With a device whose sweep latency is
    # itself seconds a 3 s timer fires before ANY round can finish and
    # the committee view-changes perpetually from t=0, so the timer
    # scales with the verify backend. The 15 s was set on 2026-07-31
    # against device round trips of 82-372 ms (git
    # 7f473af:bench_results/chip_r05.jsonl); S1 re-derives it from the
    # round trip chip_smoke.py measures, and --view-timeout overrides it
    # meanwhile.
    degraded_vt = 3.0 if verifier in ("cpu", "insecure") else 15.0
    com = LocalCommittee.build(
        n=n,
        clients=n_clients,
        fault_plan=plan,
        verifier_factory=factory,
        max_batch=batch,
        view_timeout=view_timeout
        or (30.0 if not (storm or chaos or schedule) else degraded_vt),
        checkpoint_interval=64,
        watermark_window=1024,
        qc_mode=qc_mode,
        # ISSUE 15: speculative execution at PREPARED (on by default;
        # --no-spec is the A/B arm measuring the pre-speculation shape)
        speculative=speculative,
    )
    for c in com.clients:
        # Storms/chaos: the first send of a request can go to a crashed
        # primary (storm) or get dropped outright (chaos) and NOTHING
        # reaches the committee until this timer triggers the broadcast
        # retry — so it must be a small multiple of failover time, not a
        # lazy 30 s (which was the entire tail of every storm p99).
        # Clean steady-state benches keep the long timeout so retries
        # never distort throughput numbers.
        degraded = storm or bool(chaos) or schedule is not None
        c.request_timeout = (
            1.5 * (view_timeout or degraded_vt) if degraded else 30.0
        )
        if degraded:
            # hedged first sends: a crashed primary or a dropped frame
            # must not leave the request unknown to the whole committee
            # (see client.Client.hedge)
            c.hedge = 2

    if verifier == "tpu":
        # Pre-pay every (bucket, table-shape) compile BEFORE the timed
        # window, with the committee's REAL key population so the warmed
        # shapes are the ones live sweeps hit. The shared jit makes the
        # compiles process-wide, so one warmer covers all n replicas.
        # The warm budget must cover the COALESCED maximum, not one
        # replica's sweep: the service folds every replica's pending
        # items into one pile, so the first busy moment hits the top
        # bucket — an unwarmed bucket is a minutes-long compile at
        # dispatch, stalling the whole committee (caught by the r5
        # forced-CPU preflight: svc_max_coalesced=1917 wedged in the
        # 2048-bucket compile, zero commits).
        from simple_pbft_tpu.crypto.tpu_verifier import BUCKETS

        # Warm EVERY bucket: a per-round arithmetic bound is unsound —
        # while a multi-second device pass is in flight, each replica's
        # transport backlog accumulates several rounds (multiple
        # pre-prepares x batch client sigs per sweep, max_drain=4096
        # messages), and the service coalesces all replicas' sweeps, so
        # any bucket up to the service max is reachable under load. An
        # unwarmed bucket is a minutes-long compile under the device
        # lock mid-window; warm time is paid once, off the clock.
        need = BUCKETS[-1]
        t0 = time.perf_counter()
        shared_verifier.warm_for_population(
            [kp.pub for kp in com.keys.values()], max_sweep=need
        )
        print(
            f"warmed sweeps <= {need} at table cap "
            f"{shared_verifier._bank._cap} "
            f"in {time.perf_counter() - t0:.0f}s",
            file=sys.stderr,
        )
        # occupancy counters start at the timed window, not the warmup
        shared_verifier.device_calls = 0
        shared_verifier.device_items = 0
        shared_verifier.device_seconds = 0.0

    com.start()

    # live telemetry plane (ISSUE 2): per-replica /metrics.json endpoints
    # mid-run, crash-surviving flight-recorder timelines, and sampled
    # phase-level traces that join client and replica events. ISSUE 4
    # adds per-stage span attribution (spans.jsonl -> tools/
    # critical_path.py), the event-loop lag gauge, and per-replica
    # stall-autopsy watchdogs.
    from simple_pbft_tpu import spans as spans_mod
    from simple_pbft_tpu.telemetry import resolve_sample_mod

    status_servers = []
    recorders = []
    watchdogs = []
    tracers = {}
    lag_gauge = com.attach_loop_lag()
    # per-config span surface: configure() RESETS the process recorder,
    # so each ladder cell's rec["spans"] describes that cell alone, and
    # each cell gets its own <config>.spans.jsonl (critical_path
    # discovers *.spans.jsonl) instead of an append-mode mixture
    spans_mod.configure(
        name,
        os.path.join(flight_dir, f"{name}.spans.jsonl")
        if flight_dir else None,
    )
    # device-plane observatory (ISSUE 14): reset the per-dispatch device
    # ledger in lockstep with spans — after warm, per cell — so each
    # cell's rec["device"] aggregates describe that cell's window alone
    # and tools/verify_observatory.py can reconcile ledger vs spans
    from simple_pbft_tpu import devledger as devledger_mod

    devledger_mod.configure(name)
    if device_profile > 0 and flight_dir:
        devledger_mod.arm_profile(
            os.path.join(flight_dir, "device_profile"), device_profile
        )
    sample_mod = resolve_sample_mod(trace_sample)
    if sample_mod > 0:
        tracers = com.attach_tracers(
            sample_mod=sample_mod, trace_dir=flight_dir
        )
    # consensus audit plane (ISSUE 5): with a flight dir every replica
    # gets a SafetyAuditor — evidence + observation ledgers land next to
    # the flight timelines, so tools/ledger_audit.py can join the whole
    # committee's run post-hoc (and a --fault-schedule equiv=/forkckpt=
    # run proves detection end to end)
    auditors = {}
    if flight_dir:
        auditors = com.attach_auditors(log_dir=flight_dir)
    if status_port_base > 0 or flight_dir:
        from simple_pbft_tpu.telemetry import (
            FlightRecorder,
            ProgressWatchdog,
            StatusServer,
        )

        for i, r in enumerate(com.replicas):
            tel = com.node_telemetry(r.id)
            rec_f = None
            if status_port_base > 0:
                srv = StatusServer(tel, port=status_port_base + i)
                await srv.start()
                status_servers.append(srv)
            if flight_dir:
                rec_f = FlightRecorder(
                    tel,
                    os.path.join(flight_dir, f"{r.id}.flight.jsonl"),
                    interval=0.5,
                )
                rec_f.start()
                recorders.append(rec_f)
            if flight_dir and stall_deadline > 0 and not watchdogs:
                # wedge autopsy (ISSUE 4): a qc256-style silent stall in
                # a BENCH run now leaves <flight-dir>/<id>.autopsy.json
                # naming the stalled stage instead of a blank record.
                # ONE watchdog (the first replica), not n: in-process the
                # verify service, QC lane, task/thread stacks, and spans
                # are all process-wide, so a committee-wide stall would
                # trip every watchdog in the same poll interval and
                # serialize n near-identical full stack dumps on the
                # already-wedged loop (n=256: seconds of self-inflicted
                # freeze). One dump describes the committee; per-process
                # node.py deployments still get one per node.
                wd = ProgressWatchdog(
                    tel,
                    path=os.path.join(flight_dir, f"{r.id}.autopsy.json"),
                    deadline=stall_deadline,
                    flight=rec_f,
                )
                wd.start()
                watchdogs.append(wd)
                for aud in auditors.values():
                    # a safety violation fires the same forensic dump
                    # path as a stall (one autopsy per auditor)
                    aud.attach_watchdog(wd)
        if status_servers:
            print(
                f"telemetry: /metrics.json on 127.0.0.1:"
                f"{status_port_base}..{status_port_base + n - 1}",
                file=sys.stderr,
            )

    telemetry_start = _committee_telemetry(
        com, service if verifier == "tpu" else None
    )

    latencies: List[float] = []
    errors: List[int] = []
    t_start = time.perf_counter()
    stop_at = t_start + seconds
    per_client = max(1, outstanding // n_clients)
    pumps = [
        asyncio.create_task(_pump(c, stop_at, latencies, errors))
        for c in com.clients
        for _ in range(per_client)
    ]

    injector = None
    injector_task = None
    if schedule is not None:
        injector = FaultInjector(
            committee=com,
            schedule=schedule,
            service=service if verifier == "tpu" else None,
            slow=slow_wrap,
        )
        # the injector's deadline rides the CLOCK SEAM's timebase
        # (clock.now() — virtual under simulation), which shares no
        # epoch with the perf_counter-based bench window above
        from simple_pbft_tpu import clock as pbft_clock

        injector_task = asyncio.create_task(
            injector.run(pbft_clock.now() + seconds)
        )

    crash_info = {}
    if storm:
        # config 5: kill the primary mid-load REPEATEDLY; committee must
        # view-change and keep committing under each successor
        crashes = 0
        next_crash = t_start + seconds / 6
        while time.perf_counter() < stop_at - 1.0:
            await asyncio.sleep(0.2)
            if time.perf_counter() >= next_crash and crashes < max_crashes:
                view = max(r.view for r in com.replicas if r._running)
                target = com.replica(com.cfg.primary(view))
                if not target._running:
                    continue  # failover still in progress; don't double-count
                target.kill()  # crash-stop, no drain
                crashes += 1
                next_crash += seconds / 5
        crash_info = {"primary_crashes": crashes}

    await asyncio.gather(*pumps, return_exceptions=True)
    if injector_task is not None:
        injector.stop()  # cancel pending window restores (they restore)
        await asyncio.gather(injector_task, return_exceptions=True)
    elapsed = time.perf_counter() - t_start
    # throughput over the window; stragglers completing in the drain
    # tail still contribute their LATENCY samples below, honestly
    # fattening the percentiles instead of silently deflating req/s
    committed = sum(1 for done_at, _ in latencies if done_at <= stop_at)
    window = min(elapsed, seconds)
    # replica-side truth: total requests the (surviving) replicas executed
    exec_counts = sorted(
        r.metrics.get("committed_requests", 0) for r in com.replicas if r._running
    )
    # designated-replier fan-out: replies transmitted per committed
    # request committee-wide (cfg.repliers = f+1 plus loss spares;
    # everything beyond f+1 is deliberate redundancy, everything under
    # n is the rotation's savings vs reply-from-everyone)
    # (surviving replicas only, matching exec_counts — a crashed
    # replica's pre-crash replies would otherwise inflate the ratio)
    replies_sent = sum(
        r.metrics.get("replies_sent", 0) for r in com.replicas if r._running
    )
    # overload/degraded-mode evidence (ISSUE 1): how much inbound traffic
    # the priority shed dropped, how many sweeps the verify service
    # admission-rejected, and whether any replica is still flagged
    # degraded at window end. Client-side: retransmissions vs requests
    # that RECOVERED after a retry — the reconciliation for "unexplained
    # client timeouts" (VERDICT r5 weak #3): a shed-then-recovered
    # request now shows up here instead of vanishing into the timeout
    # column.
    shed_info = {
        "messages_shed": sum(
            r.metrics.get("messages_shed", 0) for r in com.replicas
        ),
        "sweeps_shed_overload": sum(
            r.metrics.get("sweeps_shed_overload", 0) for r in com.replicas
        ),
        "degraded_replicas": sum(
            1 for r in com.replicas if r.metrics.get("degraded_mode", 0)
        ),
        "client_retransmissions": sum(
            c.metrics.get("retransmissions", 0) for c in com.clients
        ),
        "client_recovered_after_retry": sum(
            c.metrics.get("recovered_after_retry", 0) for c in com.clients
        ),
    }
    if storm:
        # certificate-size evidence: the qc_mode claim is smaller failover
        # certificates — report the biggest ones actually built
        crash_info["max_viewchange_bytes"] = max(
            (r.metrics.get("max_viewchange_bytes", 0) for r in com.replicas),
            default=0,
        )
        crash_info["max_newview_bytes"] = max(
            (r.metrics.get("max_newview_bytes", 0) for r in com.replicas),
            default=0,
        )
    # verify-batch occupancy (VERDICT r3 #3): sampled BEFORE com.stop()
    # — stop() clears _running on every replica, which would always
    # empty this snapshot. Device-side numbers come from the SHARED
    # verifier's own counters, measured inside the device lock by the
    # holder: summing caller-side wall clocks across n replicas counts
    # lock wait once per blocked caller (up to n x underreport).
    verify_stats = {}
    if verifier == "tpu":
        v = shared_verifier
        verify_stats = dict(
            verify_calls=v.device_calls,
            verify_fresh_items=v.device_items,
            verify_batch_mean=(
                round(v.device_items / v.device_calls, 1)
                if v.device_calls
                else 0.0
            ),
            verify_ms_mean=(
                round(1e3 * v.device_seconds / v.device_calls, 1)
                if v.device_calls
                else 0.0
            ),
            verify_per_s_device=(
                round(v.device_items / v.device_seconds, 1)
                if v.device_seconds
                else 0.0
            ),
            # coalescing-service occupancy: how hard the device passes
            # actually batched across replicas, and what the CPU
            # small-batch path absorbed
            svc_device_passes=service.device_passes,
            svc_device_items=service.device_pass_items,
            svc_cpu_passes=service.cpu_passes,
            svc_cpu_items=service.cpu_pass_items,
            svc_max_coalesced=service.max_coalesced,
            svc_submissions=service.coalesced_submissions,
            svc_rtt_ms_ema=round(service.rtt_ms, 1),
            # overload-resilience evidence (ISSUE 1): bounded-admission
            # pressure, watchdog activity, and CPU reroute volume — the
            # post-mortem for any degraded window in this run
            svc_degraded=service.degraded,
            svc_max_pending_seen=service.max_pending_seen,
            svc_overload_rejections=service.overload_rejections,
            svc_watchdog_failovers=service.watchdog_failovers,
            svc_quarantine_probes=service.quarantine_probes,
            svc_cpu_reroute_passes=service.cpu_reroute_passes,
            svc_cpu_reroute_items=service.cpu_reroute_items,
            svc_cpu_reroute_chunks=service.cpu_reroute_chunks,
            svc_late_device_completions=service.late_device_completions,
            # shape stability (ISSUE 3): after warmup this must report
            # post_warm_compiles == 0 — a nonzero value means the run
            # paid a mid-window XLA compile (the r5 qc256 suspect)
            svc_device_shapes=shared_verifier.shape_snapshot(),
        )

    telemetry_end = _committee_telemetry(
        com, service if verifier == "tpu" else None
    )
    loop_lag = lag_gauge.snapshot()
    for wd in watchdogs:
        await wd.stop()
    for rec_f in recorders:
        await rec_f.stop()
    for srv in status_servers:
        await srv.stop()

    await com.stop()
    for tr in tracers.values():
        tr.close()
    for aud in auditors.values():
        aud.close()
    if verifier == "tpu":
        service.close()

    lat_ms = sorted(x * 1e3 for _, x in latencies)

    def _pctv(vals, p: float) -> float:
        # one percentile formula for every latency surface in the record
        # (p50_ms, the spec/final split): nearest-rank on a sorted list
        return vals[min(len(vals) - 1, int(p * len(vals)))] if vals else 0.0

    def pct(p: float) -> float:
        return _pctv(lat_ms, p)

    from simple_pbft_tpu.telemetry import (
        BENCH_SCHEMA_VERSION,
        wire_delta,
        wire_per_commit,
    )

    rec = {
        # the ledger's own schema stamp (ISSUE 12 satellite): the bench
        # ledger is what tools/bench_gate.py compares, and it had no
        # version while the telemetry snapshots have carried one since
        # PR 5 — the gate refuses cross-schema comparisons
        "schema_version": BENCH_SCHEMA_VERSION,
        # the device the numbers came from (main() selected it)
        **simple_pbft_tpu.device_stamp(),
        "config": name,
        "n": n,
        "qc_mode": qc_mode,
        "speculative": speculative,
        "chaos": chaos or None,
        "verifier": verifier,
        "clients": n_clients,
        "outstanding": per_client * n_clients,
        "batch": batch,
        "seconds": round(elapsed, 1),
        "window_s": round(window, 1),
        "committed_req_s": round(committed / window, 1),
        # full-run rate: every completed request over the whole wall
        # clock including the drain tail (VERDICT r4 weak #2 — a run
        # that completes all traffic at t=41 s after a 30 s window is a
        # slow-warmup run, not a dead one; the windowed number alone
        # cannot tell them apart)
        "full_run_req_s": round(len(latencies) / max(elapsed, 1e-9), 1),
        "drain_tail_s": round(max(0.0, elapsed - seconds), 1),
        "completed_total": len(latencies),
        "p50_ms": round(pct(0.50), 2),
        "p99_ms": round(pct(0.99), 2),
        "client_timeouts": len(errors),
        "replica_exec_min": exec_counts[0] if exec_counts else 0,
        "replica_exec_max": exec_counts[-1] if exec_counts else 0,
        "replies_sent": replies_sent,
        "reply_fanout": round(
            replies_sent / max(1, exec_counts[-1] if exec_counts else 1), 1
        ),
        "repliers_cfg": com.cfg.repliers,
        "vs_reference_req_s": round(committed / window / 0.4, 1),  # ref ~0.4/s
    }
    rec.update(shed_info)
    rec.update(verify_stats)
    rec.update(crash_info)
    # speculative execution (ISSUE 15): the p50/p99 split the roadmap
    # acceptance gates on — spec-accept latency (client submit -> 2f+1
    # matching speculative marks) vs final-commit confirmation latency
    # (submit -> f+1 final replies) — plus the replica-side slot
    # counters and the execute.spec/execute.final span histograms that
    # attribute the win per percentile (already in rec["spans"])
    spec_lat = sorted(
        lat * 1e3
        for c in com.clients
        for (lat, kind) in getattr(c, "accept_latencies", ())
        if kind == "spec"
    )
    confirm_lat = sorted(
        lat * 1e3
        for c in com.clients
        for lat in getattr(c, "confirm_latencies", ())
    )

    rec["spec"] = {
        "executed": sum(
            r.metrics.get("spec_executed", 0) for r in com.replicas
        ),
        "confirmed": sum(
            r.metrics.get("spec_confirmed", 0) for r in com.replicas
        ),
        "rolled_back": sum(
            r.metrics.get("spec_rolled_back", 0) for r in com.replicas
        ),
        "rollbacks": sum(
            r.metrics.get("spec_rollbacks", 0) for r in com.replicas
        ),
        "replies_sent": sum(
            r.metrics.get("spec_replies_sent", 0) for r in com.replicas
        ),
        "client_spec_accepted": sum(
            c.metrics.get("spec_accepted", 0) for c in com.clients
        ),
        "client_final_confirms": sum(
            c.metrics.get("final_confirms", 0) for c in com.clients
        ),
        "client_spec_final_mismatch": sum(
            c.metrics.get("spec_final_mismatch", 0) for c in com.clients
        ),
    }
    if spec_lat:
        rec["p50_spec_latency_ms"] = round(_pctv(spec_lat, 0.50), 2)
        rec["p99_spec_latency_ms"] = round(_pctv(spec_lat, 0.99), 2)
    if confirm_lat:
        rec["p50_final_latency_ms"] = round(_pctv(confirm_lat, 0.50), 2)
        rec["p99_final_latency_ms"] = round(_pctv(confirm_lat, 0.99), 2)
    # wire accounting (ISSUE 12 tentpole): the measurement window's
    # per-kind msgs+bytes and the derived per-commit costs — msgs/commit,
    # bytes/commit, per-phase broadcast amplification (the O(n²) storm,
    # previously visible only as the reply_fanout scalar, is now a
    # first-class per-phase number in every record)
    wire_kinds = wire_delta(
        telemetry_start.get("wire_per_kind", {}),
        telemetry_end.get("wire_per_kind", {}),
    )
    slots_delta = (
        telemetry_end.get("exec_seq_max", 0)
        - telemetry_start.get("exec_seq_max", 0)
    )
    rec["wire"] = {
        "per_kind": wire_kinds,
        "per_commit": wire_per_commit(
            wire_kinds, slots_delta, max(1, committed)
        ),
    }
    # QC-plane fast path (ISSUE 3): certificate-verify lane occupancy —
    # batch sizes, pairing latency, queue pressure. Present whenever any
    # QC was verified this process (qc_mode configs; None otherwise).
    from simple_pbft_tpu.consensus import qc as qc_lane_mod

    lane_snap = qc_lane_mod.lane_snapshot()
    if lane_snap is not None:
        rec["qc_lane"] = lane_snap
    # start/end unified snapshots: the cell carries the telemetry that
    # explains it (e.g. a low committed_req_s with end.verify.quarantined
    # true and messages_shed high IS the diagnosis, no log forensics)
    rec["telemetry"] = {"start": telemetry_start, "end": telemetry_end}
    # per-stage latency attribution (ISSUE 4): every cell now carries
    # the stage histograms that say WHERE its p99 went, plus the
    # event-loop lag gauge (a starved dispatcher core is visible) and
    # any stall autopsies the watchdogs wrote
    rec["spans"] = spans_mod.snapshot()["stages"]
    # device-plane observatory (ISSUE 14): the per-dispatch ledger's
    # aggregates — dispatch rate, occupancy, effective verifies/s, pad
    # waste, per-shape counts — as a first-class record block, the
    # surface tools/bench_gate.py device floors and
    # tools/verify_observatory.py gate on
    rec["device"] = devledger_mod.snapshot()
    rec["loop_lag"] = loop_lag
    if watchdogs:
        rec["autopsy_dumps"] = sum(wd.dumps for wd in watchdogs)
    if sample_mod > 0:
        rec["trace_events"] = sum(t.events_emitted for t in tracers.values())
        rec["trace_dropped"] = sum(t.trace_dropped for t in tracers.values())
    if auditors:
        # accountability summary: any safety violation during the run,
        # broken down by invariant, with the union of accused replicas —
        # zero across the board is the honest-run clean bill
        by_kind = {}
        accused = set()
        for aud in auditors.values():
            for k, v in aud.by_kind.items():
                by_kind[k] = by_kind.get(k, 0) + v
            accused.update(aud.accused_ever)
        rec["audit"] = {
            "violations": sum(a.violations for a in auditors.values()),
            "observations": sum(a.observations for a in auditors.values()),
            "by_kind": dict(sorted(by_kind.items())),
            "accused": sorted(accused),
        }
    if schedule is not None:
        rec["faults"] = schedule.summary()
        rec["faults_applied"] = injector.applied_count
        rec["faults_skipped"] = injector.skipped
        rec["fault_crashes"] = injector.crashes_applied
        # byzantine wrappers (equivocate / fork_checkpoint events): how
        # many frames were actually forged — a detection test asserting
        # "the auditor accused rX" must also prove rX really misbehaved
        rec["fault_byzantine_injections"] = injector.byzantine_injections
    return rec


async def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--configs", default="1")
    # insecure = accept-everything backend: measures the consensus-plane
    # ceiling with verification free — the asymptote a fully-overlapped
    # device offload approaches (and reference-parity mode: the
    # reference verifies nothing)
    ap.add_argument(
        "--verifier", default="cpu", choices=["cpu", "tpu", "insecure"]
    )
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--outstanding", type=int, default=128)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--storm", action="store_true")
    ap.add_argument(
        "--crashes", type=int, default=3,
        help="storm: number of primary crash-stops (successive crashes "
        "race each new view's first commit — the hardest variant)",
    )
    ap.add_argument(
        "--chaos", default=None,
        help="fault injection for the run, e.g. drop=0.02,delay=0.03,"
        "dup=0.01,seed=42 (reproduces the committed soak numbers)",
    )
    ap.add_argument(
        "--fault-schedule", default=None,
        help="deterministic seeded fault schedule (simple_pbft_tpu/"
        "faults.py), e.g. seed=42,crashes=3,drops=1,delays=1,stalls=1 — "
        "the reproducible chaos/storm cell; crash counts here give the "
        "crash-count-matched storm A/B (stalls need --verifier tpu). "
        "Byzantine injectors: equiv=N arms equivocating primaries, "
        "forkckpt=N checkpoint forkers — pair with --flight-dir so the "
        "audit plane's ledgers prove detection (docs/AUDIT.md)",
    )
    ap.add_argument(
        "--replay", default=None, metavar="RECORD",
        help="replay the EXACT fault schedule of a previous run from "
        "its bench record (a JSON file, or a .jsonl ledger — last line "
        "wins): the record's faults block carries the complete (seed, "
        "horizon, event list, kind-table crc) tuple, so the schedule "
        "reconstructs without the original CLI spec; --seconds is "
        "overridden by the recorded horizon",
    )
    ap.add_argument(
        "--verify-deadline", type=float, default=60.0,
        help="tpu verify service: device dispatch deadline in seconds "
        "before the watchdog fails the sweep over to the CPU verifier "
        "and quarantines the device path (0 disables)",
    )
    ap.add_argument(
        "--verify-max-pending", type=int, default=65536,
        help="tpu verify service: pending-item cap; submits past it are "
        "admission-rejected with Overloaded instead of queued",
    )
    ap.add_argument(
        "--status-port-base", type=int, default=0,
        help="live telemetry: serve each replica's /metrics.json at "
        "127.0.0.1:(base+i) during the run (0 disables) — scrape with "
        "tools/pbft_top.py --endpoints or curl",
    )
    ap.add_argument(
        "--flight-dir", default=None,
        help="write per-replica flight-recorder JSONL (and trace JSONL "
        "when --trace-sample is set) under this directory; a SIGKILLed "
        "run still leaves its snapshot timeline",
    )
    ap.add_argument(
        "--trace-sample", type=float, default=0,
        help="phase-level request tracing: N > 1 keeps ~1/N of requests "
        "(deterministic hash sampling); a fraction in (0, 1] keeps that "
        "share — '--trace-sample 1.0' is the explicit full-fidelity "
        "debug mode; 0 off. The record carries trace_dropped so "
        "sampling loss is measurable",
    )
    ap.add_argument(
        "--stall-deadline", type=float, default=30.0,
        help="wedge autopsy (needs --flight-dir): seconds without a "
        "commit (with work outstanding) before a replica dumps "
        "<flight-dir>/<id>.autopsy.json naming the stalled stage "
        "(0 disables)",
    )
    ap.add_argument(
        "--view-timeout", type=float, default=0.0,
        help="failover timer override; the storm default (3 s) assumes "
        "view-change validation is fast — on a single-core host a 64-node "
        "certificate takes seconds to check, so raise this accordingly",
    )
    ap.add_argument(
        "--no-spec", action="store_true",
        help="disable speculative execution (ISSUE 15) — the A/B arm "
        "for attributing the spec-latency win; the record then carries "
        "no p50_spec_latency_ms field",
    )
    ap.add_argument(
        "--device-profile", type=float, default=0.0,
        help="arm ONE bounded jax.profiler capture of this many seconds "
        "per cell (needs --flight-dir; artifacts under "
        "<flight-dir>/device_profile). The always-on device ledger "
        "(rec['device']) does not need this — kernel forensics only",
    )
    args = ap.parse_args()
    # the platform follows --verifier (module docstring)
    simple_pbft_tpu.select_platform(
        args.verifier == "tpu", "bench_consensus.py --verifier tpu"
    )
    # watchdog scales with the requested ladder: measurement time plus
    # generous per-config setup/teardown slack (large committees take tens
    # of seconds to wind up on a small host); env var still overrides
    n_configs = max(1, len([k for k in args.configs.split(",") if k.strip()]))
    default_budget = n_configs * (args.seconds + 120.0) + 60.0
    _start_watchdog(
        float(os.environ.get("BENCH_CONSENSUS_TIMEOUT", str(default_budget)))
    )

    ladder = {
        "1": dict(name="pbft-n4", n=4),
        "2": dict(name="pbft-n16", n=16),
        "3": dict(name="pbft-n64", n=64),
        "4": dict(name="bls-qc-n256", n=256, qc_mode=True),
        "100": dict(name="pbft-n100", n=100),
        # qc_mode at mid sizes: the storm comparison points — a NEW-VIEW
        # carries 2f+1 O(1) QCs instead of 2f+1 full vote certificates
        "qc16": dict(name="bls-qc-n16", n=16, qc_mode=True),
        "qc64": dict(name="bls-qc-n64", n=64, qc_mode=True),
        # the 10k req/s extrapolation's shape (cpu_budget_r04.md): O(n)
        # vote traffic at the reference-class committee size
        "qc100": dict(name="bls-qc-n100", n=100, qc_mode=True),
    }
    chaos = None
    if args.chaos:
        try:
            raw = dict(kv.split("=", 1) for kv in args.chaos.split(","))
            if not raw or any(
                k not in ("drop", "delay", "dup", "seed") for k in raw
            ):
                raise ValueError(args.chaos)
            # resolve to effective numeric values (defaults included) so
            # the emitted record reproduces the exact fault plan
            chaos = {
                "drop": float(raw.get("drop", 0.0)),
                "delay": float(raw.get("delay", 0.0)),
                "dup": float(raw.get("dup", 0.0)),
                "seed": int(raw.get("seed", 42)),
            }
        except ValueError:
            sys.exit(f"bad --chaos spec {args.chaos!r}: "
                     f"use drop=0.02,delay=0.03,dup=0.01,seed=42")

    replay_schedule = None
    if args.replay:
        from simple_pbft_tpu.faults import FaultSchedule

        with open(args.replay) as f:
            text = f.read()
        try:
            # a single JSON document (bench record, sim repro artifact —
            # artifacts are pretty-printed, so they span many lines)
            doc = json.loads(text)
        except json.JSONDecodeError:
            # a .jsonl ledger: the last record wins
            lines = [ln for ln in text.splitlines() if ln.strip()]
            doc = json.loads(lines[-1])
        faults = doc.get("faults") or (
            (doc.get("scenario") or {}).get("schedule")
        )
        if not faults:
            sys.exit(f"{args.replay!r} carries no faults block "
                     "(nothing to replay)")
        replay_schedule = FaultSchedule.from_summary(faults)
        args.seconds = replay_schedule.horizon
        print(f"[replay] {args.replay}: seed={replay_schedule.seed} "
              f"horizon={replay_schedule.horizon}s "
              f"events={len(replay_schedule.events)}"
              + (f" (recorded n={doc['n']})" if doc.get("n") else ""))

    for key in args.configs.split(","):
        key = key.strip()
        if key not in ladder:
            sys.exit(
                f"unknown config {key!r}: valid are "
                f"{sorted(ladder)} (config 5, the view-change storm, "
                f"runs via --storm over one of these committee sizes)"
            )
        cfg = ladder[key]
        resilience = dict(
            fault_spec=replay_schedule or args.fault_schedule,
            verify_deadline=args.verify_deadline,
            verify_max_pending=args.verify_max_pending,
            status_port_base=args.status_port_base,
            flight_dir=args.flight_dir,
            trace_sample=args.trace_sample,
            stall_deadline=args.stall_deadline,
            device_profile=args.device_profile,
            speculative=not args.no_spec,
        )
        if args.storm:
            rec = await run_config(
                f"viewchange-storm-{cfg['name']}", cfg["n"], args.seconds,
                args.clients, args.outstanding, args.verifier, args.batch,
                storm=True, view_timeout=args.view_timeout,
                qc_mode=cfg.get("qc_mode", False), chaos=chaos,
                max_crashes=args.crashes, **resilience,
            )
        else:
            rec = await run_config(
                cfg["name"], cfg["n"], args.seconds, args.clients,
                args.outstanding, args.verifier, args.batch,
                view_timeout=args.view_timeout,
                qc_mode=cfg.get("qc_mode", False), chaos=chaos,
                **resilience,
            )
        _emit(rec)


if __name__ == "__main__":
    asyncio.run(main())
