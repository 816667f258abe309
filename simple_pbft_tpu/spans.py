"""Critical-path span layer (ISSUE 4 tentpole).

Round 5's verdict: steady-state consensus drives the device at 6-31k
verifies/s against the same chip's 715k microbench, and nothing in the
telemetry plane could say where the other ~96% goes — the counters count
but cannot ATTRIBUTE. This module is the attribution layer: monotonic-
clock, allocation-light span records for every stage of the verify
critical path and the consensus pipeline, so a commit's latency
decomposes into named waits instead of one opaque number.

A span is (stage, start, duration) plus whatever ids the stage has
(node, view/seq slot, request id, item count). Recording is one tuple
append into a bounded ring plus one O(1) histogram update under a lock —
no dict is built unless the span is exported or persisted. Stages:

  verify.queue       VerifyService admission-queue wait (submit -> take)
  verify.host_prep   TpuVerifier host-side batch prep before dispatch
  verify.device      device dispatch -> result RTT (one coalesced pass),
                     tiled exactly by the four legs below (adjacent
                     endpoints on time.perf_counter, one of each a pass):
    verify.dispatch      t0 -> dispatch_batch returned (dispatcher thread:
                         prep, the device lock, the enqueue)
    verify.finish_queue  dispatch_batch returned -> the completion thread
                         takes the pass off its queue (behind the pass
                         before it at depth 2)
    verify.result_wait   taken -> finisher() returned, on the thread that
                         ran it (the watchdog's verify-finish sidecar)
    verify.finish        finisher() returned -> the completion thread's
                         round-trip stamp (the sidecar's wake-up)
  verify.return      a submission's future resolved -> the replica's
                     coroutine resumed after ``await`` (the way back
                     through the event loop; histogram only)
  verify.ladder      verify.device's round trip, of a pass's table-free
                     program alone (the rows whose key has no table);
                     n = those rows
  verify.cpu         CPU small-batch pass
  verify.cpu_reroute CPU reroute chunk (quarantine / depth-full big pile)
  verify.bank_build  the warm's key-table build and its one upload
  qc.queue           QcVerifyLane wait (cert submit -> batch start)
  qc.pairing         one RLC multi-pairing batch
  qc.sign_share      one BLS share signed (a vote's or a checkpoint's):
                     on the signing lane's threads, a chunk's wall time
                     over its shares, one sample a share; inside
                     loop.sign_vote only where the share is signed
                     inline (under simulation); histogram only
  qc.aggregate       one certificate's shares aggregated (build_qc: the
                     primary's first build and its rebuild after a bisect)
  replica.verify_wait  a sweep's verify from the replica's seat (queue +
                       device + resolution, the full service round trip)
  phase.prepare      pre-prepare admission -> slot prepared
  phase.commit       prepared -> commit certificate formed
  phase.execute      commit certificate -> applied in order
  execute.spec       admission -> speculative reply sent (ISSUE 15)
  execute.final      admission -> applied in order (the same slot's
                     full commit latency, comparable against spec)
  transport.queue    local-transport residency (enqueue -> recv), fault
                     delay included — the wire's contribution
  client.e2e         client submit -> f+1 accepted

The three phase.* spans of a slot tile its end-to-end commit latency
exactly (same clock, adjacent endpoints), which is what lets
``tools/critical_path.py`` check its decomposition against the measured
``commit_ms`` histogram — the acceptance reconciliation.

Loop-held stages (ISSUE 26) say what the event loop's ONE thread does
while the chip waits. They are accumulators, not spans: per stage a
count, a sum of SELF time (duration less what nested stages cover) and a
maximum — plain adds on the loop's thread, no lock, no ring slot, no
JSONL line, never counted in ``recorded``/``persisted``. A section
(``with spans.held(stage)``, or ``begin``/``end`` where a sweep pays it)
never spans an ``await`` that can suspend: where code awaits, the section
starts after it. The awaits inside sections are ``transport.send`` /
``broadcast``, which no transport in the tree suspends in (they deliver
or enqueue; transport/base.py states it, tests/test_loop_stages.py holds
all three to it), and four in QC mode that do suspend and so sit in
``with spans.parked()``, which sets the open sections aside meanwhile
(a certificate's verdict, a bisect, a view change's certificate check,
and a share on the signing lane).

  loop.ingest        drain + decode + shed + signature items of a sweep
  loop.sigcache      sha256 cache keys and the LRU, when on the loop
  loop.verify_submit VerifyService.submit as called from the loop
                     (charged out of the sigcache section around it,
                     so a trace shows it under loop.sigcache)
  loop.route         a verified sweep's routing (parent of the five below)
  loop.sign_vote     signing a vote, a pre-prepare or a checkpoint
  loop.send          encoding + transport.broadcast/send (n = destinations)
  loop.execute       a block's ordered or speculative execution
  loop.sign_reply    MAC or Ed25519 over replies, once per block or list
  loop.client        the client's request signing and reply handling

and from the heartbeat (telemetry.LoopLagGauge, one task per process):

  loop.lag           how late a 50 ms tick woke
  loop.offcpu        wall time less the loop thread's CPU time, per tick
  loop.unattributed  the thread's CPU time less the nine stages' self
                     time, per tick (floored at 0: sections are timed on
                     the wall clock and may hold off-CPU time)
  gc.pause           one collection (gc.callbacks), generation as n
  gc.full            the same pause again where the collection was a full
                     one (generation 2): its count and its maximum are the
                     full collections', which heap.py's policy keeps few

While a profiler capture is open (``annotating``), every section and the
verify threads' stages (``annotation(stage)``; verify.collect is the
dispatcher's wait for a pile) also enter ``jax.profiler.TraceAnnotation``
so they land on the device trace's clock. Of verify.device's legs the two
a thread is busy in are annotated (verify.dispatch, verify.result_wait);
verify.device itself, and the queue residencies verify.finish_queue and
verify.return, are spans only.

One recorder per process (like consensus/qc.py's verify lane): the
coalescing service and the QC lane are process-wide anyway, and
per-node spans carry their node id in the record. ``configure()``
attaches the JSONL sink (``<log-dir>/<id>.spans.jsonl`` in node.py;
``<flight-dir>/<config>.spans.jsonl`` in bench_consensus). High-volume
stages (per-message transport residency) record with ``persist=False``:
histogram only — never a file line per message, and never a slot in the
recent ring the autopsy exports.
"""

from __future__ import annotations

import contextlib
import gc
import logging
import sys
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

from . import clock, sanitize
from .logutil import Histogram

log = logging.getLogger("pbft.spans")

# canonical stage names (keep tools/critical_path.py's grouping in sync)
VERIFY_QUEUE = "verify.queue"
VERIFY_HOST_PREP = "verify.host_prep"
VERIFY_DEVICE = "verify.device"
# the four legs that tile verify.device, in order (record only where
# verify.device is recorded, so all five count alike)
VERIFY_DISPATCH = "verify.dispatch"
"""t0 -> ``dispatch_batch`` returned, on the dispatcher thread."""
VERIFY_FINISH_QUEUE = "verify.finish_queue"
"""``dispatch_batch`` returned -> ``_complete_loop`` takes the pass."""
VERIFY_RESULT_WAIT = "verify.result_wait"
"""``_complete_loop`` took the pass -> ``finisher()`` returned."""
VERIFY_FINISH = "verify.finish"
"""``finisher()`` returned -> the completion thread's round-trip stamp."""
VERIFY_RETURN = "verify.return"
"""``_resolve`` set the future -> ``Replica._submit_verify`` resumed."""
VERIFY_LADDER = "verify.ladder"
VERIFY_CPU = "verify.cpu"
VERIFY_REROUTE = "verify.cpu_reroute"
VERIFY_BANK_BUILD = "verify.bank_build"
QC_QUEUE = "qc.queue"
QC_PAIRING = "qc.pairing"
QC_SIGN_SHARE = "qc.sign_share"
QC_AGGREGATE = "qc.aggregate"
REPLICA_VERIFY_WAIT = "replica.verify_wait"
PHASE_PREPARE = "phase.prepare"
PHASE_COMMIT = "phase.commit"
PHASE_EXECUTE = "phase.execute"
# the phase.execute split (ISSUE 15): both measured from pre-prepare
# ADMISSION so their percentiles are directly comparable — the gap
# between p50(execute.spec) and p50(execute.final) IS the speculative
# win. phase.execute keeps its commit-cert→applied meaning (the tiling/
# reconciliation contract below depends on it); these two are the
# attribution overlay, not a rename.
EXECUTE_SPEC = "execute.spec"      # admission -> speculative reply sent
EXECUTE_FINAL = "execute.final"    # admission -> applied in order
TRANSPORT_QUEUE = "transport.queue"
CLIENT_E2E = "client.e2e"

# annotation only (no span, no accumulator): the verify dispatcher's wait
# for a pile it may dispatch
VERIFY_COLLECT = "verify.collect"
# loop-held stages: accumulators of self time on the event loop's thread
LOOP_INGEST = "loop.ingest"
LOOP_SIGCACHE = "loop.sigcache"
LOOP_VERIFY_SUBMIT = "loop.verify_submit"
LOOP_ROUTE = "loop.route"
LOOP_SIGN_VOTE = "loop.sign_vote"
LOOP_SEND = "loop.send"
LOOP_EXECUTE = "loop.execute"
LOOP_SIGN_REPLY = "loop.sign_reply"
LOOP_CLIENT = "loop.client"
LOOP_STAGES = (
    LOOP_INGEST, LOOP_SIGCACHE, LOOP_VERIFY_SUBMIT, LOOP_ROUTE,
    LOOP_SIGN_VOTE, LOOP_SEND, LOOP_EXECUTE, LOOP_SIGN_REPLY, LOOP_CLIENT,
)
# loop health, one sample per heartbeat tick; gc.pause one per collection
# of any generation, gc.full one per full collection (a part of gc.pause)
LOOP_LAG = "loop.lag"
LOOP_OFFCPU = "loop.offcpu"
LOOP_UNATTRIBUTED = "loop.unattributed"
GC_PAUSE = "gc.pause"
GC_FULL = "gc.full"

# the slot-level stages that tile a commit's end-to-end latency, in
# pipeline order (critical_path.py reconciles their sum against commit_ms)
PHASE_STAGES = (PHASE_PREPARE, PHASE_COMMIT, PHASE_EXECUTE)


class Accum:
    """Count, sum and maximum of one loop-held stage, in seconds; ``n``
    sums what the stage handled (messages, items, replies). Written by
    one thread with plain adds, so there is no lock; a reader on another
    thread sees each field whole (GIL) and the four at most one update
    apart."""

    __slots__ = ("count", "total", "max", "n")

    def __init__(self) -> None:
        self.count = 0
        self.total = 0.0
        self.max = 0.0
        self.n = 0

    def add(self, dur: float, n: int = 1) -> None:
        self.count += 1
        self.total += dur
        self.n += n
        if dur > self.max:
            self.max = dur

    def summary(self) -> Dict[str, float]:
        """The histogram stages' shape, ms, less what an accumulator does
        not know (min, quantiles). ``mean`` is unrounded: a reader that
        wants the sum multiplies it by ``count``."""
        return {
            "count": self.count,
            "mean": self.total * 1e3 / self.count if self.count else 0.0,
            "max": self.max * 1e3,
            "sum": self.total * 1e3,
            "n": self.n,
        }


class SpanRecorder:
    """Bounded-memory span sink: per-stage histograms + a recent ring +
    an optional line-flushed JSONL file.

    Thread-safe (`record` is called from the event loop, the verify
    dispatcher/completion threads, the QC lane worker, and reroute
    threads). The main lock covers one deque append and one histogram
    update — nanoseconds — so an event-loop recorder (per-message
    transport spans) can never block behind disk. Sink writes happen
    OUTSIDE it under their own lock, and on failure the sink degrades
    to the in-memory surfaces exactly like the flight recorder
    (telemetry must never take down the node it observes)."""

    def __init__(self, ring: int = 4096) -> None:
        self._lock = sanitize.wrap_lock(threading.Lock(), "spans.recorder")
        # serializes file I/O only; same sanitizer group as _lock: the
        # two must never be held together (sink I/O off the ring lock)
        self._sink_lock = sanitize.wrap_lock(threading.Lock(), "spans.sink")
        self._ring: deque = deque(maxlen=ring)
        self._hists: Dict[str, Histogram] = {}
        # loop-held stages and loop health (module docstring): written
        # without the lock by the thread that owns each stage
        self._held: Dict[str, Accum] = {}
        self._sink = None
        self.node_id = ""
        self.recorded = 0
        self.persisted = 0

    def accum(self, stage: str) -> Accum:
        acc = self._held.get(stage)
        if acc is None:
            # setdefault: the gc callback may run on any thread
            acc = self._held.setdefault(stage, Accum())
        return acc

    def held_seconds(self) -> float:
        """Self time the nine loop-held stages have charged so far."""
        held = self._held
        return sum(held[s].total for s in LOOP_STAGES if s in held)

    def configure(self, node_id: str, path: Optional[str] = None) -> None:
        """Name the process (multi-process deployments: the node id),
        attach the JSONL sink, and START A FRESH SURFACE — histograms,
        ring, and counters reset, so a process running several
        measurement cells (bench_consensus config ladder) never bleeds
        one cell's spans into the next cell's record."""
        from .telemetry import _JsonlSink  # no cycle: telemetry never

        # imports spans at module level
        with self._sink_lock:
            old = self._sink
            if old is not None:
                old.close()
            new_sink = _JsonlSink(path) if path else None
        with self._lock:
            self.node_id = node_id
            self._sink = new_sink
            self._ring.clear()
            self._hists = {}
            self._held = {}
            self.recorded = 0
            self.persisted = 0

    def record(
        self,
        stage: str,
        dur: float,
        *,
        node: Optional[str] = None,
        view: Optional[int] = None,
        seq: Optional[int] = None,
        rid: Optional[str] = None,
        n: Optional[int] = None,
        persist: bool = True,
    ) -> None:
        """One span: ``dur`` seconds of ``stage``, ending now. The record
        is stamped with its END time on the clock seam (monotonic on real
        runs, virtual under the sim loop — so sim span ledgers are
        byte-deterministic and joinable with trace-plane edge docs) —
        start is end - dur, same clock. ``persist=False`` marks
        per-message-volume stages: histogram only — no file line, and no
        slot in the recent ring (an autopsy's last-N window must hold the
        pipeline spans that diagnose a wedge, not thousands of transport
        residencies)."""
        if persist:  # only the ring and the file read the end stamp
            rec = (stage, clock.now(), dur, node, view, seq, rid, n)
        with self._lock:
            h = self._hists.get(stage)
            if h is None:
                h = self._hists[stage] = Histogram()
            h.record(dur * 1e3)
            self.recorded += 1
            sink = None
            if persist:
                self._ring.append(rec)
                sink = self._sink
        if sink is not None:
            doc = self._to_doc(rec)
            with self._sink_lock:
                sink.write(doc)
                if sink._fh is not None:
                    # counted only when the line actually landed: a sink
                    # degraded by ENOSPC must not keep inflating the
                    # on-disk count post-mortem tooling trusts
                    self.persisted += 1

    def record_many(self, stage: str, durs: List[float]) -> None:
        """``record(stage, d, persist=False)`` for each of ``durs`` (s)
        under one acquisition of the lock: the same histogram samples,
        no ring slot and no file line (a transport drain's residencies)."""
        ms = [d * 1e3 for d in durs]
        with self._lock:
            h = self._hists.get(stage)
            if h is None:
                h = self._hists[stage] = Histogram()
            h.record_many(ms)
            self.recorded += len(ms)

    def emit(self, doc: Dict[str, Any]) -> None:
        """Write one non-span ledger doc straight to the JSONL sink.

        The trace plane's cross-node edge events and per-certificate
        quorum docs (trace.py) share the span ledger file — one
        ``<id>.spans.jsonl`` per node is the unit slot_trace joins —
        but they are not spans: no histogram, no ring slot, and no-op
        when no sink is attached. Never raises (a ledger write must not
        be able to take down the transport or consensus path calling
        it)."""
        try:
            with self._lock:
                sink = self._sink
            if sink is None:
                return
            with self._sink_lock:
                sink.write(doc)
                if sink._fh is not None:
                    self.persisted += 1
        except Exception:
            pass

    def _to_doc(self, rec) -> Dict[str, Any]:
        stage, end, dur, node, view, seq, rid, n = rec
        doc: Dict[str, Any] = {
            "evt": "span",
            "stage": stage,
            "node": node if node is not None else self.node_id,
            "t_mono": round(end, 6),
            "dur_ms": round(dur * 1e3, 4),
        }
        if view is not None:
            doc["view"] = view
        if seq is not None:
            doc["seq"] = seq
        if rid is not None:
            doc["rid"] = rid
        if n is not None:
            doc["n"] = n
        return doc

    def recent(self, limit: int = 256) -> List[Dict[str, Any]]:
        """The last ``limit`` spans as dicts (autopsy dumps, tests)."""
        with self._lock:
            tail = list(self._ring)[-limit:]
        return [self._to_doc(rec) for rec in tail]

    def stage_summaries(self) -> Dict[str, Dict[str, float]]:
        """Per-stage summaries, ms (telemetry snapshots): the histogram
        stages and, in the same shape, the accumulators."""
        with self._lock:
            out = {s: h.summary() for s, h in self._hists.items()}
        out.update((s, a.summary()) for s, a in list(self._held.items()))
        return dict(sorted(out.items()))

    def snapshot(self) -> Dict[str, Any]:
        sink = self._sink
        return {
            "recorded": self.recorded,
            "persisted": self.persisted,
            # nonzero = the JSONL surface is truncated (sink degraded to
            # in-memory on a write failure); critical_path consumers
            # should distrust file completeness past that point
            "sink_write_errors": sink.write_errors if sink is not None else 0,
            "stages": self.stage_summaries(),
        }

    def close(self) -> None:
        with self._lock:
            if self._sink is not None:
                self._sink.close()
                self._sink = None


# the process-wide recorder (every in-process node shares the verify
# service and QC lane, so they share the span surface too; per-node
# stages carry node= in each record)
_recorder = SpanRecorder()


def recorder() -> SpanRecorder:
    return _recorder


def configure(node_id: str, path: Optional[str] = None) -> None:
    _recorder.configure(node_id, path)


def record(stage: str, dur: float, **kw) -> None:
    _recorder.record(stage, dur, **kw)


def record_many(stage: str, durs: List[float]) -> None:
    _recorder.record_many(stage, durs)


def emit(doc: Dict[str, Any]) -> None:
    _recorder.emit(doc)


def recent(limit: int = 256) -> List[Dict[str, Any]]:
    return _recorder.recent(limit)


def snapshot() -> Dict[str, Any]:
    return _recorder.snapshot()


# ---------------------------------------------------------------------------
# loop-held stages: self-time accounting on the event loop's thread
# ---------------------------------------------------------------------------

# open sections on the loop's thread, innermost last, three slots each:
# the stage (while annotating, its (stage, annotation) pair), the time the
# sections nested in it covered, its start. One loop per process is the
# deployment (node.py, LocalCommittee), so one stack serves it.
_open: List[Any] = []
# True while, and only while, a profiler capture is open: the one global a
# section reads when it is off
_annotating = False
_TraceAnnotation: Any = None
_NO_ANNOTATION = contextlib.nullcontext()
_torn_logged = False


def annotating() -> bool:
    return _annotating


def _set_annotating(on: bool) -> None:
    global _annotating, _TraceAnnotation
    if on and _TraceAnnotation is None:
        from jax.profiler import TraceAnnotation

        _TraceAnnotation = TraceAnnotation
    _annotating = bool(on)


def sync_annotating() -> None:
    """Follow the profiler: sections and ``annotation()`` enter
    jax.profiler.TraceAnnotation exactly while a capture is open, whoever
    opened it (devledger.arm_profile, benchmark/run.py, an operator's
    jax.profiler call). The heartbeat calls this once a tick, the flag's
    one driver. A process that never imported jax has no capture and pays
    one dict lookup."""
    prof = sys.modules.get("jax.profiler")
    if prof is not None:
        on = bool(prof.TraceAnnotation.is_enabled())
        if on != _annotating:
            _set_annotating(on)


def annotation(stage: str):
    """Context manager for a stage on a thread other than the loop's
    (verify dispatcher, completion, reroute): the profiler annotation
    while a capture is open, else nothing. Its time is recorded by the
    caller's own ``record()``."""
    return _TraceAnnotation(stage) if _annotating else _NO_ANNOTATION


def begin(stage: str) -> None:
    """Open a section in which the event loop's thread is held; ``end``
    closes it (in a ``finally``). The pair is ``held`` without the object
    and the ``with``: half the cost, for the sites every sweep pays."""
    if _annotating:
        ann = _TraceAnnotation(stage)
        ann.__enter__()
        _open.append((stage, ann))
    else:
        _open.append(stage)
    _open.append(0.0)
    # the seam, read without clock.now()'s frame: wall time on real runs,
    # virtual (so every section reads 0) under the sim
    _open.append(clock._active.now())


def end(stage: str, n: int = 1) -> None:
    """Close the innermost section and charge ``stage`` its SELF time:
    its duration less what the sections nested in it covered."""
    if not _open:
        return  # torn below, and cleared
    dur = clock._active.now() - _open.pop()
    own = dur - _open.pop()
    top = _open.pop()
    if top is not stage and not _closes(top, stage):
        return
    if _open:
        _open[-2] += dur
    acc = _recorder._held.get(stage)
    if acc is None:
        acc = _recorder.accum(stage)
    # Accum.add, inlined: this runs some fifty times a request
    acc.count += 1
    acc.total += own
    acc.n += n
    if own > acc.max:
        acc.max = own


def _closes(top: Any, stage: str) -> bool:
    """``end``'s slow path: an annotated section, or a torn stack."""
    global _torn_logged
    if type(top) is tuple and top[0] == stage:
        top[1].__exit__(None, None, None)
        return True
    if top == stage:
        return True
    # a section was suspended and another task closed one across it: the
    # contract (module docstring) is broken and self times are void.
    # Drop what is open rather than charge nonsense, and say so once.
    del _open[:]
    if not _torn_logged:
        _torn_logged = True
        log.error(
            "spans: section %s closed over %r: a section spanned an await "
            "that suspended; loop.* stages are wrong in this process",
            stage, top,
        )
    return False


class held:
    """``begin``/``end`` as a context manager:
    ``with spans.held(spans.LOOP_ROUTE) as sec: ...; sec.n = accepted``.
    Sections nest, and each charges its stage its SELF time on the clock
    seam (0 under the sim's virtual clock). Loop thread only, and never
    across an ``await`` that can suspend."""

    __slots__ = ("stage", "n")

    def __init__(self, stage: str, n: int = 1) -> None:
        self.stage = stage
        self.n = n

    def __enter__(self) -> "held":
        begin(self.stage)
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        end(self.stage, self.n)


class parked:
    """Around an ``await`` that CAN suspend under open sections (the QC
    lane's verdict, an off-thread bisect: ``with spans.parked(): await
    ...``). The open sections are set aside, so what other tasks run
    meanwhile does not nest in them, and the time away comes out of their
    self time; their annotations close and reopen with them."""

    __slots__ = ("_saved", "_t0")

    def __enter__(self) -> None:
        saved = self._saved = _open[:]
        del _open[:]
        for slot in saved[::-3]:  # innermost first
            if type(slot) is tuple:
                slot[1].__exit__(None, None, None)
        self._t0 = clock._active.now()

    def __exit__(self, exc_type, exc, tb) -> None:
        saved = self._saved
        if not saved:
            return
        saved[-2] += clock._active.now() - self._t0
        for i in range(0, len(saved), 3):
            if type(saved[i]) is tuple:
                stage = saved[i][0]
                saved[i] = stage
                if _annotating:
                    ann = _TraceAnnotation(stage)
                    ann.__enter__()
                    saved[i] = (stage, ann)
        # whatever ran meanwhile closed or parked its own: none is open
        _open[:] = saved


def charge(stage: str, dur: float, n: int = 1) -> None:
    """Charge ``stage`` time the caller measured itself (reply signing
    summed over a block: one update per block, not one per reply; the
    submit inside a sweep's sigcache section). An enclosing section loses
    it from its self time. It enters no annotation: in a trace the time
    reads under the enclosing section's stage."""
    if _open:
        _open[-2] += dur
    _recorder.accum(stage).add(dur, n)


def stage_summaries() -> Dict[str, Dict[str, float]]:
    return _recorder.stage_summaries()


# ---------------------------------------------------------------------------
# loop health: what a heartbeat tick charges, and gc pauses
# ---------------------------------------------------------------------------


class LoopBeat:
    """The per-tick arithmetic of the heartbeat (telemetry.LoopLagGauge
    owns the task). Over each tick: ``loop.offcpu`` is wall time less the
    loop thread's CPU time (select wait, GIL wait, preemption);
    ``loop.unattributed`` is that CPU time less the self time the nine
    stages charged (hooks of the audit, trace and telemetry planes,
    timers, asyncio itself). Both floor at 0: a section is timed on the
    wall clock, so one that waited for the GIL is charged more than the
    CPU it had. Under the sim's virtual clock only ``loop.lag`` is kept
    (and reads 0)."""

    def __init__(self) -> None:
        self._wall = clock.now()
        self._cpu = time.thread_time()
        self._held = _recorder.held_seconds()

    def tick(self, lag: float) -> None:
        sync_annotating()
        _recorder.accum(LOOP_LAG).add(lag)
        if clock.simulated():
            return
        wall, cpu = clock.now(), time.thread_time()
        held_now = _recorder.held_seconds()
        d_wall, d_cpu = wall - self._wall, cpu - self._cpu
        d_held = held_now - self._held
        if d_held < 0.0:  # configure() emptied the accumulators
            d_held = held_now
        self._wall, self._cpu, self._held = wall, cpu, held_now
        _recorder.accum(LOOP_OFFCPU).add(max(0.0, d_wall - d_cpu))
        _recorder.accum(LOOP_UNATTRIBUTED).add(max(0.0, d_cpu - d_held))


_gc_open: List[Any] = []  # [t0, annotation | None] of the running collection


def _on_gc(phase: str, info: Dict[str, Any]) -> None:
    """gc.callbacks hook: one ``gc.pause`` per collection, with the
    generation as ``n``, on whichever thread tripped it (every Python
    thread waits meanwhile); a full collection's pause goes to ``gc.full``
    as well. Never part of the loop's sum: a collection is inside
    whichever section it interrupted."""
    if phase == "start":
        ann = None
        if _annotating:
            ann = _TraceAnnotation(GC_PAUSE)
            ann.__enter__()
        _gc_open[:] = [clock.now(), ann]
    elif _gc_open:
        t0, ann = _gc_open
        del _gc_open[:]
        dur = clock.now() - t0
        generation = info.get("generation", 0)
        _recorder.accum(GC_PAUSE).add(dur, generation)
        if generation == 2:
            _recorder.accum(GC_FULL).add(dur)
        if ann is not None:
            ann.__exit__(None, None, None)


def watch_gc(on: bool) -> None:
    """Install (or remove) the gc hook; the heartbeat does both."""
    if on and _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)
    elif not on and _on_gc in gc.callbacks:
        gc.callbacks.remove(_on_gc)
        del _gc_open[:]
