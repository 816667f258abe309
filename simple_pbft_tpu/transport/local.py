"""In-process simulated network with fault injection.

The whole committee (replicas + clients) lives in one process, one asyncio
queue per node. This is the test/bench substrate SURVEY.md §4 calls for:
the reference could only be "tested" by launching 4 OS processes and
eyeballing logs; here an N-replica committee is a plain object, and the
network can drop, delay, duplicate, or partition traffic deterministically.
"""

from __future__ import annotations

import asyncio
import random
import time
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Set, Tuple

from .. import spans, trace
from .base import WireAccounting, account_recvs, base_metrics


@dataclass
class FaultPlan:
    """Deterministic fault injection knobs (seeded RNG)."""

    drop_rate: float = 0.0  # iid drop probability per message
    delay_range: Tuple[float, float] = (0.0, 0.0)  # uniform delay seconds
    duplicate_rate: float = 0.0
    partitions: Set[Tuple[str, str]] = field(default_factory=set)
    # directed (src, dst) pairs that are cut; use both directions for a
    # symmetric partition
    seed: int = 0

    def __post_init__(self) -> None:
        self.rng = random.Random(self.seed)

    def cut(self, src: str, dst: str) -> None:
        self.partitions.add((src, dst))
        self.partitions.add((dst, src))

    def heal(self) -> None:
        self.partitions.clear()


class LocalNetwork:
    """Registry of in-process endpoints + the fault plan."""

    def __init__(self, fault_plan: Optional[FaultPlan] = None) -> None:
        self.queues: Dict[str, asyncio.Queue] = {}
        self.faults = fault_plan or FaultPlan()
        self.delivered = 0
        self.dropped = 0
        # one WireAccounting per node id, shared by every endpoint handle
        # for that node (accounting must survive re-handles) and readable
        # by _deliver_many for the receiver-side count at enqueue time
        self.wire_accts: Dict[str, WireAccounting] = {}
        # optional delivery tap (ISSUE 13: the simulation runtime's
        # deterministic event trace): called (src, dst, kind, nbytes,
        # verdict) at every delivery decision. Never allowed to break
        # delivery — exceptions are swallowed at the call sites.
        self.trace = None

    def wire_for(self, node_id: str) -> WireAccounting:
        w = self.wire_accts.get(node_id)
        if w is None:
            w = self.wire_accts[node_id] = WireAccounting(node_id)
        return w

    def endpoint(self, node_id: str) -> "LocalEndpoint":
        if node_id not in self.queues:
            self.queues[node_id] = asyncio.Queue()
        return LocalEndpoint(node_id, self)

    def _trace(self, src: str, dst: str, kind: str, nbytes: int,
               verdict: str) -> None:
        tr = self.trace
        if tr is None:
            return
        try:
            tr(src, dst, kind, nbytes, verdict)
        except Exception:
            # a tracing bug must never break delivery (same contract as
            # the wire-accounting entry points)
            self.trace = None

    def _deliver_many(self, src: str, dests: Iterable[str], raw: bytes) -> None:
        """Deliver one frame from ``src`` to each of ``dests``, in order.

        What is the same for every destination is paid once: the kind
        (classified ONCE per logical send: sender and receiver ledgers
        must agree on it for per-kind conservation to hold), the send
        stamp, and the ``(t_sent, raw)`` item every queue shares. What is
        not is made per destination exactly as a send to one destination
        makes it, in destination order: the partition test, the drop
        draw, the duplicate draw, the delay draw of each copy, and the
        tap — so a seeded plan replays the same deliveries and leaves
        ``rng`` in the same state however the frames were grouped."""
        src_wire = self.wire_for(src)
        kind = src_wire.kind_of(raw)
        size = len(raw)
        queues = self.queues
        f = self.faults
        parts = f.partitions
        rand = f.rng.random
        drop_rate = f.drop_rate
        dup_rate = f.duplicate_rate
        lo, hi = f.delay_range
        tap = self.trace
        # messages are stamped at SEND: the receiver's recv span is the
        # full transport residency (injected fault delay + queue wait +
        # receiver scheduling) — the wire's leg of the critical path
        item = (time.perf_counter(), raw)
        accepted = []  # one entry per delivered copy
        for dst in dests:
            q = queues.get(dst)
            if q is None:
                # unknown destination: silently dropped (fire-and-forget)
                src_wire.account_lost("no_route", raw, kind)
                if tap is not None:
                    self._trace(src, dst, kind, size, "no_route")
                continue
            if (parts and (src, dst) in parts) or rand() < drop_rate:
                self.dropped += 1
                # FaultPlan drops are network-side: the sender's ledger
                # owns them (the receiver never saw the frame) —
                # conservation: attempted = sent + lost, and sent == received
                src_wire.account_lost("net_dropped", raw, kind)
                if tap is not None:
                    self._trace(src, dst, kind, size, "dropped")
                continue
            copies = 2 if rand() < dup_rate else 1
            if tap is not None:
                self._trace(src, dst, kind, size, "deliver")
            if hi <= 0:  # no delay draws: every copy is queued now
                q.put_nowait(item)
                accepted.append(dst)
                if copies == 2:
                    q.put_nowait(item)
                    accepted.append(dst)
                continue
            for _ in range(copies):
                delay = f.rng.uniform(lo, hi)
                if delay > 0:
                    asyncio.get_running_loop().call_later(
                        delay, q.put_nowait, item)
                else:
                    q.put_nowait(item)
                accepted.append(dst)
        if not accepted:
            return
        self.delivered += len(accepted)
        # accounted at the delivery decision (wire acceptance), not at
        # dequeue: frames resident in the recv queue at a test's end
        # must still reconcile; duplicates count per copy
        src_wire.account_sends(accepted, kind, size)
        wires = self.wire_accts
        account_recvs([wires.get(d) for d in accepted], kind, size)


class LocalEndpoint:
    """One node's transport handle on a LocalNetwork."""

    def __init__(self, node_id: str, net: LocalNetwork) -> None:
        self.node_id = node_id
        self.net = net
        self.queue = net.queues[node_id]
        # the FULL shared counter schema (transport.base.COUNTER_SCHEMA):
        # dropped_*/reconnects/frames_* stay zero on a LocalNetwork
        # (drops are network-wide here; see net.dropped) but the keys
        # exist, so the telemetry transport block and pbft_top read every
        # deployment flavor identically
        self.metrics: Dict[str, int] = base_metrics()
        # per-link per-kind msgs+bytes accounting, shared across every
        # endpoint handle for this node id (ISSUE 12)
        self.wire = net.wire_for(node_id)

    async def send(self, dest: str, raw: bytes) -> None:
        self.metrics["sent"] += 1
        self.net._deliver_many(self.node_id, (dest,), raw)

    async def broadcast(self, raw: bytes, dests: Iterable[str]) -> None:
        me = self.node_id
        targets = [d for d in dests if d != me]
        self.metrics["sent"] += len(targets)
        self.net._deliver_many(me, targets, raw)

    async def recv(self) -> bytes:
        return self._took([await self.queue.get()])[0]

    def recv_nowait(self) -> Optional[bytes]:
        frames = self.recv_many(1)
        return frames[0] if frames else None

    def recv_many(self, limit: Optional[int] = None) -> List[bytes]:
        """Up to ``limit`` queued frames (all when None), oldest first, in
        one call and under one round of bookkeeping (``_took``)."""
        q = self.queue
        n = q.qsize()
        if limit is not None and limit < n:
            n = limit
        if n <= 0:
            return []
        get = q.get_nowait
        return self._took([get() for _ in range(n)])

    def _took(self, items: List[Tuple[float, bytes]]) -> List[bytes]:
        """The receive bookkeeping of ``(t_sent, raw)`` items just taken
        off the queue, paid once for all of them: one clock read, the
        residencies into the ``transport.queue`` histogram under one
        acquisition of the recorder's lock (histogram only: one span per
        message must never become a JSONL line per message), and
        ``trace.recv_stamp`` for a frame that carries the trace envelope
        alone — AFTER queue residency, so the edge's recv time includes
        injected fault delay and queue wait."""
        self.metrics["recv"] += len(items)
        now = time.perf_counter()
        spans.record_many(spans.TRANSPORT_QUEUE, [now - t for t, _ in items])
        frames = [raw for _, raw in items]
        # bytes.find, not `in`: `in` first tries the needle as an int
        gate = trace._GATE
        for raw in frames:
            if raw.find(gate) >= 0:
                trace.recv_stamp(self.node_id, raw)
        return frames
