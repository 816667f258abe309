"""TCP transport: length-prefixed frames over persistent connections.

Parity target: the reference's HTTP transport (pbft/network/
consensusInterface.go:29-44 inbound, node.go:101-129 outbound) — one
HTTP POST per message, a fresh JSON body per peer, errors discarded.
Redesigned for a real deployment:

- One persistent TCP connection per peer direction (the reference paid
  connection setup per message via http.Post, node.go:101-104).
- 4-byte big-endian length prefix + raw message bytes; the message body
  is the same canonical JSON as every other transport (messages.py), so
  local/TCP/native transports interoperate.
- Fire-and-forget send semantics with bounded per-peer outbox queues and
  automatic reconnect — PBFT tolerates loss; it must not tolerate a slow
  peer backpressuring the replica loop (the reference's serial
  Broadcast loop blocked on each peer in turn, node.go:107-129).
- The same `Transport` interface as transport/local.py: the replica
  runtime cannot tell deployments apart.
"""

from __future__ import annotations

import asyncio
import logging
from typing import Dict, Iterable, Optional, Tuple

from .. import trace
from ..messages import DEFERRABLE_KINDS
from .base import Transport, WireAccounting, base_metrics

log = logging.getLogger("pbft.tcp")

# Must admit the largest certificate message (NewView's 256 MiB cap,
# messages.Message.MAX_CERT_WIRE_BYTES) — a loaded primary's failover
# certificate has to be deliverable. RECV_BUFFER_BYTES bounds the total
# bytes queued across ALL connections (the queue depth alone would let an
# unauthenticated peer stack huge frames until OOM); beyond it frames drop
# and PBFT retransmission recovers.
MAX_FRAME = 257 * 1024 * 1024
RECV_BUFFER_BYTES = MAX_FRAME + 64 * 1024 * 1024
OUTBOX_DEPTH = 4096  # per-peer queued frames before drops (slow peer)


def encode_frame(raw: bytes) -> bytes:
    return len(raw).to_bytes(4, "big") + raw


# DEFERRABLE message kinds (messages.DEFERRABLE — the single source
# shared with the replica's SHED_DEFERRABLE, so the two policies can't
# drift): their senders all have retry paths, so a frame lost mid-write
# just costs one retransmission. Everything else is treated as
# quorum-critical — a vote, certificate, or repair payload is emitted
# once, and losing it to a connection blip heals only through the much
# slower probe/view-change machinery — and gets ONE requeue before the
# transport gives up on it.
_DEFERRABLE_KINDS = DEFERRABLE_KINDS


def _deferrable(raw: bytes) -> bool:
    """TOP-LEVEL kind check. Not a substring scan: pre-prepares and
    NEW-VIEWs EMBED client requests, so their wire bytes contain
    '\"kind\":\"request\"' while being exactly the once-emitted frames
    the requeue guarantee exists for. Only consulted on exceptional
    paths (mid-write failure, reconnect drain), so the parse cost is
    off the hot path; unparseable frames count as critical (requeue is
    the safe polarity)."""
    try:
        import json

        return json.loads(raw)["kind"] in _DEFERRABLE_KINDS
    except Exception:
        return False


def _item_deferrable(item: list) -> bool:
    """Memoized per-item verdict: outbox items are [raw, retried, defer]
    with defer lazily filled on first consultation. A long outage runs
    the reconnect drain every backoff tick over the same queued frames —
    without the memo each tick would re-json.loads the entire outbox
    (pre-prepares carry whole request blocks) on the shared event loop."""
    if item[2] is None:
        item[2] = _deferrable(item[0])
    return item[2]


class TcpTransport(Transport):
    """One node's TCP endpoint: a listening server + per-peer senders.

    peers: node_id -> (host, port) for every node we may send to.
    Incoming frames from any connection land in one recv queue; PBFT
    authenticates by signature, not by connection, so the listener does
    not care who connects (a hostile frame is just an invalid message).
    """

    def __init__(
        self,
        node_id: str,
        listen_addr: Tuple[str, int],
        peers: Dict[str, Tuple[str, int]],
        recv_depth: int = 65536,
    ) -> None:
        self.node_id = node_id
        self.listen_addr = listen_addr
        self.peers = peers
        self._recv_q: asyncio.Queue = asyncio.Queue(maxsize=recv_depth)
        self._recv_bytes = 0  # bytes currently queued (bounded)
        self._outboxes: Dict[str, asyncio.Queue] = {}
        self._sender_tasks: Dict[str, asyncio.Task] = {}
        self._server: Optional[asyncio.AbstractServer] = None
        self._conn_writers: set = set()  # live inbound connections
        # shared schema (transport.base.COUNTER_SCHEMA): sent/recv,
        # dropped_outbox/dropped_recv, reconnects, plus frames that died
        # mid-write (connection failed with the frame already dequeued)
        # and were lost for good / requeued once because they were
        # quorum-critical (ISSUE 7 satellite: previously silent)
        self.metrics: Dict[str, int] = base_metrics()
        # per-link per-kind msgs+bytes accounting (ISSUE 12): sends are
        # accounted when the frame is actually WRITTEN to a socket;
        # overflow/mid-write losses land in named lost buckets
        self.wire = WireAccounting(node_id)

    # -- lifecycle ------------------------------------------------------

    async def start(self) -> None:
        host, port = self.listen_addr
        self._server = await asyncio.start_server(self._on_conn, host, port)

    async def stop(self) -> None:
        if self._server is not None:
            self._server.close()
            # close live inbound connections FIRST: handlers sit blocked in
            # readexactly and (Python >= 3.12) wait_closed() waits for them
            for w in list(self._conn_writers):
                w.close()
            await self._server.wait_closed()
        for task in self._sender_tasks.values():
            task.cancel()
        for task in self._sender_tasks.values():
            try:
                await task
            except (asyncio.CancelledError, Exception):
                pass
        self._sender_tasks.clear()

    @property
    def bound_port(self) -> int:
        """Actual listening port (when constructed with port 0)."""
        if self._server is None:
            raise RuntimeError("transport not started")
        return self._server.sockets[0].getsockname()[1]

    # -- inbound --------------------------------------------------------

    async def _on_conn(
        self, reader: asyncio.StreamReader, writer: asyncio.StreamWriter
    ) -> None:
        self._conn_writers.add(writer)
        try:
            while True:
                header = await reader.readexactly(4)
                size = int.from_bytes(header, "big")
                if size == 0 or size > MAX_FRAME:
                    break  # protocol violation: hard close
                if size + self._recv_bytes > RECV_BUFFER_BYTES:
                    # drain the bytes but drop the frame: keeps the stream
                    # framed while bounding resident memory
                    dropped = await reader.readexactly(size)
                    self.metrics["dropped_recv"] += 1
                    self.wire.account_lost("dropped_recv", dropped)
                    continue
                raw = await reader.readexactly(size)
                self.metrics["recv"] += 1
                try:
                    self._recv_q.put_nowait(raw)
                    self._recv_bytes += len(raw)
                    self.wire.account_recv(raw)
                except asyncio.QueueFull:
                    self.metrics["dropped_recv"] += 1
                    self.wire.account_lost("dropped_recv", raw)
        except (asyncio.IncompleteReadError, ConnectionError, OSError):
            pass
        finally:
            self._conn_writers.discard(writer)
            writer.close()
            try:
                await writer.wait_closed()
            except (ConnectionError, OSError):
                pass

    # -- outbound -------------------------------------------------------

    def _outbox(self, dest: str) -> asyncio.Queue:
        q = self._outboxes.get(dest)
        if q is None:
            q = asyncio.Queue(maxsize=OUTBOX_DEPTH)
            self._outboxes[dest] = q
            self._sender_tasks[dest] = asyncio.get_running_loop().create_task(
                self._sender_loop(dest, q)
            )
        return q

    async def _sender_loop(self, dest: str, q: asyncio.Queue) -> None:
        """Own the connection to one peer: (re)connect, drain the outbox.
        Connection failures drop queued frames after a few attempts —
        fire-and-forget, like the reference's ignored http.Post errors
        (node.go:121), but bounded and metered. A frame that fails
        MID-WRITE is no longer silently lost: it is counted
        (frames_dropped) and, when quorum-critical, requeued exactly once
        (frames_requeued) so a connection blip doesn't eat a vote or
        certificate that is emitted exactly once."""
        backoff = 0.05
        writer: Optional[asyncio.StreamWriter] = None
        while True:
            item = await q.get()
            raw, retried = item[0], item[1]
            while writer is None:
                host, port = self.peers[dest]
                try:
                    _, writer = await asyncio.open_connection(host, port)
                    backoff = 0.05
                except OSError:
                    self.metrics["reconnects"] += 1
                    await asyncio.sleep(backoff)
                    backoff = min(backoff * 2, 2.0)
                    # drain the DEFERRABLE frames that piled up while the
                    # peer was down — their senders retransmit; stale
                    # copies only add load. Quorum-critical frames (votes,
                    # certs — emitted exactly once, possibly requeued from
                    # a mid-write failure above) are kept: discarding them
                    # here would void the requeue guarantee right when the
                    # link is flapping.
                    dropped = 0
                    kept = []
                    while (
                        q.qsize() + len(kept) > OUTBOX_DEPTH // 2
                        and q.qsize() > 0
                    ):
                        qi = q.get_nowait()
                        if _item_deferrable(qi):
                            dropped += 1
                            self.wire.account_lost("dropped_outbox", qi[0])
                        else:
                            kept.append(qi)
                    for qi in kept:
                        q.put_nowait(qi)
                    self.metrics["dropped_outbox"] += dropped
            try:
                writer.write(encode_frame(raw))
                await writer.drain()
                self.metrics["sent"] += 1
                self.wire.account_send(dest, raw)
            except (ConnectionError, OSError):
                writer = None  # reconnect on next frame
                requeued = False
                if not retried and not _item_deferrable(item):
                    try:
                        q.put_nowait([raw, True, item[2]])
                        requeued = True
                        self.metrics["frames_requeued"] += 1
                    except asyncio.QueueFull:
                        pass
                if not requeued:
                    self.metrics["frames_dropped"] += 1
                    self.wire.account_lost("frames_dropped", raw)

    # -- Transport interface -------------------------------------------

    async def send(self, dest: str, raw: bytes) -> None:
        if dest == self.node_id:
            try:
                self._recv_q.put_nowait(raw)
                self._recv_bytes += len(raw)  # recv() decrements for every frame
                self.wire.account_send(dest, raw)
                self.wire.account_recv(raw)
            except asyncio.QueueFull:
                self.metrics["dropped_recv"] += 1
                self.wire.account_lost("dropped_recv", raw)
            return
        if dest not in self.peers:
            # unknown destination: fire-and-forget semantics, but the
            # bytes are still accounted (a reconfig-removed peer showing
            # up here is a diagnosable signal, not silence)
            self.wire.account_lost("no_route", raw)
            return
        try:
            self._outbox(dest).put_nowait([raw, False, None])
        except asyncio.QueueFull:
            self.metrics["dropped_outbox"] += 1
            self.wire.account_lost("dropped_outbox", raw)

    async def broadcast(self, raw: bytes, dests: Iterable[str]) -> None:
        for dest in dests:
            if dest != self.node_id:
                await self.send(dest, raw)

    async def recv(self) -> bytes:
        raw = await self._recv_q.get()
        self._recv_bytes -= len(raw)
        # trace-plane recv stamp at the dequeue seam: queue residency is
        # part of the wire edge; self-sent frames are filtered by sender
        # id inside (never raises, unstamped frames gated by substring)
        trace.recv_stamp(self.node_id, raw)
        return raw

    def recv_nowait(self) -> Optional[bytes]:
        try:
            raw = self._recv_q.get_nowait()
        except asyncio.QueueEmpty:
            return None
        self._recv_bytes -= len(raw)
        trace.recv_stamp(self.node_id, raw)
        return raw
