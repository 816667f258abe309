"""An ACTIVE Byzantine committee member: equivocation under real traffic.

Unit tests cover individual hostile messages; this harness wires a
genuinely malicious replica — valid signatures, lying content — into a
live committee and asserts the two properties PBFT exists for:

- SAFETY: no two honest replicas execute different blocks at the same
  sequence (checked over every committed (seq, digest) pair).
- LIVENESS: client work keeps committing once failover moves past the
  equivocator (n=7 tolerates f=2).
"""

import asyncio
import time

import pytest

from simple_pbft_tpu.committee import LocalCommittee
from simple_pbft_tpu.crypto.signer import Signer
from simple_pbft_tpu.messages import Commit, Message, PrePrepare, Prepare, Request
from simple_pbft_tpu.transport.base import Transport


class PassthroughTransport(Transport):
    """Base for Byzantine transport wrappers: subclasses override
    _mutate(raw) and/or broadcast."""

    def __init__(self, inner, signer: Signer):
        self.inner = inner
        self.signer = signer
        self.node_id = inner.node_id

    def _mutate(self, raw):
        return raw

    async def send(self, dest, raw):
        await self.inner.send(dest, self._mutate(raw))

    async def broadcast(self, raw, dests):
        await self.inner.broadcast(self._mutate(raw), dests)

    async def recv(self):
        return await self.inner.recv()

    def recv_nowait(self):
        return self.inner.recv_nowait()


class EquivocatingTransport(PassthroughTransport):
    """Pre-prepares are FORKED — half the committee receives the real
    block, the other half a validly-signed substitute with a different
    block — and half of its prepare votes lie about the digest (also
    validly signed)."""

    def __init__(self, inner, signer: Signer):
        super().__init__(inner, signer)
        self.forked = 0

    def _fork_pre_prepare(self, pp: PrePrepare) -> bytes:
        # the Byzantine node cannot forge CLIENT signatures, so the
        # strongest fork honest replicas will admit structurally is a
        # permuted/truncated block of already-signed requests
        block = list(reversed(pp.block))[: max(1, len(pp.block) - 1)]
        if block == pp.block:
            block = []
        forked = PrePrepare(
            view=pp.view, seq=pp.seq,
            digest=PrePrepare.block_digest(block), block=block,
        )
        self.signer.sign_msg(forked)
        return forked.to_wire()

    async def broadcast(self, raw, dests):
        try:
            msg = Message.from_wire(raw)
        except ValueError:
            msg = None
        if isinstance(msg, PrePrepare) and msg.block:
            forked_raw = self._fork_pre_prepare(msg)
            self.forked += 1
            for i, dest in enumerate(d for d in dests if d != self.node_id):
                await self.inner.send(dest, raw if i % 2 == 0 else forked_raw)
            return
        if isinstance(msg, Prepare) and self.forked % 2 == 1:
            lie = Prepare(view=msg.view, seq=msg.seq, digest="ff" * 32)
            self.signer.sign_msg(lie)
            raw = lie.to_wire()
        await self.inner.broadcast(raw, dests)


@pytest.mark.slow
def test_equivocating_primary_safety_and_liveness():
    async def main():
        c = LocalCommittee.build(n=7, clients=2, view_timeout=1.0)
        # r0 is the view-0 primary: make it Byzantine
        evil = c.replica("r0")
        evil.transport = EquivocatingTransport(
            evil.transport, Signer("r0", c.keys["r0"].seed)
        )
        for cl in c.clients:
            cl.request_timeout = 1.0
        c.start()
        t0 = time.perf_counter()
        ok = 0
        try:
            async def pump(cl, tag):
                nonlocal ok
                i = 0
                while time.perf_counter() - t0 < 30:
                    try:
                        r = await cl.submit(f"put {tag}{i} v{i}", retries=10)
                        ok += 1 if r == "ok" else 0
                    except (asyncio.TimeoutError, TimeoutError):
                        pass
                    i += 1

            await asyncio.gather(*(pump(cl, f"c{j}_")
                                   for j, cl in enumerate(c.clients)))
            await asyncio.sleep(1)
            honest = [r for r in c.replicas if r.id != "r0"]
            # SAFETY: one digest per committed seq across honest replicas
            by_seq = {}
            for r in honest:
                for seq, digest in r.committed_log.items():
                    by_seq.setdefault(seq, set()).add(digest)
                for s, d in r.checkpoint_digests.items():
                    by_seq.setdefault(("ckpt", s), set()).add(d)
            forks = {k: v for k, v in by_seq.items() if len(v) > 1}
            assert not forks, forks
            # LIVENESS: work committed despite the equivocating primary
            assert ok >= 20, ok
            # the equivocator really did equivocate
            assert evil.transport.forked >= 1
        finally:
            await c.stop()

    asyncio.run(asyncio.wait_for(main(), 120))


class SharePoisoningTransport(PassthroughTransport):
    """QC-mode Byzantine backup: ALL its votes (prepare and commit)
    carry a VALID Ed25519 signature and the correct digest, but a
    garbage-yet-on-curve BLS share — the poison only surfaces when the
    primary aggregates, forcing the bisection path under live traffic."""

    def __init__(self, inner, signer: Signer):
        super().__init__(inner, signer)
        self.poisoned = 0

    def _mutate(self, raw):
        try:
            msg = Message.from_wire(raw)
        except ValueError:
            return raw
        if isinstance(msg, (Prepare, Commit)) and getattr(
            msg, "bls_share", ""
        ):
            from simple_pbft_tpu.crypto import bls

            # a real G1 point that is NOT a share over the payload
            bogus = bls.sign(12345, b"not the payload")
            msg.bls_share = bogus.hex()
            self.signer.sign_msg(msg)
            self.poisoned += 1
            return msg.to_wire()
        return raw


class DelayedTransport(PassthroughTransport):
    """Every frame this node sends leaves 0.15 s late."""

    async def send(self, dest, raw):
        await asyncio.sleep(0.15)
        await self.inner.send(dest, raw)

    async def broadcast(self, raw, dests):
        await asyncio.sleep(0.15)
        await self.inner.broadcast(raw, dests)


@pytest.mark.slow
def test_qc_byzantine_share_poisoner_is_bisected_out():
    async def main():
        from simple_pbft_tpu.transport.local import FaultPlan

        # delay r3's traffic so the poisoner's votes are always within
        # the first 2f+1 the primary aggregates (otherwise the test's
        # bisection assertion would depend on scheduling luck)
        plan = FaultPlan(seed=3)
        c = LocalCommittee.build(n=4, clients=1, qc_mode=True,
                                 view_timeout=6.0, fault_plan=plan)
        slow = c.replica("r3")
        slow.transport = DelayedTransport(
            slow.transport, Signer("r3", c.keys["r3"].seed)
        )
        evil = c.replica("r1")  # a BACKUP poisons its vote shares
        evil.transport = SharePoisoningTransport(
            evil.transport, Signer("r1", c.keys["r1"].seed)
        )
        c.clients[0].request_timeout = 8.0
        c.start()
        try:
            for i in range(3):
                assert await c.clients[0].submit(f"put p{i} {i}",
                                                 retries=10) == "ok"
            assert evil.transport.poisoned >= 1
            # the primary detected and excluded the poisoned shares
            primary = c.replica("r0")
            assert primary.metrics.get("qc_bad_shares", 0) >= 1, dict(
                primary.metrics
            )
            assert primary.metrics.get("qcs_formed", 0) >= 1
        finally:
            await c.stop()

    asyncio.run(asyncio.wait_for(main(), 120))
