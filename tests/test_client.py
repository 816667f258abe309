"""Client library: f+1 reply matching under forged and late replies.

The client skips signature checks for replies no waiter needs (a
throughput optimization) — these tests pin that verification still
gates every reply that CAN affect a result.

Every scenario runs twice: ``single`` (one request in flight, every
replica answers with a ``Reply``) and ``batched`` (two requests of the
client in one block, every replica answers both in ONE ``ReplyBatch``
under one authenticator). A batch's entries must count exactly as the
single replies would.
"""

import asyncio

import pytest

from simple_pbft_tpu.client import Client
from simple_pbft_tpu.config import make_test_committee
from simple_pbft_tpu.crypto import mac as mac_mod
from simple_pbft_tpu.crypto.signer import Signer
from simple_pbft_tpu.messages import Reply, ReplyBatch
from simple_pbft_tpu.transport.base import Transport


class FakeTransport(Transport):
    """Message sink + injectable inbox (no network)."""

    def __init__(self, node_id: str):
        self.node_id = node_id
        self.q: asyncio.Queue = asyncio.Queue()
        self.sent = []

    async def send(self, dest, raw):
        self.sent.append((dest, raw))

    async def broadcast(self, raw, dests):
        self.sent.append(("*", raw))

    async def recv(self):
        return await self.q.get()

    def recv_nowait(self):
        try:
            return self.q.get_nowait()
        except asyncio.QueueEmpty:
            return None


def run(coro, timeout=30):
    return asyncio.run(asyncio.wait_for(coro, timeout))


def _reply(rid, result, ts=1, view=0, spec=0):
    return Reply(sender=rid, view=view, seq=1, client_id="c0", timestamp=ts,
                 result=result, spec=spec)


class CountingVerifier:
    """Real CPU verification plus a call counter — observes whether the
    client pays signature work for a reply."""

    def __init__(self):
        from simple_pbft_tpu.crypto.verifier import best_cpu_verifier

        self.inner = best_cpu_verifier()
        self.calls = 0

    def verify_batch(self, items):
        self.calls += len(items)
        return self.inner.verify_batch(items)


@pytest.fixture(params=["single", "batched"])
def delivery(request):
    return request.param


class Wave:
    """A client with one (``single``) or two (``batched``) submits in
    flight, and replicas that answer ALL of them at once: with a
    ``Reply`` when there is one, with one ``ReplyBatch`` when there are
    two. ``put`` is one replica's answer, the same result for every
    timestamp unless ``results`` gives one each."""

    def __init__(self, delivery, n=4, verifier=None):
        self.cfg, self.keys = make_test_committee(n=n, clients=1)
        self.t = FakeTransport("c0")
        self.client = Client(client_id="c0", cfg=self.cfg,
                             seed=self.keys["c0"].seed, transport=self.t,
                             request_timeout=2.0, verifier=verifier)
        self.width = 1 if delivery == "single" else 2
        self.tasks = []
        self.tss = []

    async def __aenter__(self):
        self.client.start()
        self.tasks = [
            asyncio.create_task(self.client.submit(f"op {i}", retries=0))
            for i in range(self.width)
        ]
        await asyncio.sleep(0.05)
        self.tss = sorted(self.client._waiters)  # live wall-clock timestamps
        assert len(self.tss) == self.width
        return self

    async def __aexit__(self, *exc):
        for task in self.tasks:
            task.cancel()
        await asyncio.gather(*self.tasks, return_exceptions=True)
        await self.client.stop()

    def frame(self, rid, result, spec=0, seq=1, view=0, results=None,
              client_id="c0", tss=None):
        tss = self.tss if tss is None else tss
        results = [result] * len(tss) if results is None else results
        if len(tss) == 1:
            return Reply(sender=rid, view=view, seq=seq, client_id=client_id,
                         timestamp=tss[0], result=results[0], spec=spec)
        return ReplyBatch(sender=rid, view=view, seq=seq, client_id=client_id,
                          spec=spec, timestamps=list(tss), results=results)

    def sign(self, msg, rid=None, auth="sig"):
        """Authenticate as replica ``rid`` (default: the frame's sender)."""
        rid = rid or msg.sender
        if auth == "mac":
            key = mac_mod.shared_key(self.keys[rid].seed,
                                     self.cfg.kx_pubkeys["c0"])
            msg.mac = mac_mod.tag(key, msg.signing_payload())
        else:
            sender = msg.sender
            Signer(rid, self.keys[rid].seed).sign_msg(msg)
            msg.sender = sender
        return msg

    async def put(self, rid, result, auth="sig", **kw):
        await self.t.q.put(self.sign(self.frame(rid, result, **kw),
                                     auth=auth).to_wire())

    async def settle(self, seconds=0.2):
        await asyncio.sleep(seconds)

    def none_done(self):
        return not any(task.done() for task in self.tasks)

    async def results(self):
        return await asyncio.gather(*self.tasks)


@pytest.mark.parametrize("auth", ["sig", "mac"])
def test_forged_replies_never_match_and_valid_ones_do(delivery, auth):
    if auth == "mac" and not mac_mod.kx_available():
        pytest.skip("no X25519 backend: replies are always signed")

    async def scenario():
        async with Wave(delivery) as w:
            # forged: authenticated with a key that is not the claimed
            # sender's (r3's key under r0..r2's names; a stranger's
            # signature)
            for rid in ("r0", "r1", "r2"):
                await w.t.q.put(
                    w.sign(w.frame(rid, "EVIL"), rid="r3", auth=auth).to_wire())
            forger = Signer("evil", b"\xee" * 32)
            for rid in ("r0", "r1", "r2"):
                msg = w.frame(rid, "EVIL")
                forger.sign_msg(msg)
                msg.sender = rid
                await w.t.q.put(msg.to_wire())
            # a tag over other bytes: one entry changed after tagging
            msg = w.sign(w.frame("r0", "ok"), auth=auth)
            if isinstance(msg, ReplyBatch):
                msg.results = ["EVIL"] + msg.results[1:]
            else:
                msg.result = "EVIL"
            await w.t.q.put(msg.to_wire())
            # non-replica sender with a valid-for-itself signature
            msg = w.frame("nobody", "EVIL")
            forger.sign_msg(msg)
            await w.t.q.put(msg.to_wire())
            await w.settle()
            assert w.none_done(), "forged replies must never reach f+1"
            assert not any(w.client._replies[ts] for ts in w.tss)
            # two honest matching replies (f+1 for n=4) resolve it
            for rid in ("r0", "r1"):
                await w.put(rid, "ok", auth=auth)
            assert await w.results() == ["ok"] * w.width

    run(scenario())


def test_late_replies_after_match_skip_signature_work(delivery):
    async def scenario():
        counter = CountingVerifier()
        async with Wave(delivery, verifier=counter) as w:
            for rid in ("r0", "r1"):
                await w.put(rid, "done")
            assert await w.results() == ["done"] * w.width
            verified_during_match = counter.calls
            # both active frames verified: one check a FRAME, so a batch
            # of two pays what a single reply pays
            assert verified_during_match == 2
            # late replies for the resolved timestamps: the recv loop must
            # drop them BEFORE verification (the throughput optimization
            # this suite pins) — the counter must not move
            for rid in ("r2", "r3"):
                await w.put(rid, "divergent")
            await w.settle(0.1)
            assert counter.calls == verified_during_match

    run(scenario())


def test_spec_reply_upgrade_never_double_counts(delivery):
    """ISSUE 15 reply accounting: a replica that upgrades its
    speculative reply to final is ONE voice — per-(replica, request)
    dedupe with the stricter (final) mark winning. n=4: the speculative
    fast path needs 2f+1 = 3 DISTINCT replicas; a double-counted
    upgrade would fake the third."""

    async def scenario():
        async with Wave(delivery) as w:
            client = w.client
            # two speculative replies, then the SAME replica upgrades to
            # final: still only two distinct replicas — no quorum of any
            # kind may form (1 final < f+1 = 2; and 2 distinct marks < 3
            # spec quorum)
            await w.put("r0", "ok", spec=1)
            await w.put("r1", "ok", spec=1)
            await w.put("r0", "ok", spec=0)  # upgrade, not a third voice
            # ...and a late speculative copy must not downgrade the final
            await w.put("r0", "ok", spec=1)
            await w.settle()
            assert w.none_done(), "double-counted replica reached a quorum"
            # final won, recorded at its slot identity
            for ts in w.tss:
                assert client._replies[ts]["r0"] == ("ok", False, False, 1, 0)
                assert len(client._replies[ts]) == 2
            # a third DISTINCT replica completes the 2f+1 speculative quorum
            await w.put("r2", "ok", spec=1)
            assert await w.results() == ["ok"] * w.width
            assert client.metrics.get("spec_accepted") == w.width
            # a speculative reply never confirms the fast answer...
            await w.put("r3", "ok", spec=1)
            await w.settle()
            assert client.metrics.get("final_confirms", 0) == 0
            assert len(client._confirming) == w.width
            # ...final-commit confirmation retained: f+1 final replies
            # upgrade it (r0 final already counted; r1's arrives now)
            await w.put("r1", "ok", spec=0)
            await w.settle()
            assert client.metrics.get("final_confirms") == w.width
            assert not client._confirming

    run(scenario())


def test_spec_marks_across_slots_never_pool_into_a_quorum(delivery):
    """The speculative quorum is PER-SLOT: 2f+1 speculators of one slot
    are 2f+1 preparers of that slot (the quorum-intersection safety
    argument). Marks for the same request speculated at DIFFERENT seqs
    across failover re-proposals — each slot with <= f preparers — must
    never pool into a fake 2f+1."""

    async def scenario():
        async with Wave(delivery) as w:
            # three distinct replicas, same result — but three DIFFERENT
            # slots: no 2f+1 quorum exists for any one slot
            await w.put("r0", "ok", spec=1, seq=1)
            await w.put("r1", "ok", spec=1, seq=2)
            await w.put("r2", "ok", spec=1, seq=3)
            await w.settle()
            assert w.none_done(), "cross-slot marks pooled into a quorum"
            # a third mark for slot 2 completes a real per-slot quorum
            await w.put("r0", "ok", spec=1, seq=2)
            await w.put("r3", "ok", spec=1, seq=2)
            assert await w.results() == ["ok"] * w.width

    run(scenario())


def test_final_quorum_still_resolves_without_speculation(delivery):
    """Plain f+1 final matching is untouched: two final replies resolve
    at n=4 with no speculative reply in sight."""

    async def scenario():
        async with Wave(delivery) as w:
            for rid in ("r0", "r1"):
                await w.put(rid, "done")
            assert await w.results() == ["done"] * w.width
            assert w.client.metrics.get("spec_accepted", 0) == 0
            assert w.client.metrics["reply_frames"] == 2
            assert w.client.metrics["reply_entries_batched"] == (
                0 if delivery == "single" else 4)

    run(scenario())


def test_conflicting_results_wait_for_true_quorum(delivery):
    async def scenario():
        async with Wave(delivery) as w:
            # two replicas disagree (one Byzantine): no f+1 match yet
            await w.put("r0", "A")
            await w.put("r1", "B")
            await w.settle()
            assert w.none_done()
            # a third replica agreeing with A completes f+1 on A
            await w.put("r2", "A")
            assert await w.results() == ["A"] * w.width

    run(scenario())


def test_f_lying_replicas_cannot_form_either_quorum(delivery):
    """n=7, f=2: two liars answer every timestamp, speculative and final,
    over and over. f+1 = 3 finals and 2f+1 = 5 marks both stay out of
    reach, and the honest answer still wins."""

    async def scenario():
        async with Wave(delivery, n=7) as w:
            for _ in range(3):
                for rid in ("r5", "r6"):
                    await w.put(rid, "LIE", spec=1)
                    await w.put(rid, "LIE", spec=0)
                    await w.put(rid, "LIE", spec=1, seq=2)
            await w.settle()
            assert w.none_done(), "f replicas reached a quorum alone"
            for ts in w.tss:
                assert set(w.client._replies[ts]) == {"r5", "r6"}
            # with two honest finals beside them no result has f+1 either
            await w.put("r0", "ok")
            await w.put("r1", "ok")
            await w.settle()
            assert w.none_done()
            await w.put("r2", "ok")
            assert await w.results() == ["ok"] * w.width

    run(scenario())


# -- what only a ReplyBatch can get wrong ----------------------------------


def test_misaddressed_or_malformed_batches_are_dropped_unchecked():
    """A batch for another client, from a non-member, or with lists of
    unequal length counts for nothing and costs no signature check."""

    async def scenario():
        counter = CountingVerifier()
        async with Wave("batched", verifier=counter) as w:
            stranger = Signer("nobody", b"\xee" * 32)
            for rid in ("r0", "r1", "r2"):
                await w.put(rid, "ok", client_id="c9")
                short = w.frame(rid, "ok")
                short.results = short.results[:1]
                await w.t.q.put(w.sign(short).to_wire())
                longer = w.frame(rid, "ok")
                longer.results = longer.results + ["ok"]
                await w.t.q.put(w.sign(longer).to_wire())
            msg = w.frame("nobody", "ok")
            stranger.sign_msg(msg)
            await w.t.q.put(msg.to_wire())
            # well-formed JSON that is no ReplyBatch: never decoded
            await w.t.q.put(
                b'{"kind":"replybatch","client_id":"c0","sender":"r0",'
                b'"timestamps":[true],"results":["ok"]}')
            await w.settle()
            assert w.none_done()
            assert counter.calls == 0
            assert not any(w.client._replies[ts] for ts in w.tss)
            assert w.client.metrics["reply_entries_batched"] == 0

    run(scenario())


def test_repeated_timestamps_in_one_batch_fill_one_slot():
    """A replica is one voice per timestamp however often its batch names
    it: the last entry overwrites its own slot, and a second liar doing
    the same still leaves f+1 = 2... of the SAME result out of reach."""

    async def scenario():
        async with Wave("batched") as w:
            ts = w.tss[0]
            await w.put("r0", "", tss=[ts, ts, ts], results=["A", "A", "B"])
            await w.settle(0.1)
            assert w.none_done()
            assert w.client._replies[ts] == {"r0": ("B", False, False, 1, 0)}
            assert not w.client._replies[w.tss[1]]
            # speculative too: three copies are one mark of the 2f+1 = 3
            await w.put("r1", "", spec=1, tss=[ts, ts, ts],
                        results=["B", "B", "B"])
            await w.settle(0.1)
            assert w.none_done()
            # an honest second voice for B is a real f+1 for that timestamp
            await w.put("r2", "B", tss=[ts])
            assert await w.tasks[0] == "B"
            assert not w.tasks[1].done()

    run(scenario())


def test_batch_nobody_waits_for_is_dropped_before_its_check():
    """Unknown timestamps are ignored; a batch with no entry anybody
    waits for is dropped without paying for its authenticator, and a
    speculative batch for answers that only await confirmation too."""

    async def scenario():
        counter = CountingVerifier()
        async with Wave("batched", verifier=counter) as w:
            await w.put("r0", "ok", tss=[5, 6, 7])
            await w.settle(0.1)
            assert counter.calls == 0 and 5 not in w.client._replies
            # a batch that names one live timestamp among unknown ones is
            # checked once and counts for that timestamp alone
            await w.put("r0", "ok", tss=[5, w.tss[0], 7])
            await w.settle(0.1)
            assert counter.calls == 1
            assert set(w.client._replies[w.tss[0]]) == {"r0"}
            assert 5 not in w.client._replies and 7 not in w.client._replies
            # answer both speculatively: the futures resolve, the
            # timestamps stay in _confirming
            for rid in ("r1", "r2", "r3"):
                await w.put(rid, "ok", spec=1)
            await w.put("r0", "ok", spec=1)
            assert await w.results() == ["ok", "ok"]
            assert len(w.client._confirming) == 2
            paid = counter.calls
            # late speculative batch: nothing it could do, so no check
            await w.put("r0", "ok", spec=1)
            await w.settle(0.1)
            assert counter.calls == paid
            # final batches are still checked, and confirm
            await w.put("r0", "ok")
            await w.put("r1", "ok")
            await w.settle(0.1)
            assert counter.calls == paid + 2
            assert w.client.metrics["final_confirms"] == 2
            # and once confirmed, finals are late replies too
            await w.put("r2", "ok")
            await w.settle(0.1)
            assert counter.calls == paid + 2

    run(scenario())


def test_a_batch_may_mix_waited_and_confirming_timestamps():
    """One final batch can carry an entry that still has a live waiter
    and one whose speculative answer awaits confirmation: each is counted
    where a single Reply would be."""

    async def scenario():
        async with Wave("batched") as w:
            first, second = w.tss
            for rid in ("r0", "r1", "r2"):
                await w.put(rid, "ok", spec=1, tss=[first])
            assert await w.tasks[0] == "ok"
            assert first in w.client._confirming
            await w.put("r0", "ok")
            await w.put("r1", "ok")
            assert await w.tasks[1] == "ok"
            await w.settle(0.1)
            assert w.client.metrics["final_confirms"] == 1
            assert w.client.metrics.get("spec_final_mismatch", 0) == 0

    run(scenario())
