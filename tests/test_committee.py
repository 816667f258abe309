"""Integration tests: full committees on the in-process network.

Behavioral-parity checkpoint vs the reference's only demonstrated scenario
(SURVEY.md §3.2: 4 nodes, one client, request -> 3-phase commit -> reply),
then everything the reference could not do: concurrent requests, larger
committees, faulty replicas, duplicate/dropped messages.
"""

import asyncio

import pytest

from simple_pbft_tpu.committee import LocalCommittee
from simple_pbft_tpu.transport.local import FaultPlan


def run(coro, timeout=30):
    return asyncio.run(asyncio.wait_for(coro, timeout))


def test_four_node_single_request():
    """The reference's run.bat scenario: commit one request, reply to
    client — but signed, event-driven, and with f+1 reply matching."""

    async def scenario():
        com = LocalCommittee.build(n=4, clients=1)
        com.start()
        try:
            result = await com.clients[0].submit("put k hello")
            assert result == "ok"
            result = await com.clients[0].submit("get k")
            assert result == "hello"
        finally:
            await com.stop()
        # all replicas executed both blocks and agree on state
        digests = {r.app.state_digest() for r in com.replicas}
        assert len(digests) == 1
        assert all(r.executed_seq == 2 for r in com.replicas)

    run(scenario())


def test_concurrent_requests_pipeline():
    """Many in-flight requests (the reference serialized rounds via its
    scalar CurrentState; here seqs pipeline)."""

    async def scenario():
        com = LocalCommittee.build(n=4, clients=1)
        com.start()
        try:
            results = await asyncio.gather(
                *(com.clients[0].submit(f"put k{i} v{i}") for i in range(20))
            )
            assert results == ["ok"] * 20
        finally:
            await com.stop()
        primary = com.replica("r0")
        assert primary.metrics["committed_requests"] == 20
        # batching: fewer blocks than requests (drain sweeps coalesce)
        assert primary.metrics["committed_blocks"] <= 20
        digests = {r.app.state_digest() for r in com.replicas}
        assert len(digests) == 1

    run(scenario())


def test_seven_node_committee():
    """n=7, f=2: quorums of 5."""

    async def scenario():
        com = LocalCommittee.build(n=7, clients=1)
        com.start()
        try:
            assert await com.clients[0].submit("put a 1") == "ok"
        finally:
            await com.stop()
        assert sum(r.executed_seq == 1 for r in com.replicas) == 7

    run(scenario())


def test_commits_with_f_crashed_backups():
    """f crashed backups must not block progress (quorum 2f+1 of n)."""

    async def scenario():
        com = LocalCommittee.build(n=4, clients=1)
        # crash r3 by never starting it
        for r in com.replicas:
            if r.id != "r3":
                r.start()
        for c in com.clients:
            c.start()
        try:
            assert await com.clients[0].submit("put a 1") == "ok"
        finally:
            await com.stop()

    run(scenario())


def test_progress_under_message_duplication():
    async def scenario():
        com = LocalCommittee.build(
            n=4, clients=1, fault_plan=FaultPlan(duplicate_rate=0.5, seed=7)
        )
        com.start()
        try:
            for i in range(5):
                assert await com.clients[0].submit(f"put x{i} {i}") == "ok"
        finally:
            await com.stop()
        digests = {r.app.state_digest() for r in com.replicas}
        assert len(digests) == 1

    run(scenario())


def test_progress_under_light_message_loss():
    """Client retransmission + quorum redundancy ride out 5% drop."""

    async def scenario():
        com = LocalCommittee.build(
            n=4, clients=1, fault_plan=FaultPlan(drop_rate=0.05, seed=3)
        )
        com.start()
        try:
            for i in range(5):
                # generous retries: a dropped-vote pattern can force a
                # multi-view failover (~7 s with 2 s view timers) and the
                # client must outlast it, not win a race with it
                assert (
                    await com.clients[0].submit(f"put y{i} {i}", retries=12)
                    == "ok"
                )
        finally:
            await com.stop()

    run(scenario())


def test_duplicate_request_reexecutes_nothing():
    """At-most-once execution: a retransmitted request must not re-apply."""

    async def scenario():
        com = LocalCommittee.build(n=4, clients=1)
        com.start()
        try:
            await com.clients[0].submit("put k 1")
            # forge a retransmission of the EXECUTED timestamp (clients
            # use wall-clock timestamps) straight to the primary
            from simple_pbft_tpu.messages import Request

            primary = com.replica("r0")
            for _ in range(100):  # submit returns on f+1; primary may lag
                if primary.recent_replies.get("c0"):
                    break
                await asyncio.sleep(0.02)
            (ts,) = primary.recent_replies["c0"].keys()
            req = Request(client_id="c0", timestamp=ts, operation="put k 1")
            com.clients[0].signer.sign_msg(req)
            await com.clients[0].transport.send("r0", req.to_wire())
            await asyncio.sleep(0.2)
        finally:
            await com.stop()
        primary = com.replica("r0")
        assert primary.metrics["committed_requests"] == 1

    run(scenario())


def test_unsigned_traffic_rejected():
    """Messages with missing/garbage signatures never reach consensus."""

    async def scenario():
        com = LocalCommittee.build(n=4, clients=1)
        com.start()
        try:
            from simple_pbft_tpu.messages import PrePrepare, Request

            # unsigned request straight at the primary
            req = Request(
                sender="c0", client_id="c0", timestamp=99, operation="put z 9"
            )
            ep = com.net.endpoint("intruder")
            await ep.send("r0", req.to_wire())
            # bogus pre-prepare from a non-member
            pp = PrePrepare(
                sender="intruder", view=0, seq=1, digest="d", block=[]
            )
            await ep.send("r1", pp.to_wire())
            await asyncio.sleep(0.2)
        finally:
            await com.stop()
        assert all(r.metrics["committed_requests"] == 0 for r in com.replicas)
        # unsigned request = no signature items collected -> precheck drop
        assert com.replica("r0").metrics["dropped_precheck"] >= 1
        assert com.replica("r1").metrics["dropped_precheck"] >= 1

    run(scenario())


def test_checkpoint_advances_watermark_and_gcs():
    async def scenario():
        com = LocalCommittee.build(
            n=4, clients=1, checkpoint_interval=2, watermark_window=64
        )
        com.start()
        try:
            for i in range(6):
                await com.clients[0].submit(f"put c{i} {i}")
            await asyncio.sleep(0.3)  # let checkpoint gossip settle
        finally:
            await com.stop()
        for r in com.replicas:
            assert r.stable_seq >= 2, (r.id, r.stable_seq)
            # GC dropped instances at/below the watermark
            assert all(seq > r.stable_seq for (_, seq) in r.instances)

    run(scenario())


def test_client_keys_cannot_join_quorums():
    """A Byzantine primary signing votes as clients must not reach quorum
    (clients' keys are known committee-wide but carry no consensus role)."""

    async def scenario():
        from simple_pbft_tpu.crypto.signer import Signer
        from simple_pbft_tpu.messages import Commit, PrePrepare, Prepare

        com = LocalCommittee.build(n=4, clients=2)
        # only r0 (Byzantine primary) + r1 honest; r2/r3 "crashed"
        com.replica("r0").start()
        com.replica("r1").start()
        for c in com.clients:
            c.start()
        try:
            # r0 proposes an empty block legitimately, then forges
            # prepare/commit votes as c0 and c1 toward r1
            block = []
            pp = PrePrepare(
                view=0, seq=1, digest=PrePrepare.block_digest(block), block=block
            )
            r0 = com.replica("r0")
            r0.signer.sign_msg(pp)
            await r0.transport.send("r1", pp.to_wire())
            for fake in ["c0", "c1"]:
                signer = Signer(fake, com.keys[fake].seed)
                for cls in (Prepare, Commit):
                    vote = cls(view=0, seq=1, digest=pp.digest)
                    signer.sign_msg(vote)
                    await r0.transport.send("r1", vote.to_wire())
            await asyncio.sleep(0.3)
        finally:
            await com.stop()
        r1 = com.replica("r1")
        assert r1.metrics["committed_blocks"] == 0
        # client-keyed votes are a ROLE violation: rejected before any
        # signature items are collected (bad_sig stays a pure forged-
        # signature alarm)
        assert r1.metrics["dropped_precheck"] >= 4

    run(scenario())


def test_client_impersonation_rejected():
    """c1 signing a request that claims client_id=c0 must be dropped."""

    async def scenario():
        from simple_pbft_tpu.messages import Request

        com = LocalCommittee.build(n=4, clients=2)
        com.start()
        try:
            req = Request(client_id="c0", timestamp=5, operation="put k evil")
            com.clients[1].signer.sign_msg(req)  # signs as c1
            await com.clients[1].transport.send("r0", req.to_wire())
            await asyncio.sleep(0.2)
        finally:
            await com.stop()
        assert all(r.metrics["committed_requests"] == 0 for r in com.replicas)

    run(scenario())


def test_lagging_replica_state_transfer():
    """A replica partitioned through several checkpoints must catch up via
    verified snapshot transfer when the partition heals."""

    async def scenario():
        plan = FaultPlan()
        com = LocalCommittee.build(
            n=4, clients=1, fault_plan=plan, checkpoint_interval=2
        )
        # partition r3 from everyone
        for other in ["r0", "r1", "r2", "c0"]:
            plan.cut("r3", other)
        com.start()
        try:
            for i in range(6):
                assert await com.clients[0].submit(f"put s{i} {i}") == "ok"
            r3 = com.replica("r3")
            assert r3.executed_seq == 0  # fully partitioned
            plan.heal()
            # next round of traffic brings checkpoint gossip + state sync
            for i in range(6, 10):
                assert await com.clients[0].submit(f"put s{i} {i}") == "ok"
            await asyncio.sleep(0.5)
        finally:
            await com.stop()
        r3 = com.replica("r3")
        assert r3.metrics["state_syncs"] >= 1
        assert r3.executed_seq >= 6
        # r3's data matches the quorum's
        assert r3.app.data == com.replica("r0").app.data

    run(scenario())


def test_committee_over_meshed_tpu_verifier():
    """Consensus traffic through the dp-SHARDED verifier: one TpuVerifier
    over an 8-device mesh (shard_map wire kernel, batch rows split
    across devices, tables replicated) shared by every replica — the
    multi-chip §2.2 data plane under a live committee, not a standalone
    batch call."""

    async def scenario():
        import jax
        import numpy as np
        from jax.sharding import Mesh

        from simple_pbft_tpu.crypto.tpu_verifier import TpuVerifier

        mesh = Mesh(np.asarray(jax.devices()[:8]), ("dp",))
        shared = TpuVerifier(mesh=mesh, initial_keys=16)
        com = LocalCommittee.build(
            n=4,
            clients=1,
            verifier_factory=lambda: shared,
            # 8 virtual devices time-share ONE core here: a sharded
            # dispatch costs ~1 s, a 3-phase round tens of seconds —
            # timers sized for the hardware shape
            view_timeout=180.0,
        )
        shared.warm(
            pubkeys=[kp.pub for kp in com.keys.values()], buckets=[8, 32]
        )
        baseline = shared.device_calls  # warm() already dispatched
        com.clients[0].request_timeout = 150.0
        com.start()
        try:
            assert await com.clients[0].submit("put m1 1") == "ok"
            assert await com.clients[0].submit("get m1") == "1"
            # consensus traffic itself must hit the mesh, beyond warmup
            assert shared.device_calls > baseline
        finally:
            await com.stop()

    run(scenario(), timeout=360)


def test_committee_over_tpu_verifier():
    """The full replica<->device seam under real traffic: every replica
    runs the TpuVerifier (fused comb engine, CPU-jax here, same code path
    as TPU) while clients drive concurrent requests, including one forged
    vote injected mid-stream. VERDICT round-1 weak #5."""

    async def scenario():
        from simple_pbft_tpu.crypto.ed25519_cpu import public_key, sign
        from simple_pbft_tpu.crypto.tpu_verifier import TpuVerifier
        from simple_pbft_tpu.crypto.verifier import BatchItem

        # Pre-warm the shared jit cache for the bucket sizes this traffic
        # hits (8 and 32): first-compile is ~40-60 s on a small CPU host,
        # far beyond a client's retry patience, and belongs to no replica.
        warm_seed = b"\xaa" * 32
        warm = [
            BatchItem(public_key(warm_seed), b"warm %d" % i, sign(warm_seed, b"warm %d" % i))
            for i in range(9)
        ]
        warmer = TpuVerifier()
        await asyncio.to_thread(warmer.verify_batch, warm[:1])  # bucket 8
        await asyncio.to_thread(warmer.verify_batch, warm)  # bucket 32

        # CPU-jax device calls are ~100-150 ms each (vs ~2 ms on the real
        # chip), so a 3-phase round takes seconds here: give the client and
        # the failover timers TPU-test-scale patience.
        com = LocalCommittee.build(
            n=4,
            clients=1,
            verifier_factory=lambda: TpuVerifier(),
            view_timeout=60.0,
        )
        com.clients[0].request_timeout = 30.0
        com.start()
        try:
            results = await asyncio.gather(
                *(com.clients[0].submit(f"put t{i} {i}") for i in range(8))
            )
            assert results == ["ok"] * 8
            # forged commit vote: signed with r2's key but claiming r1
            from simple_pbft_tpu.crypto.signer import Signer
            from simple_pbft_tpu.messages import Commit

            r0 = com.replica("r0")
            # target a not-yet-quorate slot: votes for already-committed
            # seqs are dropped pre-verification as redundant (and thus
            # never reach the forged-signature alarm)
            forged = Commit(view=0, seq=200, digest="f" * 64)
            Signer("r1", com.keys["r2"].seed).sign_msg(forged)
            forged.sender = "r1"
            await com.net.endpoint("r2").send("r0", forged.to_wire())
            for _ in range(100):  # poll: the verify may still be in flight
                if r0.metrics["bad_sig"] >= 1:
                    break
                await asyncio.sleep(0.1)
            assert r0.metrics["bad_sig"] >= 1
            assert await com.clients[0].submit("get t3") == "3"
            await asyncio.sleep(0.5)  # let laggards finish the last block
        finally:
            await com.stop()
        for r in com.replicas:
            # concurrent submits batch into few blocks; count requests
            assert r.metrics["committed_requests"] >= 9
            assert r.metrics["sweep_errors"] == 0

    run(scenario(), timeout=240)


# -- reply batching: one frame per (replica, client, block) ----------------


def _tap(client):
    """Record every frame the client takes off the wire, as
    (kind, raw bytes), before the client sees it."""
    from simple_pbft_tpu.transport.base import wire_kind

    frames = []
    seen = client._on_wire

    def on_wire(raw):
        frames.append((wire_kind(raw), raw))
        seen(raw)

    client._on_wire = on_wire
    return frames


async def _settled(com, timeout=10.0):
    """Wait until every replica has executed what the quorum committed,
    and the clients have read what that sent them."""
    deadline = asyncio.get_running_loop().time() + timeout
    while (len({r.executed_seq for r in com.replicas}) > 1
           and asyncio.get_running_loop().time() < deadline):
        await asyncio.sleep(0.02)
    await asyncio.sleep(0.05)


def test_pipelined_client_is_answered_in_reply_batches():
    """16 puts of ONE client in flight at n=4: every put is acknowledged,
    every replica's state equals the plain reference, and the replies
    came as ``replybatch`` frames, one per replica and block."""
    import json

    async def scenario():
        com = LocalCommittee.build(n=4, clients=1)
        client = com.clients[0]
        frames = _tap(client)
        reference = {}
        com.start()
        try:
            for wave in range(4):
                puts = {f"k{i}": f"v{wave}.{i}" for i in range(16)}
                results = await asyncio.gather(
                    *(client.submit(f"put {k} {v}") for k, v in puts.items())
                )
                assert results == ["ok"] * 16
                reference.update(puts)
            await _settled(com)
        finally:
            await com.stop()
        for r in com.replicas:
            assert json.loads(r.app.snapshot()) == reference, r.id
        assert len({r.app.state_digest() for r in com.replicas}) == 1
        assert len({r.executed_seq for r in com.replicas}) == 1
        sent = {
            key: sum(r.metrics[key] for r in com.replicas)
            for key in ("reply_entries_batched", "reply_frames_sent",
                        "replies_sent", "spec_replies_sent")
        }
        assert sent["reply_entries_batched"] > 0
        # fewer frames than replies, and every reply is in exactly one
        assert sent["reply_frames_sent"] < (
            sent["replies_sent"] + sent["spec_replies_sent"])
        kinds = [kind for kind, _raw in frames]
        assert set(kinds) <= {"reply", "replybatch"}
        assert "replybatch" in kinds
        assert client.metrics["reply_frames"] == len(kinds)
        assert client.metrics["reply_entries_batched"] == (
            sent["reply_entries_batched"])
        assert sent["reply_frames_sent"] == len(kinds)
        assert client.metrics.get("spec_final_mismatch", 0) == 0
        # the reply cache is checkpoint state: one plain Reply a request,
        # whatever frame carried it
        from simple_pbft_tpu.messages import Reply

        for r in com.replicas:
            assert len(r.recent_replies["c0"]) == 64
            assert all(type(rep) is Reply
                       for rep in r.recent_replies["c0"].values())
        assert len({r._checkpoint_snapshot() for r in com.replicas}) == 1

    run(scenario())


# sha256 of the frames the PARENT of the reply-batching change sent for
# (view 0, seq 1, client c0, timestamp 7, result "ok", epoch 0) at n=4,
# keyed (sender, spec): the MAC'd Reply of each replica
GOLDEN_REPLY_SHA256 = {
    ("r0", 1): "9997afe9eb088f041b7a22bef73c98a605608097395da2befe5159112e7d59dd",
    ("r0", 0): "eaa0611fba79e8179d18b423069bf2e3f17ad32b2b22f84eb96e2db77e58c11b",
    ("r1", 1): "ba7ccbb878ab7e26a454e03e66447609c8f497c9a0c535cc2a979976a5657954",
    ("r1", 0): "c8e99c1a184894e00e20c6ff2bf96e8f20e98b619e2cb245d8b386354addc45b",
    ("r2", 1): "9e08382c81a658ec656cfe508bf8feee13128683ca35bad07ff848613183e383",
    ("r2", 0): "4619f133de9250a32f2ebb57bff12c548af232814aa97aed01e61108cadb4ff8",
    ("r3", 1): "03077b800e18392edfb36dd8e24be778ea003e11b16bf6e82bfa65415d47ccff",
    ("r3", 0): "bb636daffe63920e57c3f10c3677c9c49cb21acb4570263eb3d6e56e4acb6f97",
}
GOLDEN_R1_FINAL = (
    b'{"client_id":"c0","epoch":0,"kind":"reply","mac":"da16c28521d1af26a6c'
    b'bbb7e6847878ca4ca654379d6d9ab5225e07b7a76329b","result":"ok","sender"'
    b':"r1","seq":1,"sig":"","spec":0,"superseded":0,"timestamp":7,"view":0}'
)


def test_one_request_in_flight_sends_the_parents_reply_bytes():
    """A client with one request in flight pays nothing for the batching:
    no ``replybatch`` frame exists, and each ``Reply`` is byte for byte
    what the parent commit sent (golden frames, timestamps pinned)."""
    import hashlib
    import itertools
    import json

    from simple_pbft_tpu.crypto import mac as mac_mod

    if not mac_mod.kx_available():
        pytest.skip("no X25519 backend: replies are signed, not MAC'd")

    async def scenario():
        com = LocalCommittee.build(n=4, clients=1)
        client = com.clients[0]
        client._ts = itertools.count(7)
        frames = _tap(client)
        com.start()
        try:
            assert await client.submit("put k v") == "ok"
            first = list(frames)
            for i in range(5):
                assert await client.submit(f"put k{i} v") == "ok"
            await _settled(com)
        finally:
            await com.stop()
        assert {kind for kind, _raw in frames} == {"reply"}
        assert sum(r.metrics["reply_entries_batched"] for r in com.replicas) == 0
        assert client.metrics["reply_entries_batched"] == 0
        assert client.metrics["reply_frames"] == len(frames)
        assert len(first) >= com.cfg.quorum
        finals = 0
        for _kind, raw in first:
            doc = json.loads(raw)
            assert doc["timestamp"] == 7
            finals += not doc["spec"]
            assert hashlib.sha256(raw).hexdigest() == GOLDEN_REPLY_SHA256[
                (doc["sender"], doc["spec"])], raw
        assert hashlib.sha256(GOLDEN_R1_FINAL).hexdigest() == (
            GOLDEN_REPLY_SHA256[("r1", 0)])
        every = [raw for _kind, raw in frames]
        assert GOLDEN_R1_FINAL in every or finals == 0

    run(scenario())


def test_a_dropped_batch_is_repaired_by_single_cached_replies():
    """Lose every ``replybatch`` frame: the pipelined client times out,
    rebroadcasts each request, and every replica answers from
    ``recent_replies`` with a single ``Reply`` (signed on demand: the
    cached copies of batched replies carry no authenticator)."""

    async def scenario():
        com = LocalCommittee.build(n=4, clients=1)
        client = com.clients[0]
        client.request_timeout = 0.4
        frames = _tap(client)
        tapped = client._on_wire
        dropped = []

        def lossy(raw):
            if b'"kind":"replybatch"' in raw:
                dropped.append(raw)
                return
            tapped(raw)

        client._on_wire = lossy
        com.start()
        try:
            results = await asyncio.gather(
                *(client.submit(f"put k{i} v{i}") for i in range(8))
            )
            assert results == ["ok"] * 8
        finally:
            await com.stop()
        assert dropped, "eight puts in flight were never batched"
        assert {kind for kind, _raw in frames} == {"reply"}
        assert client.metrics["retransmissions"] > 0
        assert client.metrics["recovered_after_retry"] > 0
        assert client.metrics["reply_entries_batched"] == 0
        for r in com.replicas:
            assert r.metrics["committed_requests"] == 8  # at most once

    run(scenario())


@pytest.mark.parametrize("auth", ["mac", "sig"])
def test_reply_frames_are_one_per_slot_and_client_in_block_order(auth):
    """The sender's grouping, on its own: replies of two slots (what a
    re-speculation hands over) and three clients become one frame per
    (slot, client), a lone reply stays a ``Reply``, block order is kept,
    and a client accepts each frame under the replica's MAC or, where a
    side publishes no kx key, its Ed25519 signature."""
    from simple_pbft_tpu.messages import Message, Reply, ReplyBatch

    overrides = {"kx_pubkeys": {}} if auth == "sig" else {}
    com = LocalCommittee.build(n=4, clients=3, **overrides)
    rep = com.replica("r2")
    if auth == "mac" and not rep.cfg.kx_pubkeys:
        pytest.skip("no X25519 backend: replies are always signed")

    def owed(seq, client, ts):
        return Reply(view=0, seq=seq, client_id=client, timestamp=ts,
                     result=f"res{ts}", spec=1, epoch=0)

    replies = [owed(5, "c0", 11), owed(5, "c1", 21), owed(5, "c0", 12),
               owed(5, "c2", 31), owed(5, "c0", 13), owed(5, "c1", 22),
               owed(6, "c0", 14), owed(6, "c0", 15), owed(6, "c1", 23)]
    frames = rep._reply_frames(replies)
    assert [(type(f), f.seq, f.client_id) for f in frames] == [
        (ReplyBatch, 5, "c0"), (ReplyBatch, 5, "c1"), (Reply, 5, "c2"),
        (ReplyBatch, 6, "c0"), (Reply, 6, "c1")]
    assert frames[0].timestamps == [11, 12, 13]
    assert frames[0].results == ["res11", "res12", "res13"]
    assert frames[1].timestamps == [21, 22]
    assert frames[3].timestamps == [14, 15]
    assert frames[2] is replies[3] and frames[4] is replies[8]
    assert rep.metrics["reply_frames_sent"] == 5
    assert rep.metrics["reply_entries_batched"] == 7
    for frame in frames:
        assert frame.sender == "r2" and frame.spec == 1
        assert bool(frame.mac) == (auth == "mac")
        assert bool(frame.sig) == (auth == "sig")
        client = com.clients[int(frame.client_id[1:])]
        assert client._authentic(Message.from_wire(frame.to_wire()))
        other = com.clients[(int(frame.client_id[1:]) + 1) % 3]
        if auth == "mac":  # a MAC speaks to one client only
            assert not other._authentic(Message.from_wire(frame.to_wire()))
    # members of a batch stay unauthenticated: signed on demand if resent
    assert not replies[0].mac and not replies[0].sig
    # every client owed one reply: the list goes out as it came
    singles = [owed(7, "c0", 16), owed(7, "c1", 24), owed(7, "c2", 32)]
    assert rep._reply_frames(singles) == singles
    assert rep.metrics["reply_entries_batched"] == 7
