"""Directed tests for the round-3 advisor findings (ADVICE.md):

1. Hedged sends on a single-replica committee must not divide by zero
   (the rotation modulus was len(ids) - 1).
2. The nesting-depth guard runs on every frame: the old small-frame
   skip made validity size- and version-dependent (a deep <=1500-byte
   subtree accepted standalone, rejected when embedded in a NewView,
   and a RecursionError risk on CPython <= 3.11 re-encodes).
3. NativeEdVerifier's pubkey row cache and MacBank's shared-key cache
   must stay bounded under adversarial key/peer churn.
4. A mixed superseded/real reply split for one timestamp (a checkpoint
   fold racing a retransmission) triggers one early rebroadcast instead
   of waiting out the full request_timeout.
"""

import asyncio
import json

import pytest

from simple_pbft_tpu.client import Client, SupersededError
from simple_pbft_tpu.config import make_test_committee
from simple_pbft_tpu.crypto import ed25519_cpu
from simple_pbft_tpu.crypto.mac import MacBank
from simple_pbft_tpu.crypto.verifier import BatchItem
from simple_pbft_tpu.messages import Message, Reply
from simple_pbft_tpu.transport.base import Transport


class FakeTransport(Transport):
    def __init__(self, node_id: str):
        self.node_id = node_id
        self.q: asyncio.Queue = asyncio.Queue()
        self.sent = []
        self.broadcasts = []

    async def send(self, dest, raw):
        self.sent.append((dest, raw))

    async def broadcast(self, raw, dests):
        self.broadcasts.append((raw, tuple(dests)))

    async def recv(self):
        return await self.q.get()


def run(coro, timeout=30):
    return asyncio.run(asyncio.wait_for(coro, timeout))


def test_hedged_submit_single_replica_committee():
    """hedge > 0 with n=1: the send path must reach the timeout, not
    die in the hedge rotation's modulus."""

    async def scenario():
        cfg, keys = make_test_committee(n=1, clients=1)
        t = FakeTransport("c0")
        client = Client(
            client_id="c0", cfg=cfg, seed=keys["c0"].seed, transport=t,
            request_timeout=0.05, hedge=2,
        )
        with pytest.raises((asyncio.TimeoutError, TimeoutError)):
            await client.submit("op", retries=0)
        # the one replica got the request; no crash before the send
        assert t.sent and t.sent[0][0] == "r0"

    run(scenario())


def test_deep_small_frame_rejected_on_every_version():
    """A <=1500-byte ViewChange smuggling a >MAX_NESTING-deep subtree
    (wrapped in a dict element so typed-field validation alone doesn't
    catch it) must be rejected by the depth walk on EVERY CPython
    version. A small-frame skip here once made validity size- and
    version-dependent: the same bytes accepted standalone would be
    rejected by backups when embedded in a larger NewView — a
    re-poisonable view-change stall."""
    depth = 600
    deep = json.loads("[" * depth + "]" * depth)
    d = {
        "kind": "viewchange",
        "sender": "r1",
        "new_view": 1,
        "stable_seq": 0,
        "checkpoint_proof": [],
        "prepared_proofs": [{"deep": deep}],
    }
    raw = json.dumps(d, separators=(",", ":")).encode()
    assert len(raw) <= 1500
    with pytest.raises(ValueError, match="nesting"):
        Message.from_wire(raw)
    # sanity: a shallow frame of the same shape parses fine
    d["prepared_proofs"] = [{"deep": []}]
    msg = Message.from_wire(json.dumps(d, separators=(",", ":")).encode())
    assert isinstance(msg.signing_payload(), bytes)


def test_native_verifier_row_cache_bounded():
    try:
        from simple_pbft_tpu.crypto.verifier import NativeEdVerifier

        v = NativeEdVerifier()
    except ImportError:
        pytest.skip("native ed25519 library unavailable")
    v.MAX_KEYS = 4  # shadow the class bound for the test
    v._row_cache.clear()  # process-wide cache: isolate from other tests
    items = []
    for i in range(10):
        seed = bytes([i + 1]) * 32
        pk = ed25519_cpu.public_key(seed)
        msg = b"churn %d" % i
        items.append(BatchItem(pk, msg, ed25519_cpu.sign(seed, msg)))
    out = v.verify_batch(items)
    # correctness is unaffected by the bound: every signature verifies,
    # including the ones whose keys no longer fit in the cache
    assert out == [True] * 10
    assert len(v._row_cache) <= 4
    # uncached keys still verify on a second pass (recomputed per batch)
    assert v.verify_batch(items[-2:]) == [True, True]
    # corrupted sig under an uncached key still rejects
    bad = BatchItem(items[-1].pubkey, items[-1].msg,
                    items[-1].sig[:-1] + bytes([items[-1].sig[-1] ^ 1]))
    assert v.verify_batch([bad]) == [False]


def test_macbank_unknown_peer_not_cached():
    cfg, keys = make_test_committee(n=4, clients=1)
    bank = MacBank(keys["c0"].seed, cfg.kx_pubkeys)
    for i in range(100):
        assert bank.key_for(f"evil{i}") is None
    assert len(bank._keys) == 0  # misses never cached
    from simple_pbft_tpu.crypto import mac as mac_mod

    if not mac_mod.kx_available():
        # no X25519 backend: the committee publishes no kx keys and every
        # reply falls back to Ed25519 signatures — the known-peer half of
        # this test has nothing to exercise
        pytest.skip("cryptography wheel absent: MAC fast path disabled")
    known = bank.key_for("r0")
    assert known is not None and len(bank._keys) == 1


def test_mixed_split_triggers_early_rebroadcast():
    """One superseded + one real reply for the same ts (no quorum yet):
    the client rebroadcasts after a short backoff — well before
    request_timeout — and f+1 superseded replies then resolve the wait
    as SupersededError."""

    async def scenario():
        cfg, keys = make_test_committee(n=4, clients=1)
        t = FakeTransport("c0")
        client = Client(
            client_id="c0", cfg=cfg, seed=keys["c0"].seed, transport=t,
            request_timeout=5.0,
        )
        task = asyncio.create_task(client.submit("op", retries=0))
        await asyncio.sleep(0.05)
        (ts,) = client._waiters.keys()
        client._on_reply(Reply(sender="r0", view=0, seq=1, client_id="c0",
                               timestamp=ts, result="ok"))
        client._on_reply(Reply(sender="r1", view=0, seq=1, client_id="c0",
                               timestamp=ts, result="", superseded=1))
        # mixed split detected -> one rebroadcast lands after <=0.25 s
        await asyncio.sleep(0.5)
        assert len(t.broadcasts) == 1
        # a third conflicting reply must not schedule another one
        client._on_reply(Reply(sender="r2", view=0, seq=1, client_id="c0",
                               timestamp=ts, result="stale"))
        await asyncio.sleep(0.4)
        assert len(t.broadcasts) == 1
        # stabilized: a second superseded reply reaches f+1
        client._on_reply(Reply(sender="r3", view=0, seq=1, client_id="c0",
                               timestamp=ts, result="", superseded=1))
        with pytest.raises(SupersededError):
            await task

    run(scenario())
