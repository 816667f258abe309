"""Message schema: canonical serialization, digests, signing payloads."""

import json
import random

from simple_pbft_tpu import messages as m
from simple_pbft_tpu.crypto import ed25519_cpu as ed


def test_roundtrip_all_kinds():
    samples = [
        m.Request(sender="c1", client_id="c1", timestamp=7, operation="put x 1"),
        m.Reply(sender="r0", view=1, seq=2, client_id="c1", timestamp=7, result="ok"),
        m.ReplyBatch(sender="r0", view=1, seq=2, client_id="c1", spec=1, epoch=3,
                     timestamps=[7, 8, 9], results=["ok", "", "v\"1"], mac="ab" * 32),
        m.PrePrepare(sender="r0", view=0, seq=1, digest="ab", block=[{"op": 1}]),
        m.Prepare(sender="r1", view=0, seq=1, digest="ab"),
        m.Commit(sender="r2", view=0, seq=1, digest="ab"),
        m.Checkpoint(sender="r1", seq=100, state_digest="cd"),
        m.ViewChange(sender="r3", new_view=2, stable_seq=100),
        m.NewView(sender="r2", new_view=2),
    ]
    for msg in samples:
        wire = msg.to_wire()
        back = m.Message.from_wire(wire)
        assert back == msg
        assert type(back) is type(msg)


def test_canonical_encoding_deterministic():
    a = m.Prepare(sender="r1", view=3, seq=9, digest="dd")
    b = m.Prepare(digest="dd", seq=9, view=3, sender="r1")
    assert a.to_wire() == b.to_wire()
    assert a.payload_digest() == b.payload_digest()


def test_signing_payload_excludes_sig():
    msg = m.Prepare(sender="r1", view=1, seq=1, digest="d")
    unsigned_payload = msg.signing_payload()
    msg.sig = "aa" * 64
    assert msg.signing_payload() == unsigned_payload
    assert msg.payload_digest() == m.Message.from_wire(msg.to_wire()).payload_digest()


def test_sign_and_verify_message():
    seed = b"\x05" * 32
    pub = ed.public_key(seed)
    msg = m.Commit(sender="r2", view=1, seq=4, digest="beef")
    msg.sig = ed.sign(seed, msg.signing_payload()).hex()
    assert ed.verify(pub, msg.signing_payload(), bytes.fromhex(msg.sig))
    # Mutating any field invalidates
    msg.seq = 5
    assert not ed.verify(pub, msg.signing_payload(), bytes.fromhex(msg.sig))


def test_reply_batch_payload_blanks_both_authenticators():
    """sig and mac attest the same bytes, as Reply's do; any entry moves
    the payload, an authenticator never does."""
    batch = m.ReplyBatch(sender="r0", view=1, seq=2, client_id="c1",
                         timestamps=[7, 8], results=["a", "b"])
    payload = batch.signing_payload()
    batch.sig = "aa" * 64
    batch.mac = "bb" * 32
    assert batch.signing_payload() == payload
    assert m.Message.from_wire(batch.to_wire()).signing_payload() == payload
    batch.results = ["a", "c"]
    assert batch.signing_payload() != payload
    # one entry says what a Reply with the frame's fields says, under
    # another kind: the two payloads can never be taken for each other
    single = m.Reply(sender="r0", view=1, seq=2, client_id="c1", timestamp=7,
                     result="a")
    assert single.signing_payload() != m.ReplyBatch(
        sender="r0", view=1, seq=2, client_id="c1", timestamps=[7],
        results=["a"]).signing_payload()


def test_block_digest_matches_content():
    block = [{"client_id": "c", "timestamp": 1, "operation": "x"}]
    d1 = m.PrePrepare.block_digest(block)
    d2 = m.PrePrepare.block_digest(list(block))
    assert d1 == d2
    assert d1 != m.PrePrepare.block_digest([])


def test_from_wire_malformed_always_valueerror():
    import pytest

    bad = [
        b"not json",
        b"123",
        b"[1,2]",
        b'{"kind":"nope"}',
        b'{"no_kind":1}',
        b'{"kind":"prepare","sender":{"x":1}}',
        b'{"kind":"prepare","view":"high"}',
        b'{"kind":"prepare","view":true}',
        b'{"kind":"preprepare","block":"notalist"}',
        b'{"kind":"replybatch","timestamps":7,"results":["ok"]}',
        b'{"kind":"replybatch","timestamps":["7"],"results":["ok"]}',
        b'{"kind":"replybatch","timestamps":[true],"results":["ok"]}',
        b'{"kind":"replybatch","timestamps":[7.5],"results":["ok"]}',
        b'{"kind":"replybatch","timestamps":[7],"results":[1]}',
        b'{"kind":"replybatch","timestamps":[7],"results":[["ok"]]}',
        b'{"kind":"replybatch","timestamps":[7],"results":"ok"}',
        b'{"kind":"replybatch","timestamps":[7],"results":["ok"],"spec":"1"}',
        b"\xff\xfe",
    ]
    for raw in bad:
        with pytest.raises(ValueError):
            m.Message.from_wire(raw)


def test_from_wire_hostile_nesting_and_size():
    import pytest

    deep = b"[" * 200000 + b"]" * 200000
    with pytest.raises(ValueError):
        m.Message.from_wire(b'{"kind":"preprepare","block":' + deep + b"}")
    nested = {"kind": "preprepare", "block": [{"a": 1}]}
    cur = nested["block"][0]
    for _ in range(100):
        cur["a"] = [{"a": 1}]
        cur = cur["a"][0]
    import json

    with pytest.raises(ValueError):
        m.Message.from_dict(nested)
    with pytest.raises(ValueError):
        m.Message.from_wire(b" " * (m.Message.MAX_WIRE_BYTES + 1))


def test_list_fields_require_dict_elements():
    import pytest

    with pytest.raises(ValueError):
        m.Message.from_wire(
            b'{"kind":"preprepare","view":0,"seq":1,"digest":"d","block":[1,"x"]}'
        )


def test_fuzz_mutated_wires_never_crash():
    """Systematic hostile-input sweep (SURVEY.md §5 sanitizer hygiene):
    thousands of deterministic random mutations of valid wire bytes must
    either decode to a Message or raise ValueError — never any other
    exception. This is the invariant every transport relies on."""
    rng = random.Random(1234)
    samples = [
        m.Request(sender="c1", client_id="c1", timestamp=7, operation="x"),
        m.PrePrepare(sender="r0", view=0, seq=1, digest="ab", block=[{"o": 1}]),
        m.Prepare(sender="r1", view=0, seq=1, digest="ab"),
        m.ReplyBatch(sender="r0", view=0, seq=1, client_id="c1",
                     timestamps=[7, 8], results=["ok", "ok"]),
        m.ViewChange(sender="r3", new_view=2, stable_seq=100),
        m.NewView(sender="r2", new_view=2),
    ]
    wires = [s.to_wire() for s in samples]
    for _ in range(4000):
        raw = bytearray(rng.choice(wires))
        for _ in range(rng.randint(1, 8)):
            op = rng.randrange(3)
            pos = rng.randrange(len(raw)) if raw else 0
            if op == 0 and raw:
                raw[pos] ^= 1 << rng.randrange(8)
            elif op == 1 and raw:
                del raw[pos]
            else:
                raw.insert(pos, rng.randrange(256))
        try:
            m.Message.from_wire(bytes(raw))
        except ValueError:
            pass  # the one allowed failure mode


def test_fuzz_random_json_structures_never_crash():
    """Random well-formed JSON (nested arrays/objects/scalars in schema
    and out) through from_wire: decode or ValueError, nothing else."""
    rng = random.Random(99)

    def gen(depth):
        k = rng.randrange(7 if depth < 4 else 5)
        if k == 0:
            return rng.randrange(-(2**40), 2**40)
        if k == 1:
            return rng.choice(["", "r0", "prepare", "x" * rng.randrange(40)])
        if k == 2:
            return rng.choice([True, False, None])
        if k == 3:
            return rng.random()
        if k == 4:
            kind = rng.choice(
                ["request", "preprepare", "prepare", "commit", "reply",
                 "replybatch", "checkpoint", "viewchange", "newview", "zzz"]
            )
            return {"kind": kind, "view": gen(depth + 1), "seq": gen(depth + 1)}
        if k == 5:
            return [gen(depth + 1) for _ in range(rng.randrange(4))]
        return {
            rng.choice(["kind", "view", "block", "sig", "sender", "q",
                        "timestamps", "results"]):
                gen(depth + 1)
            for _ in range(rng.randrange(4))
        }

    for _ in range(2000):
        raw = json.dumps(gen(0)).encode()
        try:
            m.Message.from_wire(raw)
        except ValueError:
            pass
