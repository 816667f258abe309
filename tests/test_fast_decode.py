"""The codec's fast path against the generic decode (ISSUE 35).

The invariant (messages.py's docstring): for every byte string the fast
path either declines, and the generic path runs, or gives the type, the
fields and the ``signing_payload()`` the generic path gives; and it never
accepts what the generic path rejects. ``generic()`` below is the reference:
``from_wire`` with the fast path taken out."""

import asyncio
import dataclasses
import random
import time

import pytest

from simple_pbft_tpu import messages as m
from simple_pbft_tpu import trace
from simple_pbft_tpu.committee import LocalCommittee
from simple_pbft_tpu.crypto.signer import Signer

FLAT = ("int", "str")


def is_flat(cls) -> bool:
    """Told from the annotations, not from the codec's own table."""
    return all(f.type in FLAT for f in dataclasses.fields(cls))


def sample(cls, filled: bool):
    """One message of a class: every field at its default, or every int
    and str field filled (hex where a signature goes)."""
    msg = cls()
    if filled:
        for i, f in enumerate(dataclasses.fields(cls)):
            if f.type == "int":
                setattr(msg, f.name, 10 ** i + i)
            elif f.type == "str":
                setattr(msg, f.name,
                        "ab" * 64 if f.name == "sig" else f"{f.name} ~!#[]/{i}")
    return msg


def outcome(decode, raw: bytes):
    """What a decode gives, in a form two decodes can be compared by."""
    try:
        msg = decode(raw)
    except ValueError:
        return "ValueError"
    return (type(msg), msg.to_dict(), list(msg.to_dict()),
            msg.signing_payload(), msg.payload_digest())


def generic(raw: bytes):
    saved = m._fast_decode
    m._fast_decode = lambda raw: None
    try:
        return m.Message.from_wire(raw)
    finally:
        m._fast_decode = saved


def took_fast_path(raw: bytes) -> bool:
    return m._fast_decode(raw) is not None


KINDS = [(kind, filled) for kind in m.ALL_KINDS for filled in (False, True)]


@pytest.mark.parametrize("kind,filled", KINDS,
                         ids=[f"{k}-{'filled' if f else 'defaults'}"
                              for k, f in KINDS])
def test_every_kind_decodes_as_the_generic_path_does(kind, filled):
    cls = m._REGISTRY[kind]
    raw = sample(cls, filled).to_wire()
    assert took_fast_path(raw) == is_flat(cls)
    msg = m.Message.from_wire(raw)
    assert outcome(m.Message.from_wire, raw) == outcome(generic, raw)
    # the payload came with the decode exactly where the class signs the
    # base payload; it is the frame less its authenticators' values
    assert ("_payload" in msg.__dict__) == is_flat(cls)
    if is_flat(cls):
        blanked = msg.to_dict()
        for name in cls._AUTH_FIELDS:
            blanked[name] = ""
        assert msg.__dict__["_payload"] == m.canonical_json(blanked)


def test_the_flat_kinds_are_the_ones_the_issue_names():
    flat = {k for k in m.ALL_KINDS if is_flat(m._REGISTRY[k])}
    assert {"prepare", "commit", "checkpoint", "request", "reply"} <= flat
    assert not flat & {"preprepare", "viewchange", "newview", "qc",
                       "replybatch", "blockreply", "blockfetch", "slotfetch"}


# ---------------------------------------------------------------------------
# departures from the canonical layout: each declines, and what comes back
# is what the generic path gives (the same message or the same ValueError)
# ---------------------------------------------------------------------------

VOTE = m.Prepare(sender="r3", view=2, seq=7, digest="ab" * 32,
                 sig="cd" * 64).to_wire()
assert VOTE.startswith(b'{"bls_share":"","digest":"abab')
assert VOTE.endswith(b'","view":2}')


DEPARTURES = {
    "missing_default_field": VOTE.replace(b'"bls_share":"",', b""),
    "extra_key": VOTE.replace(b',"view":2}', b',"view":2,"zz":1}'),
    "swapped_keys": VOTE.replace(b'"seq":7,', b"").replace(
        b',"view":2}', b',"view":2,"seq":7}'),
    "duplicate_key": VOTE.replace(b'"seq":7,', b'"seq":6,"seq":7,'),
    "duplicate_last_key": VOTE.replace(b',"view":2}', b',"view":2,"view":3}'),
    "space_after_colon": VOTE.replace(b'"seq":7', b'"seq": 7'),
    "space_after_comma": VOTE.replace(b',"seq"', b', "seq"'),
    "leading_space": b" " + VOTE,
    "trailing_space": VOTE + b" ",
    "trailing_newline": VOTE + b"\n",
    "trailing_bytes": VOTE + b"x",
    "trailing_frame": VOTE + VOTE,
    "unicode_escape": VOTE.replace(b'"r3"', b'"r\\u0033"'),
    "solidus_escape": VOTE.replace(b'"r3"', b'"r\\/3"'),
    "escaped_quote": VOTE.replace(b'"r3"', b'"r\\"3"'),
    "non_ascii_byte": VOTE.replace(b'"r3"', '"ré"'.encode()),
    "invalid_utf8_byte": VOTE.replace(b'"r3"', b'"r\xff"'),
    "raw_del_byte": VOTE.replace(b'"r3"', b'"r\x7f"'),
    "raw_control_byte": VOTE.replace(b'"r3"', b'"r\t3"'),
    "true_for_int": VOTE.replace(b'"seq":7', b'"seq":true'),
    "null_for_int": VOTE.replace(b'"seq":7', b'"seq":null'),
    "string_for_int": VOTE.replace(b'"seq":7', b'"seq":"7"'),
    "int_for_string": VOTE.replace(b'"sender":"r3"', b'"sender":3'),
    "null_for_string": VOTE.replace(b'"sender":"r3"', b'"sender":null'),
    "list_for_string": VOTE.replace(b'"sender":"r3"', b'"sender":["r3"]'),
    "negative_int": VOTE.replace(b'"seq":7', b'"seq":-1'),
    "negative_zero": VOTE.replace(b'"seq":7', b'"seq":-0'),
    "leading_zeros": VOTE.replace(b'"seq":7', b'"seq":007'),
    "plus_sign": VOTE.replace(b'"seq":7', b'"seq":+7'),
    "int_of_19_digits": VOTE.replace(b'"seq":7', b'"seq":' + b"9" * 19),
    "int_of_40_digits": VOTE.replace(b'"seq":7', b'"seq":' + b"1" * 40),
    "float": VOTE.replace(b'"seq":7', b'"seq":7.0'),
    "exponent": VOTE.replace(b'"seq":7', b'"seq":7e0'),
    "empty_int": VOTE.replace(b'"seq":7', b'"seq":'),
    "unknown_kind": VOTE.replace(b'"prepare"', b'"prepared"'),
    "kind_in_capitals": VOTE.replace(b'"prepare"', b'"PREPARE"'),
    "kind_of_a_list_bearing_class": VOTE.replace(b'"prepare"', b'"qc"'),
    "no_kind": VOTE.replace(b'"kind":"prepare",', b""),
    "unclosed": VOTE[:-1],
    "list_around": b"[" + VOTE + b"]",
    "utf8_bom": b"\xef\xbb\xbf" + VOTE,
    "utf16": VOTE.decode().encode("utf-16"),
    "empty": b"",
}


def test_a_trace_stamped_frame_declines_and_reads_as_the_generic_path_reads():
    trace.configure(True)
    try:
        raw = trace.stamp(VOTE, trace.PREPARE, 2, 7, "r3")
    finally:
        trace.configure(False)
    assert trace._GATE in raw
    assert not took_fast_path(raw)
    assert outcome(m.Message.from_wire, raw) == outcome(generic, raw)
    assert m.Message.from_wire(raw) == m.Message.from_wire(VOTE)


@pytest.mark.parametrize("name", sorted(DEPARTURES))
def test_a_departure_declines_and_reads_as_the_generic_path_reads(name):
    raw = DEPARTURES[name]
    assert raw != VOTE
    assert not took_fast_path(raw)
    assert outcome(m.Message.from_wire, raw) == outcome(generic, raw)


def test_another_flat_kind_in_a_votes_layout_is_that_kind():
    raw = VOTE.replace(b'"prepare"', b'"commit"')
    assert took_fast_path(raw)
    assert type(m.Message.from_wire(raw)) is m.Commit
    assert outcome(m.Message.from_wire, raw) == outcome(generic, raw)


def test_the_longest_int_the_fast_path_takes_is_exact():
    raw = VOTE.replace(b'"seq":7', b'"seq":' + b"9" * 18)
    assert took_fast_path(raw)
    assert m.Message.from_wire(raw).seq == 10 ** 18 - 1
    assert outcome(m.Message.from_wire, raw) == outcome(generic, raw)


# ---------------------------------------------------------------------------
# seeded fuzz: mutated frames of every flat kind
# ---------------------------------------------------------------------------

SPLICES = [b'"', b"\\", b",", b":", b"{", b"}", b" ", b"0", b"-", b"1.5",
           b"true", b"null", b'"kind":"', b'"sig":"', b'""', b"\x7f", b"\xc3\xa9",
           b"\\u0041", b"[", b"]", b"e9", b"\n"]


def mutate(rng: random.Random, raw: bytes) -> bytes:
    out = bytearray(raw)
    for _ in range(rng.choice((1, 1, 1, 2, 3))):
        at = rng.randrange(len(out) + 1)
        how = rng.randrange(6)
        if how == 0 and out:
            out[min(at, len(out) - 1)] = rng.randrange(256)
        elif how == 1 and out:
            del out[min(at, len(out) - 1)]
        elif how == 2:
            out[at:at] = rng.choice(SPLICES)
        elif how == 3:
            end = min(len(out), at + rng.randrange(1, 40))
            out[at:at] = out[at:end]  # a stretch repeated
        elif how == 4:
            end = min(len(out), at + rng.randrange(1, 40))
            del out[at:end]
        else:
            other = rng.randrange(len(out) + 1)
            lo, hi = sorted((at, other))
            out[lo:hi] = out[lo:hi][::-1] if rng.random() < 0.5 else out[lo:hi].upper()
    return bytes(out)


@pytest.mark.parametrize("seed", range(4))
def test_fuzzed_frames_read_as_the_generic_path_reads(seed):
    rng = random.Random(3500 + seed)
    frames = [sample(m._REGISTRY[k], filled).to_wire()
              for k in m.ALL_KINDS if is_flat(m._REGISTRY[k])
              for filled in (False, True)]
    taken = 0
    for _ in range(1500):
        raw = mutate(rng, rng.choice(frames))
        taken += took_fast_path(raw)
        assert outcome(m.Message.from_wire, raw) == outcome(generic, raw), raw
    # the mutations land on both sides of the matcher
    assert 50 < taken < 1450


# ---------------------------------------------------------------------------
# the cached payload follows the fields
# ---------------------------------------------------------------------------


def test_a_field_set_after_a_fast_decode_drops_the_cached_payload():
    msg = m.Message.from_wire(VOTE)
    before = msg.__dict__["_payload"]
    msg.view = 9
    assert "_payload" not in msg.__dict__
    assert msg.signing_payload() != before
    assert msg.signing_payload() == generic(msg.to_wire()).signing_payload()


def test_setting_an_authenticator_keeps_the_cached_payload():
    vote = m.Message.from_wire(VOTE)
    vote.sig = "00" * 64
    assert "_payload" in vote.__dict__
    reply = m.Message.from_wire(
        m.Reply(sender="r1", view=1, seq=2, client_id="c0", timestamp=5,
                result="ok", sig="ab" * 64, mac="cd" * 32).to_wire())
    payload = reply.__dict__["_payload"]
    reply.mac = "11" * 32
    reply.sig = ""
    assert reply.__dict__["_payload"] == payload
    assert reply.signing_payload() == generic(reply.to_wire()).signing_payload()


# ---------------------------------------------------------------------------
# a hostile megabyte costs a scan, not a search
# ---------------------------------------------------------------------------

MEGABYTE = 1 << 20
REQUEST = m.Request(sender="c0", client_id="c0", timestamp=1,
                    operation="OPERATION", sig="ab" * 64).to_wire()
HOSTILE = {
    "a_flat_frame_of_one_long_string":
        REQUEST.replace(b"OPERATION", b"a" * MEGABYTE),
    "a_string_that_never_closes":
        REQUEST.split(b"OPERATION")[0] + b"a" * MEGABYTE,
    "a_string_that_closes_on_the_wrong_key":
        REQUEST.replace(b"OPERATION", b"a" * MEGABYTE).replace(
            b'"sender"', b'"sendex"'),
    "an_int_of_a_million_digits":
        REQUEST.replace(b'"timestamp":1', b'"timestamp":' + b"1" * MEGABYTE),
    "kind_keys_over_and_over": b'{"kind":"' * (MEGABYTE // 9 + 1),
    "a_kind_of_a_megabyte":
        REQUEST.replace(b'"request"', b'"' + b"request" * (MEGABYTE // 7) + b'"'),
    "quotes_and_commas": b'{"ack":0,"client_id":"' + b'","' * (MEGABYTE // 3),
}


@pytest.mark.parametrize("name", sorted(HOSTILE))
def test_a_hostile_megabyte_decodes_or_declines_in_linear_time(name):
    raw = HOSTILE[name]
    assert len(raw) >= MEGABYTE
    tenth = raw[: len(raw) // 10]
    m._fast_decode(tenth)  # the matcher is derived outside the clock
    t0 = time.perf_counter()
    m._fast_decode(tenth)
    t_tenth = time.perf_counter() - t0
    t0 = time.perf_counter()
    msg = m._fast_decode(raw)
    t_whole = time.perf_counter() - t0
    assert (msg is not None) == (name == "a_flat_frame_of_one_long_string")
    # ten times the bytes: a quadratic scan would take a hundred times
    # as long (and minutes in all); a floor keeps timer noise out
    assert t_whole < 0.5
    assert t_whole < 30 * max(t_tenth, 1e-3)


# ---------------------------------------------------------------------------
# the replica: the same signature obligations either way
# ---------------------------------------------------------------------------


def run(coro, timeout=60):
    return asyncio.run(asyncio.wait_for(coro, timeout))


def signed_sweep(com):
    """One sweep for r0: a prepare and a commit from each peer, and a
    client's request."""
    frames = []
    for rid in ("r1", "r2", "r3"):
        signer = Signer(rid, com.keys[rid].seed)
        for cls in (m.Prepare, m.Commit):
            vote = cls(view=0, seq=1, digest="ab" * 32)
            signer.sign_msg(vote)
            frames.append(vote.to_wire())
    req = m.Request(client_id="c0", timestamp=1000, operation="put k v")
    Signer("c0", com.keys["c0"].seed).sign_msg(req)
    frames.append(req.to_wire())
    return frames


async def sweep_items(frames):
    """(decoded, the BatchItems of each message, sig_spans, metrics) of one
    sweep through a fresh replica's ``_start_sweep``."""
    com = LocalCommittee.build(n=4, clients=1)
    r0 = com.replica("r0")
    per_message = []
    collect = r0._batch_items

    def recording(msg):
        per_message.append(collect(msg))
        return per_message[-1]

    r0._batch_items = recording
    decoded, sig_spans, task = r0._start_sweep(frames)
    assert all(await task)
    return decoded, per_message, sig_spans, r0.metrics


def test_a_sweep_gives_the_same_items_fast_or_generic():
    async def scenario():
        frames = signed_sweep(LocalCommittee.build(n=4, clients=1))
        spaced = [raw[:-1] + b" }" for raw in frames]
        assert all(took_fast_path(raw) for raw in frames)
        assert not any(took_fast_path(raw) for raw in spaced)
        fast = await sweep_items(frames)
        slow = await sweep_items(spaced)
        assert fast[0] == slow[0] and len(fast[0]) == 7
        assert fast[1] == slow[1]
        assert [len(items) for items in fast[1]] == [1] * 7
        assert fast[2] == slow[2] == [(i, i + 1) for i in range(7)]
        assert fast[3]["frames_fast_decoded"] == 7
        assert slow[3]["frames_fast_decoded"] == 0
        assert fast[3]["verified_sigs"] == slow[3]["verified_sigs"] == 7
        assert fast[3]["malformed"] == slow[3]["malformed"] == 0

    run(scenario())


def test_a_forged_signature_on_a_fast_decoded_vote_is_a_bad_sig():
    async def scenario():
        com = LocalCommittee.build(n=4, clients=1)
        r0 = com.replica("r0")
        vote = m.Prepare(view=0, seq=1, digest="ab" * 32)
        Signer("r1", com.keys["r2"].seed).sign_msg(vote)  # r2's key, r1's name
        honest = m.Prepare(view=0, seq=1, digest="ab" * 32)
        Signer("r3", com.keys["r3"].seed).sign_msg(honest)
        frames = [vote.to_wire(), honest.to_wire()]
        assert all(took_fast_path(raw) for raw in frames)
        await r0.process_sweep(frames)
        assert r0.metrics["frames_fast_decoded"] == 2
        assert r0.metrics["bad_sig"] == 1
        assert r0.metrics["verified_sigs"] == 2

    run(scenario())


def test_a_tampered_field_on_a_fast_decoded_vote_is_a_bad_sig():
    """The cached payload is the frame's own bytes: a frame whose field
    was changed after signing carries the changed payload to the verifier."""

    async def scenario():
        com = LocalCommittee.build(n=4, clients=1)
        r0 = com.replica("r0")
        vote = m.Prepare(view=0, seq=1, digest="ab" * 32)
        Signer("r1", com.keys["r1"].seed).sign_msg(vote)
        raw = vote.to_wire().replace(b'"seq":1', b'"seq":2')
        assert took_fast_path(raw)
        await r0.process_sweep([raw])
        assert r0.metrics["bad_sig"] == 1

    run(scenario())
