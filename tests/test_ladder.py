"""The table-free verify program (ops/ladder.py) against its two references:
the RFC 8032 oracle (crypto/ed25519_cpu.verify), item for item, and the
comb kernel on the same items under keys that have tables, verdict for
verdict. A pile's verdicts must not depend on which program a row took
(ISSUE 36), so every failure the benchmark plants, every key-side edge
vector and the RFC's own vectors go through both.

One batch of 32 rows, one compile a program.
"""

import random

import pytest

from simple_pbft_tpu.crypto import ed25519_cpu as ref
from simple_pbft_tpu.crypto import tpu_verifier as tv
from simple_pbft_tpu.crypto.verifier import BatchItem
from test_tpu_verifier import RFC8032_VECTORS

SEED = 36


def _signed(i: int, msg: bytes) -> BatchItem:
    seed = bytes([i]) * 32
    return BatchItem(ref.public_key(seed), msg, ref.sign(seed, msg))


def _not_a_point(rng: random.Random) -> bytes:
    while True:
        cand = bytes([rng.randrange(256) for _ in range(31)] + [0])
        if ref.point_decompress(cand) is None:
            return cand


def _under(key: bytes, it: BatchItem) -> BatchItem:
    return BatchItem(key, it.msg, it.sig)


def _r_is_rb(key: bytes, msg: bytes, r: int) -> BatchItem:
    """(R = [r]B, S = r): verifies under `key` iff [k]A is the identity."""
    return BatchItem(
        key, msg,
        ref.point_compress(ref.point_mul(r, ref.B)) + r.to_bytes(32, "little"))


def _order_two(parity: int) -> BatchItem:
    """Under A = (0, -1), of order 2, [k]A vanishes iff k is even: the
    same signature shape accepted and rejected by k's low bit alone."""
    key = (ref.P - 1).to_bytes(32, "little")
    for i in range(64):
        it = _r_is_rb(key, b"order two %d" % i, 12345 + i)
        if ref.challenge_scalar(it.sig[:32], key, it.msg) % 2 == parity:
            return it
    raise AssertionError("no message with that parity")


def _cases():
    """name -> item. Good rows first, then kernel_stage's seven planted
    failures by its names, then the key-side and signature-side edges."""
    rng = random.Random(SEED)
    good = [_signed(1 + i % 5, b"row %d " % i + rng.randbytes(8)) for i in range(8)]
    cases = {f"good {i}": it for i, it in enumerate(good)}
    for i, (_seed, pub, msg, sig) in enumerate(RFC8032_VECTORS):
        cases[f"RFC 8032 vector {i + 1}"] = BatchItem(
            bytes.fromhex(pub), bytes.fromhex(msg), bytes.fromhex(sig))
    it = good[0]
    flipped = bytearray(it.sig)
    flipped[rng.randrange(64)] ^= 1 << rng.randrange(8)
    cases["flipped signature byte"] = BatchItem(it.pubkey, it.msg, bytes(flipped))
    cases["signed by another committee key"] = BatchItem(
        good[1].pubkey, good[1].msg, ref.sign(bytes([1]) * 32, good[1].msg))
    it = good[2]
    s_big = int.from_bytes(it.sig[32:], "little") + ref.L
    cases["S >= L"] = BatchItem(
        it.pubkey, it.msg, it.sig[:32] + s_big.to_bytes(32, "little"))
    cases["S = L"] = BatchItem(
        it.pubkey, it.msg, it.sig[:32] + ref.L.to_bytes(32, "little"))
    it = good[3]
    cases["non-canonical R.y"] = BatchItem(
        it.pubkey, it.msg, (ref.P + 1).to_bytes(32, "little") + it.sig[32:])
    cases["wrong-length key"] = _under(good[4].pubkey[:31], good[4])
    cases["wrong-length signature"] = BatchItem(
        good[5].pubkey, good[5].msg, good[5].sig[:63])
    cases["key not a curve point"] = _under(_not_a_point(rng), good[6])
    cases["A.y = p + 3"] = _under(
        (ref.P + 3).to_bytes(32, "little"), good[6])
    cases["A.y = 2^255 - 1"] = _under(b"\xff" * 31 + b"\x7f", good[6])
    cases["A.y >= p with the sign bit set"] = _under(b"\xff" * 32, good[6])
    cases["A: x = 0 with the sign bit set"] = _under(
        (1 | 1 << 255).to_bytes(32, "little"), good[7])
    cases["A the identity, a good signature of another key"] = _under(
        (1).to_bytes(32, "little"), good[7])
    cases["A the identity, R = [r]B, S = r: accepted"] = _r_is_rb(
        (1).to_bytes(32, "little"), b"any message", 2**200 + 36)
    cases["A of order 2, k even: accepted"] = _order_two(0)
    cases["A of order 2, k odd"] = _order_two(1)
    cases["A of order 4 (y = 0)"] = _under(bytes(32), good[7])
    cases["wrong message"] = BatchItem(good[4].pubkey, b"not it", good[4].sig)
    assert len(cases) <= 32
    return cases


CASES = _cases()
ACCEPTED = {n for n in CASES if n.startswith(("good", "RFC")) or "accepted" in n}


@pytest.fixture(scope="module")
def verdicts():
    """name -> (oracle, comb, ladder): one pass of each program over the
    same 32 rows. The ladder's verifier holds a one-key bank that a key
    no row uses fills, so every well-formed row is uncached; the comb's
    is roomy, so every valid key gets a table."""
    items = list(CASES.values())
    oracle = [ref.verify(it.pubkey, it.msg, it.sig) for it in items]
    comb = tv.TpuVerifier().verify_batch(items)
    lad = tv.TpuVerifier()
    lad._bank = tv.KeyBank(initial_capacity=1, max_keys=1)
    assert lad.verify_batch([_signed(99, b"occupier")]) == [True]
    ladder = lad.verify_batch(items)
    well_formed = sum(
        1 for it in items if len(it.pubkey) == 32 and len(it.sig) == 64)
    assert (lad.ladder_items, lad.ladder_passes) == (well_formed, 1)
    assert lad.overcap_fallback_items == 0 and len(lad._bank._index) == 1
    return {name: (oracle[i], comb[i], ladder[i]) for i, name in enumerate(CASES)}


@pytest.mark.parametrize("name", list(CASES))
def test_ladder_comb_and_oracle_agree(name, verdicts):
    oracle, comb, ladder = verdicts[name]
    assert oracle == (name in ACCEPTED)  # the case is what its name says
    assert ladder == oracle
    assert comb == oracle


def test_the_cases_cross_both_verdicts_and_every_planted_kind(verdicts):
    import os
    import re

    assert 10 < sum(v[0] for v in verdicts.values()) < len(verdicts) - 10
    stages = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "stages.py")
    with open(stages) as fh:
        planted = re.findall(r'plant\(\s*"([^"]+)"', fh.read())
    assert len(planted) == 7 and set(planted) <= set(CASES)


def test_a_rejected_key_never_poisons_its_neighbours():
    """A row whose key is no curve point runs the ladder on the identity:
    an off-curve point in the formulas could reach Z = 0, and one zero in
    the batch inversion's product tree would take every row with it."""
    rng = random.Random(SEED + 1)
    good = [_signed(1 + i % 3, b"neighbour %d" % i) for i in range(8)]
    items = [it if i % 2 else _under(_not_a_point(rng), it)
             for i, it in enumerate(good)]
    v = tv.TpuVerifier()
    v._bank = tv.KeyBank(initial_capacity=1, max_keys=1)
    assert v.verify_batch([_signed(99, b"occupier")]) == [True]
    assert v.verify_batch(items) == [bool(i % 2) for i in range(8)]
    assert v.ladder_items == 8


def test_no_bigint_decompression_for_a_key_past_the_bank(monkeypatch):
    """crypto/ed25519_cpu.point_decompress runs at most once a distinct
    uncached key over many piles (here: never; the device decompresses),
    a known-invalid key included."""
    calls = []
    real = ref.point_decompress
    monkeypatch.setattr(
        tv.ref, "point_decompress", lambda s: calls.append(s) or real(s))
    v = tv.TpuVerifier()
    v._bank = tv.KeyBank(initial_capacity=2, max_keys=2)
    banked = [_signed(1, b"a"), _signed(2, b"b")]
    assert v.verify_batch(banked) == [True, True]
    assert sorted(calls) == sorted(it.pubkey for it in banked)
    rng = random.Random(SEED + 2)
    pile = banked + [_signed(3 + i % 4, b"pile %d" % i) for i in range(5)]
    pile.append(_under(_not_a_point(rng), pile[-1]))
    del calls[:]  # the oracle's own calls while the pile was made
    for _ in range(6):
        assert v.verify_batch(pile) == [True] * 7 + [False]
    assert calls == []
    assert (v.ladder_items, v.ladder_passes) == (6 * 6, 6)
    assert len(v._bank._index) == 2 and not v._bank._invalid_cache
