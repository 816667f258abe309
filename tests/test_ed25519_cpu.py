"""Known-answer + property tests for the pure-Python Ed25519 backend.

RFC 8032 §7.1 test vector 1 plus cross-validation against the independent
`cryptography` (OpenSSL) implementation.
"""

import os

import pytest

from simple_pbft_tpu.crypto import ed25519_cpu as ed


# RFC 8032 §7.1 TEST 1 (empty message)
RFC_SEED = bytes.fromhex(
    "9d61b19deffd5a60ba844af492ec2cc44449c5697b326919703bac031cae7f60"
)
RFC_PUB = bytes.fromhex(
    "d75a980182b10ab7d54bfed3c964073a0ee172f3daa62325af021a68f707511a"
)
RFC_SIG = bytes.fromhex(
    "e5564300c360ac729086e2cc806e828a"
    "84877f1eb8e5d974d873e06522490155"
    "5fb8821590a33bacc61e39701cf9b46b"
    "d25bf5f0595bbe24655141438e7a100b"
)


def test_rfc8032_vector1_pubkey():
    assert ed.public_key(RFC_SEED) == RFC_PUB


def test_rfc8032_vector1_sign():
    assert ed.sign(RFC_SEED, b"") == RFC_SIG


def test_rfc8032_vector1_verify():
    assert ed.verify(RFC_PUB, b"", RFC_SIG)


def test_tampered_message_rejected():
    assert not ed.verify(RFC_PUB, b"x", RFC_SIG)


def test_tampered_sig_rejected():
    bad = bytearray(RFC_SIG)
    bad[0] ^= 1
    assert not ed.verify(RFC_PUB, b"", bytes(bad))


def test_wrong_key_rejected():
    other_pub = ed.public_key(b"\x01" * 32)
    assert not ed.verify(other_pub, b"", RFC_SIG)


def test_noncanonical_s_rejected():
    s = int.from_bytes(RFC_SIG[32:], "little") + ed.L
    bad = RFC_SIG[:32] + int.to_bytes(s, 32, "little")
    assert not ed.verify(RFC_PUB, b"", bad)


def test_sign_verify_roundtrip_many():
    for i in range(8):
        seed = bytes([i]) * 32
        pub = ed.public_key(seed)
        msg = b"message-%d" % i
        sig = ed.sign(seed, msg)
        assert ed.verify(pub, msg, sig)
        assert not ed.verify(pub, msg + b"!", sig)


def test_cross_check_against_openssl():
    pytest.importorskip("cryptography")
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PrivateKey,
    )

    for i in range(4):
        seed = os.urandom(32)
        msg = os.urandom(100)
        sk = Ed25519PrivateKey.from_private_bytes(seed)
        from cryptography.hazmat.primitives import serialization

        their_pub = sk.public_key().public_bytes(
            serialization.Encoding.Raw, serialization.PublicFormat.Raw
        )
        their_sig = sk.sign(msg)
        # Our pubkey matches theirs; our signature matches theirs
        # (Ed25519 signing is deterministic); our verify accepts theirs.
        assert ed.public_key(seed) == their_pub
        assert ed.sign(seed, msg) == their_sig
        assert ed.verify(their_pub, msg, their_sig)


def test_batch_verify_bitmap():
    seeds = [bytes([i]) * 32 for i in range(4)]
    pubs = [ed.public_key(s) for s in seeds]
    msgs = [b"m%d" % i for i in range(4)]
    sigs = [ed.sign(s, m) for s, m in zip(seeds, msgs)]
    sigs[2] = sigs[2][:-1] + bytes([sigs[2][-1] ^ 1])
    assert ed.batch_verify_cpu(pubs, msgs, sigs) == [True, True, False, True]


def test_point_roundtrip():
    p = ed.point_mul(12345, ed.B)
    enc = ed.point_compress(p)
    q = ed.point_decompress(enc)
    assert q is not None
    assert ed.point_equal(p, q)


def test_decompress_invalid():
    # A y-coordinate >= p with no valid x (all-0xff is non-canonical/invalid)
    assert ed.point_decompress(b"\xff" * 32) is None


def test_openssl_verifier_key_cache_is_bounded():
    """The OpenSSL backend's parsed-key cache must not grow without
    bound under an adversarial fresh-key spray (it serves as the
    TpuVerifier's over-bank-cap fallback, which sees exactly that
    traffic shape): it stops inserting at MAX_KEYS, the keys cached
    before the cap stay, and verdicts stay correct for keys that never
    got in."""
    pytest.importorskip("cryptography")
    from simple_pbft_tpu.crypto.verifier import BatchItem, OpenSSLVerifier

    v = OpenSSLVerifier()
    v.MAX_KEYS = 8  # shrink the bound for the test
    items = []
    for i in range(20):
        seed = bytes([i]) * 32
        msg = b"spray %d" % i
        items.append(BatchItem(ed.public_key(seed), msg, ed.sign(seed, msg)))
    bad = BatchItem(items[0].pubkey, b"other", items[0].sig)
    out = v.verify_batch(items + [bad])
    assert out == [True] * 20 + [False]
    assert len(v._cache) == 8
    # the first eight keys got in before the cap and stay resident
    assert set(v._cache) == {it.pubkey for it in items[:8]}
    # a key that never got in still verifies (parsed per batch, never
    # cached), and a forgery under it is still rejected
    assert items[15].pubkey not in v._cache
    forged = BatchItem(items[15].pubkey, b"other", items[15].sig)
    assert v.verify_batch([items[15], forged]) == [True, False]
    assert set(v._cache) == {it.pubkey for it in items[:8]}
