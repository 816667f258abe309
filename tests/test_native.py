"""Native host-prep library (simple_pbft_tpu/native) vs Python oracles.

The C++ SHA-512 and sc_reduce must agree with hashlib / the pure-Python
RFC 8032 implementation on every input shape that matters: empty
messages, single-block, exact padding boundaries (111/112/128 bytes),
multi-block, and large buffers. If the toolchain is unavailable the
library falls back to Python — these tests then exercise the fallback.
"""

import hashlib

import numpy as np
import pytest

from simple_pbft_tpu import native
from simple_pbft_tpu.crypto import ed25519_cpu as ref

# message lengths crossing all SHA-512 padding boundaries for the
# 64-byte (R||A) prefix: total = 64 + n, block = 128, len-field at 112
EDGE_LENS = [0, 1, 47, 48, 49, 63, 64, 65, 111, 112, 127, 128, 129, 1000, 5000]


def test_sha512_batch_matches_hashlib():
    rng = np.random.default_rng(7)
    msgs = [rng.integers(0, 256, n, dtype=np.uint8).tobytes() for n in EDGE_LENS]
    got = native.sha512_batch(msgs)
    for i, m in enumerate(msgs):
        assert got[i].tobytes() == hashlib.sha512(m).digest(), f"len {len(m)}"


def test_challenge_batch_matches_oracle():
    rng = np.random.default_rng(8)
    n = len(EDGE_LENS)
    r = rng.integers(0, 256, (n, 32), dtype=np.uint8)
    a = rng.integers(0, 256, (n, 32), dtype=np.uint8)
    msgs = [rng.integers(0, 256, ln, dtype=np.uint8).tobytes() for ln in EDGE_LENS]
    got = native.challenge_batch(r, a, msgs)
    for i in range(n):
        want = ref.challenge_scalar(r[i].tobytes(), a[i].tobytes(), msgs[i])
        assert got[i].tobytes() == want.to_bytes(32, "little"), f"row {i}"


def test_challenge_batch_random_bulk():
    rng = np.random.default_rng(9)
    n = 256
    r = rng.integers(0, 256, (n, 32), dtype=np.uint8)
    a = rng.integers(0, 256, (n, 32), dtype=np.uint8)
    msgs = [b"x" * int(i % 7) for i in range(n)]
    got = native.challenge_batch(r, a, msgs)
    for i in range(n):
        want = ref.challenge_scalar(r[i].tobytes(), a[i].tobytes(), msgs[i])
        assert int.from_bytes(got[i].tobytes(), "little") == want


def test_sc_reduce_boundary_values():
    """The signed-fold reduction's edge cases, driven directly: zero, the
    sign-flip magnitudes, values straddling L, 2^252, 2^253 and the
    512-bit top — each compared against Python bigint mod."""
    L = ref.L
    cases = [
        0, 1, 2, L - 1, L, L + 1, 2 * L, 2 * L - 1,
        2**252 - 1, 2**252, 2**252 + 1, 2**253 - 1, 2**253, 2**253 + 1,
        (2**512 - 1) // L * L,          # largest multiple of L in range
        (2**512 - 1) // L * L - 1,
        2**512 - 1, 2**511, 2**256 - 1, 2**256, 2**384 - 1,
        17 * L + 5, (2**260) * L % (2**512),
    ]
    rng = np.random.default_rng(11)
    cases += [int(rng.integers(0, 2**63)) * L for _ in range(8)]  # exact multiples
    digests = np.stack(
        [np.frombuffer(v.to_bytes(64, "little"), np.uint8) for v in cases]
    )
    got = native.sc_reduce_batch(digests)
    for i, v in enumerate(cases):
        assert int.from_bytes(got[i].tobytes(), "little") == v % L, f"case {i}: {v}"


def test_empty_batch():
    assert native.challenge_batch(
        np.zeros((0, 32), np.uint8), np.zeros((0, 32), np.uint8), []
    ).shape == (0, 32)
    assert native.sha512_batch([]).shape == (0, 64)


@pytest.mark.parametrize("hold_lock", [True, False], ids=["held", "released"])
def test_prepare_wire_matches_oracle(hold_lock):
    """The verifier's staging in one call, on random rows over every
    SHA-512 padding edge: S || k || R with the oracle's k, the reject
    policy as Python bigints state it, the padding zeroed."""
    if not native.available():
        pytest.skip("no native host-prep library on this machine")
    rng = np.random.default_rng(12)
    n, size = len(EDGE_LENS), 32
    pub = rng.integers(0, 256, 32 * n, dtype=np.uint8).tobytes()
    sig = bytearray(rng.integers(0, 256, 64 * n, dtype=np.uint8).tobytes())
    for i in range(0, n, 2):
        sig[64 * i + 63] &= 0x0F  # every other S below L
    msgs = [rng.integers(0, 256, ln, dtype=np.uint8).tobytes() for ln in EDGE_LENS]
    ok = bytearray(b"\x01") * n
    ok[4] = 0
    wire, precheck = native.prepare_wire(pub, bytes(sig), msgs, ok, size, hold_lock)
    assert wire.shape == (size, 96) and wire.dtype == np.uint8
    assert precheck.shape == (size,) and precheck.dtype == np.bool_
    for i in range(n):
        r, s = bytes(sig[64 * i : 64 * i + 32]), bytes(sig[64 * i + 32 : 64 * i + 64])
        k = ref.challenge_scalar(r, pub[32 * i : 32 * i + 32], msgs[i])
        assert wire[i].tobytes() == s + k.to_bytes(32, "little") + r, f"row {i}"
        y = int.from_bytes(r, "little") & ((1 << 255) - 1)
        want = ok[i] == 1 and int.from_bytes(s, "little") < ref.L and y < ref.P
        assert bool(precheck[i]) == want, f"row {i}"
    assert precheck[:n].sum() >= 6  # both verdicts occur
    assert not wire[n:].any() and not precheck[n:].any()


@pytest.mark.parametrize("n_rows, size", [(1, 8), (5, 8), (600, 2048)])
def test_ladder_rows_copies_rows_with_their_keys_and_masks_the_pile(n_rows, size):
    """native.ladder_rows on a random staged pile: out row i is the pile's
    row rows[i] with its key behind it, its precheck goes along and is
    cleared in the pile, untouched rows stay, the padding is zero."""
    if not native.available():
        pytest.skip("no native host-prep library on this machine")
    rng = np.random.default_rng(36)
    pile = 3 * n_rows + 1
    wire = rng.integers(0, 256, (pile, 96), dtype=np.uint8)
    pub = rng.integers(0, 256, 32 * pile, dtype=np.uint8).tobytes()
    precheck = rng.integers(0, 2, pile).astype(np.bool_)
    before = precheck.copy()
    rows = sorted(rng.choice(pile, n_rows, replace=False).tolist())
    idx, out, out_pre = native.ladder_rows(wire, pub, precheck, rows, size)
    assert idx.dtype == np.int64 and idx.tolist() == rows
    assert out.shape == (size, 128) and out.dtype == np.uint8
    assert out_pre.shape == (size,) and out_pre.dtype == np.bool_
    assert np.array_equal(out[:n_rows, :96], wire[rows])
    keys = np.frombuffer(pub, np.uint8).reshape(pile, 32)
    assert np.array_equal(out[:n_rows, 96:], keys[rows])
    assert np.array_equal(out_pre[:n_rows], before[rows])
    assert not out[n_rows:].any() and not out_pre[n_rows:].any()
    assert not precheck[rows].any()
    others = np.setdiff1d(np.arange(pile), rows)
    assert np.array_equal(precheck[others], before[others])


def test_prepare_wire_empty_pile():
    if not native.available():
        pytest.skip("no native host-prep library on this machine")
    wire, precheck = native.prepare_wire(b"", b"", [], bytearray(), 8, True)
    assert wire.shape == (8, 96) and not wire.any() and not precheck.any()
