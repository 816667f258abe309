"""Dedicated oracle-parity suite for the native batch Ed25519 verifier
(native/ed25519.cpp via crypto.verifier.NativeEdVerifier) — the default
CPU backend on hosts with a toolchain, so it gets the same adversarial
coverage as the TPU backend (tests/test_tpu_verifier.py), not just
implicit exercise through best_cpu_verifier().

Semantics note: the native backend mirrors the TPU kernel (ops/comb.py):
P = [S]B + [k](-A) must byte-compare to the wire R. For every signature
an honest signer can produce — and every corruption of one — this agrees
with the RFC 8032 oracle; the tests below pin that agreement.
"""

import random

import pytest

from simple_pbft_tpu.crypto import ed25519_cpu as ref
from simple_pbft_tpu.crypto.verifier import BatchItem, CpuVerifier

try:
    from simple_pbft_tpu.crypto.verifier import NativeEdVerifier

    _native = NativeEdVerifier()
except ImportError:  # pragma: no cover - toolchain-less host
    _native = None

pytestmark = pytest.mark.skipif(
    _native is None, reason="native ed25519 library unavailable"
)


def _sig_items(n=16, distinct_keys=4, seed=1234):
    rng = random.Random(seed)
    items = []
    for i in range(n):
        sd = bytes([i % distinct_keys + 1]) * 32
        msg = bytes(rng.randbytes(rng.randrange(0, 150)))
        items.append(BatchItem(ref.public_key(sd), msg, ref.sign(sd, msg)))
    return items


def test_valid_batch_all_true():
    items = _sig_items(32)
    assert _native.verify_batch(items) == [True] * 32


def test_corruption_classes_match_oracle():
    rng = random.Random(9)
    base = _sig_items(8)
    items = list(base)
    for it in base:
        bad_sig = bytearray(it.sig)
        bad_sig[rng.randrange(64)] ^= 1 << rng.randrange(8)
        items.append(BatchItem(it.pubkey, it.msg, bytes(bad_sig)))
        items.append(BatchItem(it.pubkey, it.msg + b"!", it.sig))
        items.append(BatchItem(ref.public_key(b"\x77" * 32), it.msg, it.sig))
        items.append(BatchItem(it.pubkey[:-1], it.msg, it.sig))  # short key
        items.append(BatchItem(it.pubkey, it.msg, it.sig[:-1]))  # short sig
        items.append(BatchItem(b"\xff" * 32, it.msg, it.sig))  # off-curve
        # malleable S' = S + L: the oracle and the native path both reject
        s_int = int.from_bytes(it.sig[32:], "little") + ref.L
        items.append(
            BatchItem(it.pubkey, it.msg, it.sig[:32] + s_int.to_bytes(32, "little"))
        )
    got = _native.verify_batch(items)
    oracle = CpuVerifier().verify_batch(items)
    assert got == oracle
    assert got[: len(base)] == [True] * len(base)
    assert not any(got[len(base) :])


def test_boundary_scalars_and_wnaf_carry_edges():
    """Signatures whose S/k hit w-NAF carry chains: long runs of 1-bits
    arise from messages hashed to extreme challenge scalars — approximate
    by verifying many random messages per key so the 251+ bit patterns
    vary; parity with the oracle is the invariant."""
    rng = random.Random(31337)
    items = []
    for i in range(96):
        sd = bytes([i % 3 + 9]) * 32
        msg = bytes(rng.randbytes(64))
        items.append(BatchItem(ref.public_key(sd), msg, ref.sign(sd, msg)))
    assert _native.verify_batch(items) == [True] * 96


def test_mixed_validity_bitmap_positions():
    items = _sig_items(12)
    bad = bytearray(items[5].sig)
    bad[3] ^= 0x10
    items[5] = BatchItem(items[5].pubkey, items[5].msg, bytes(bad))
    items[9] = BatchItem(items[9].pubkey, b"swapped", items[9].sig)
    got = _native.verify_batch(items)
    assert got == [i not in (5, 9) for i in range(12)]


def test_empty_and_single():
    assert _native.verify_batch([]) == []
    it = _sig_items(1)[0]
    assert _native.verify_batch([it]) == [True]


@pytest.mark.parametrize("key", [44, 45, 46])
def test_native_fused_table_bit_exact(key):
    """The C++ fused-table build must produce byte-identical rows to the
    exact-bigint Python path — the KeyBank swaps between them
    transparently."""
    import numpy as np

    from simple_pbft_tpu import native
    from simple_pbft_tpu.ops import comb

    pt = ref.point_decompress(ref.public_key(bytes([key]) * 32))
    nat = comb.fused_table_np(pt)
    orig = native.ed25519_fused_table
    native.ed25519_fused_table = lambda *a: None  # force the Python path
    try:
        py = comb.fused_table_np(pt)
    finally:
        native.ed25519_fused_table = orig
    assert np.array_equal(nat, py)


def test_key_cache_remap_across_calls():
    """Key bank grows across calls; later batches referencing a subset of
    cached keys must remap indices correctly."""
    a = _sig_items(8, distinct_keys=8, seed=5)
    assert _native.verify_batch(a) == [True] * 8
    # a batch touching only keys 6,7 (bank indices high) + one new key
    sub = [a[6], a[7]]
    sd = bytes([42]) * 32
    sub.append(BatchItem(ref.public_key(sd), b"new", ref.sign(sd, b"new")))
    assert _native.verify_batch(sub) == [True, True, True]
