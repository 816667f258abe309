"""TPU (JAX) batched verifier vs the pure-Python RFC 8032 oracle.

Covers SURVEY.md §4's crypto-plane test strategy: RFC 8032 known-answer
vectors, adversarial inputs (corrupted bits, non-canonical encodings,
wrong lengths), per-position verdict bitmaps under batching, the
benchmark's planted failures by name, and the meshed kernel on the
virtual 8-device mesh.
"""

import numpy as np
import pytest

from simple_pbft_tpu.crypto import ed25519_cpu as ref
from simple_pbft_tpu.crypto.verifier import BatchItem
from simple_pbft_tpu.crypto.tpu_verifier import TpuVerifier

# RFC 8032 §7.1 test vectors 1-3 (seed, pubkey, msg, sig)
RFC8032_VECTORS = [
    (
        "9d61b19deffd5a60ba844af492ec2cc44449c5697b326919703bac031cae7f60",
        "d75a980182b10ab7d54bfed3c964073a0ee172f3daa62325af021a68f707511a",
        "",
        "e5564300c360ac729086e2cc806e828a84877f1eb8e5d974d873e06522490155"
        "5fb8821590a33bacc61e39701cf9b46bd25bf5f0595bbe24655141438e7a100b",
    ),
    (
        "4ccd089b28ff96da9db6c346ec114e0f5b8a319f35aba624da8cf6ed4fb8a6fb",
        "3d4017c3e843895a92b70aa74d1b7ebc9c982ccf2ec4968cc0cd55f12af4660c",
        "72",
        "92a009a9f0d4cab8720e820b5f642540a2b27b5416503f8fb3762223ebdb69da"
        "085ac1e43e15996e458f3613d0f11d8c387b2eaeb4302aeeb00d291612bb0c00",
    ),
    (
        "c5aa8df43f9f837bedb7442f31dcb7b166d38535076f094b85ce3a2e0b4458f7",
        "fc51cd8e6218a1a38da47ed00230f0580816ed13ba3303ac5deb911548908025",
        "af82",
        "6291d657deec24024827e69c3abe01a30ce548a284743a445e3680d7db5ac3ac"
        "18ff9b538d16f290ae67f760984dc6594a7c15e9716ed28dc027beceea1ec40a",
    ),
]


@pytest.fixture(scope="module")
def verifier():
    return TpuVerifier()


@pytest.fixture(scope="module")
def meshed_verifier():
    import jax
    from jax.sharding import Mesh

    return TpuVerifier(mesh=Mesh(np.asarray(jax.devices()[:8]), ("dp",)))


def _signed(i: int, msg: bytes):
    seed = bytes([i]) * 32
    return BatchItem(ref.public_key(seed), msg, ref.sign(seed, msg))


def test_rfc8032_vectors(verifier):
    items = [
        BatchItem(bytes.fromhex(pk), bytes.fromhex(msg), bytes.fromhex(sig))
        for _, pk, msg, sig in RFC8032_VECTORS
    ]
    assert verifier.verify_batch(items) == [True] * len(items)


def test_bitmap_positions_and_adversarial(verifier):
    """One mixed batch: verdict positions must map 1:1 to items, agreeing
    with the CPU oracle on every adversarial case."""
    good = [_signed(i, b"vote %d" % i) for i in range(4)]
    bad_sig = bytearray(good[0].sig)
    bad_sig[1] ^= 0x40
    noncanon_s = good[2].sig[:32] + (
        (int.from_bytes(good[2].sig[32:], "little") + ref.L).to_bytes(32, "little")
    )
    items = [
        good[0],
        BatchItem(good[0].pubkey, good[0].msg, bytes(bad_sig)),  # flipped bit
        good[1],
        BatchItem(good[1].pubkey, b"forged", good[1].sig),  # wrong msg
        BatchItem(good[2].pubkey, good[2].msg, noncanon_s),  # S >= L
        BatchItem(good[3].pubkey[:16], good[3].msg, good[3].sig),  # bad len
        BatchItem(b"\xff" * 32, good[3].msg, good[3].sig),  # y >= p
        good[3],
    ]
    got = verifier.verify_batch(items)
    oracle = [ref.verify(i.pubkey, i.msg, i.sig) for i in items]
    assert got == oracle == [True, False, True, False, False, False, False, True]


def test_swapped_keys_rejected(verifier):
    a, b = _signed(1, b"m1"), _signed(2, b"m2")
    items = [BatchItem(b.pubkey, a.msg, a.sig), BatchItem(a.pubkey, b.msg, b.sig)]
    assert verifier.verify_batch(items) == [False, False]


def test_bucket_padding_indifferent(verifier):
    """Verdicts must not depend on padding rows (batch of 3 -> bucket 8)."""
    items = [_signed(i, b"pad %d" % i) for i in range(3)]
    assert verifier.verify_batch(items) == [True, True, True]


def test_empty_batch(verifier):
    assert verifier.verify_batch([]) == []


def test_windows_major_extraction():
    """The device-side window extraction the kernel runs on raw wire
    bytes must reassemble to the scalar and agree with its numpy twin,
    at both widths the kernel uses: 4-bit scalar windows and R's 15-bit
    limbs."""
    import jax

    from simple_pbft_tpu.ops import comb
    from simple_pbft_tpu.ops import field25519 as fe

    rng = np.random.default_rng(11)
    data = rng.integers(0, 256, (16, 32), dtype=np.uint8)
    data[0, :] = 0xFF
    for w, count in ((comb.WBITS, comb.NPOS), (fe.RADIX, fe.NLIMB)):
        out = np.asarray(
            jax.jit(fe.extract_windows_dev, static_argnums=(1, 2))(data, w, count)
        )
        assert out.shape == (count, 16)
        assert (out < (1 << w)).all() and (out >= 0).all()
        assert (out == fe.extract_windows_np(data, w, count)).all()
        for j in range(16):
            v = sum(int(out[i, j]) << (w * i) for i in range(count))
            want = int.from_bytes(bytes(data[j]), "little")
            assert v == want & ((1 << (w * count)) - 1)


def test_initial_keys_pins_table_shape_and_warm_is_inert():
    """TpuVerifier(initial_keys=...) must fix the bank capacity so live
    traffic never grows it (a growth means a fresh kernel compile under
    the device lock — the bug that zeroed every consensus-on-chip run),
    and warm() must not register its dummy row into the bank."""
    v = TpuVerifier(initial_keys=20)
    assert v._bank._cap == 32  # next power of two
    v.warm(buckets=[8])
    assert len(v._bank._index) == 0  # dummy never registered
    items = [_signed(i, b"pin %d" % i) for i in range(6)]
    assert v.verify_batch(items) == [True] * 6
    assert v._bank._cap == 32  # capacity untouched by traffic


def test_keybank_cap_takes_the_ladder():
    """Keys beyond the bank cap must still verify correctly (on the
    device, by the table-free program), and the bank must not grow past
    max_keys."""
    from simple_pbft_tpu.crypto.tpu_verifier import KeyBank

    v = TpuVerifier()
    v._bank = KeyBank(initial_capacity=2, max_keys=2)
    items = [_signed(i, b"cap %d" % i) for i in range(4)]  # 4 distinct keys
    bad = bytearray(items[3].sig)
    bad[2] ^= 4
    items.append(BatchItem(items[3].pubkey, items[3].msg, bytes(bad)))
    assert v.verify_batch(items) == [True, True, True, True, False]
    assert len(v._bank._index) == 2
    assert (v.ladder_items, v.ladder_passes) == (3, 1)
    assert v.overcap_fallback_items == 0 and v._cpu_fb is None


def test_overbank_ladder_agrees_with_kernel():
    """The program for keys over the bank's cap must be
    KERNEL-EQUIVALENT (ADVICE r5): the same batch split between comb
    rows and ladder rows shares one verdict bitmap, so the two programs
    must agree on every known edge vector — non-canonical S (>= L),
    y >= p key encodings, wrong lengths, tampered bits — or a crafted
    signature could verify on one replica's split and not another's.
    Pins the agreement, that the ladder ran, and the one case that keeps
    the CPU route and its CLASS (native/oracle, never OpenSSL)."""
    from simple_pbft_tpu.crypto.tpu_verifier import KeyBank
    from simple_pbft_tpu.crypto.verifier import (
        CpuVerifier,
        NativeEdVerifier,
        kernel_equivalent_cpu_verifier,
    )

    good = [_signed(50 + i, b"edge %d" % i) for i in range(3)]
    flipped = bytearray(good[0].sig)
    flipped[1] ^= 0x40
    noncanon_s = good[1].sig[:32] + (
        (int.from_bytes(good[1].sig[32:], "little") + ref.L).to_bytes(
            32, "little"
        )
    )
    edge_items = [
        good[0],
        BatchItem(good[0].pubkey, good[0].msg, bytes(flipped)),
        good[1],
        BatchItem(good[1].pubkey, b"forged", good[1].sig),
        BatchItem(good[1].pubkey, good[1].msg, noncanon_s),  # S >= L
        BatchItem(good[2].pubkey[:16], good[2].msg, good[2].sig),  # bad len
        BatchItem(b"\xff" * 32, good[2].msg, good[2].sig),  # y >= p
        good[2],
    ]
    oracle = [ref.verify(i.pubkey, i.msg, i.sig) for i in edge_items]
    # kernel verdicts: roomy bank, every key resident
    kernel = TpuVerifier().verify_batch(edge_items)
    assert kernel == oracle
    # ladder verdicts: bank capacity 1, pre-occupied by an unrelated
    # key, so EVERY well-formed edge item routes to the table-free program
    v = TpuVerifier()
    v._bank = KeyBank(initial_capacity=1, max_keys=1)
    occupier = _signed(99, b"occupier")
    assert v.verify_batch([occupier]) == [True]
    assert len(v._bank._index) == 1
    got = v.verify_batch(edge_items)
    assert got == kernel == oracle
    assert len(v._bank._index) == 1  # nothing evicted/registered
    # the ladder ran: every row but the wrong-length one, and no CPU
    assert (v.ladder_items, v.ladder_passes) == (len(edge_items) - 1, 1)
    assert v.overcap_fallback_items == 0 and v._cpu_fb is None
    # a verifier whose warm closed the shape set WITHOUT the ladder (its
    # published population fit the bank) compiles nothing under traffic:
    # a walk-in key past the cap keeps the CPU route, a kernel-equivalent
    # class, with the same verdicts
    v._warm_done = True
    v.shape_signatures = {g for g in v.shape_signatures if g[0] != "ladder"}
    assert v.verify_batch(edge_items) == oracle
    assert v.post_warm_compiles == 0
    assert v.overcap_fallback_items == len(edge_items) - 1
    assert (v.ladder_items, v.ladder_passes) == (len(edge_items) - 1, 1)
    assert isinstance(v._cpu_fb, (NativeEdVerifier, CpuVerifier))
    assert type(kernel_equivalent_cpu_verifier()) is type(v._cpu_fb)


def test_meshed_tpu_verifier_fused(meshed_verifier):
    """TpuVerifier(mesh=...): the shard_map form of the kernel must agree
    with the oracle over the 8-device mesh."""
    items = [_signed(i % 4, b"meshed %d" % i) for i in range(12)]
    forged = BatchItem(items[0].pubkey, b"not the msg", items[0].sig)
    items.append(forged)
    oracle = [ref.verify(i.pubkey, i.msg, i.sig) for i in items]
    assert meshed_verifier.verify_batch(items) == oracle == [True] * 12 + [False]


def test_meshed_ladder_agrees_with_the_oracle():
    """The table-free program under shard_map: a meshed verifier whose
    bank is full sends uncached rows down the ladder, one row a device
    here, and the verdicts are the oracle's."""
    import jax
    from jax.sharding import Mesh

    from simple_pbft_tpu.crypto.tpu_verifier import KeyBank

    v = TpuVerifier(mesh=Mesh(np.asarray(jax.devices()[:8]), ("dp",)))
    v._bank = KeyBank(initial_capacity=1, max_keys=1)
    assert v.verify_batch([_signed(99, b"occupier")]) == [True]
    items = [_signed(60 + i % 3, b"meshed ladder %d" % i) for i in range(6)]
    items[4] = BatchItem(items[4].pubkey, b"not the msg", items[4].sig)
    items[5] = BatchItem(b"\xff" * 32, items[5].msg, items[5].sig)
    oracle = [ref.verify(i.pubkey, i.msg, i.sig) for i in items]
    assert v.verify_batch(items) == oracle == [True] * 4 + [False] * 2
    assert (v.ladder_items, v.ladder_passes) == (6, 1)
    assert v.overcap_fallback_items == 0


def _not_a_point() -> bytes:
    for last in range(256):
        cand = bytes([7] * 31 + [last & 0x7F])
        if ref.point_decompress(cand) is None:
            return cand
    raise AssertionError("no off-curve candidate found")


def _plant_flipped(it):
    sig = bytearray(it.sig)
    sig[37] ^= 0x10
    return BatchItem(it.pubkey, it.msg, bytes(sig))


def _plant_other_key(it):
    other = bytes([2]) * 32  # _signed(2, ...)'s seed: in the batch, not item 9's
    assert ref.public_key(other) != it.pubkey
    return BatchItem(it.pubkey, it.msg, ref.sign(other, it.msg))


def _plant_s_ge_l(it):
    s_big = int.from_bytes(it.sig[32:], "little") + ref.L
    return BatchItem(it.pubkey, it.msg, it.sig[:32] + s_big.to_bytes(32, "little"))


def _plant_noncanonical_r(it):
    return BatchItem(
        it.pubkey, it.msg, (ref.P + 1).to_bytes(32, "little") + it.sig[32:]
    )


# the seven failures benchmark/stages.py: kernel_stage plants, by its names
PLANTED = {
    "flipped signature byte": _plant_flipped,
    "signed by another committee key": _plant_other_key,
    "S >= L": _plant_s_ge_l,
    "non-canonical R.y": _plant_noncanonical_r,
    "wrong-length key": lambda it: BatchItem(it.pubkey[:31], it.msg, it.sig),
    "wrong-length signature": lambda it: BatchItem(it.pubkey, it.msg, it.sig[:63]),
    "key not a curve point": lambda it: BatchItem(_not_a_point(), it.msg, it.sig),
}


@pytest.mark.parametrize("which", ["unmeshed", "meshed"])
@pytest.mark.parametrize("kind", list(PLANTED))
def test_planted_failure_agrees_with_oracle(kind, which, verifier, meshed_verifier):
    """Each failure the benchmark plants, inside a batch of good
    signatures: the device's verdicts equal the RFC 8032 oracle's item
    for item, and the one False sits at the planted position."""
    v = verifier if which == "unmeshed" else meshed_verifier
    items = [_signed(i % 4, b"planted %d" % (i % 4)) for i in range(13)]
    pos = 9
    items[pos] = PLANTED[kind](items[pos])
    oracle = [ref.verify(i.pubkey, i.msg, i.sig) for i in items]
    assert oracle == [i != pos for i in range(13)]
    assert v.verify_batch(items) == oracle


def test_pallas_accumulate_matches_xla():
    """The Pallas madd-loop kernel (interpret mode on CPU) must agree
    bit-for-bit with the XLA fori_loop path on the same batch."""
    from simple_pbft_tpu.ops import comb
    from simple_pbft_tpu.crypto.tpu_verifier import KeyBank, prepare_wire_batch

    items = [_signed(i % 3, b"pallas %d" % i) for i in range(8)]
    broken = bytearray(items[5].sig)
    broken[9] ^= 2
    items[5] = BatchItem(items[5].pubkey, items[5].msg, bytes(broken))

    bank = KeyBank()
    prep = prepare_wire_batch(items, bank, 8)
    args = (prep.wire, prep.a_idx, bank.device_tables(), prep.precheck)
    try:
        comb.use_accum_impl("xla")
        want = np.asarray(comb.fused_verify_wire_kernel(*args))
        comb.use_accum_impl("pallas_interpret")
        got = np.asarray(comb.fused_verify_wire_kernel(*args))
    finally:
        comb.use_accum_impl("auto")  # restore the shipped default
    assert want.tolist() == [True] * 5 + [False] + [True] * 2
    assert got.tolist() == want.tolist()


def test_pallas_interpret_verifier_matches_oracle_and_xla():
    """The whole verifier with the Pallas accumulator (interpret mode
    here; Mosaic on the chip) must be bit-exact against both the RFC 8032
    oracle and the XLA accumulator, including invalid rows."""
    from simple_pbft_tpu.ops import comb

    good = [_signed(40 + i, b"pack %d" % i) for i in range(5)]
    bad_sig = bytearray(good[1].sig)
    bad_sig[7] ^= 1
    items = good + [
        BatchItem(good[0].pubkey, b"wrong msg", good[0].sig),
        BatchItem(good[1].pubkey, good[1].msg, bytes(bad_sig)),
    ]
    oracle = [ref.verify(i.pubkey, i.msg, i.sig) for i in items]
    assert oracle == [True] * 5 + [False, False]
    xla = TpuVerifier().verify_batch(items)
    comb.use_accum_impl("pallas_interpret")
    try:
        # the shared jit traced with the XLA accumulator above; a jit of
        # its own captures the Pallas one
        import jax

        v = TpuVerifier()
        v._fn = jax.jit(comb.fused_verify_wire_kernel)
        pal = v.verify_batch(items)
    finally:
        comb.use_accum_impl("auto")
    assert pal == xla == oracle


def test_shape_stability_hook_post_warm(monkeypatch):
    """Shape-stable coalescing (ISSUE 3): warm_for_population closes the
    jit-signature set — after warmup, NO dispatch may hit a fresh shape
    (post_warm_compiles stays 0 across every reachable batch size), and
    a verifier warmed short of a reachable bucket is caught by the hook."""
    from simple_pbft_tpu.crypto import tpu_verifier as tv

    monkeypatch.setattr(tv, "BUCKETS", (8, 32))
    pubs = [ref.public_key(bytes([40 + i]) * 32) for i in range(4)]
    items = [_signed(40 + (i % 4), b"shape probe %d" % i) for i in range(40)]

    v = tv.TpuVerifier(initial_keys=8)
    v.warm_for_population(pubs, max_sweep=32)
    snap = v.shape_snapshot()
    assert snap["warmed"] is True and snap["post_warm_compiles"] == 0
    base = v.shape_compiles
    for n in (1, 5, 8, 20, 32, 40):  # 40 chunks to 32+8: no new shape
        assert v.verify_batch(items[:n]) == [True] * n
    assert v.shape_compiles == base
    assert v.post_warm_compiles == 0
    hits = v.shape_snapshot()["bucket_hits"]
    assert set(hits) == {"8", "32"}

    # under-warmed verifier: the 32 bucket was never compiled pre-warm,
    # so the first big sweep is a mid-run compile — counted and visible
    v2 = tv.TpuVerifier(initial_keys=8)
    v2.warm_for_population(pubs, max_sweep=8)
    assert v2.post_warm_compiles == 0
    assert v2.verify_batch(items[:20]) == [True] * 20  # pads to 32
    assert v2.post_warm_compiles == 1


# ---------------------------------------------------------------------------
# host staging: native.prepare_wire against the numpy staging (ISSUE 33)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def pool():
    """32 signed items over 4 keys, message lengths 0..31: what the piles
    below are tiled from (signing is the slow part)."""
    return [_signed(1 + i % 4, b"m" * i) for i in range(32)]


@pytest.fixture
def native_lib():
    from simple_pbft_tpu import native

    if not native.available():
        pytest.skip("no native host-prep library on this machine")
    return native


def _stage_both(items, monkeypatch, bank=None):
    """(native staging, numpy staging) of one pile padded to its bucket,
    each on a bank of its own built the same way."""
    from simple_pbft_tpu.crypto import tpu_verifier as tv

    bank = bank or tv.KeyBank
    size = tv._bucket_size(len(items))
    got = tv.prepare_wire_batch(items, bank(), size)
    with monkeypatch.context() as m:
        m.setattr(tv.native, "prepare_wire", lambda *a, **kw: None)
        m.setattr(tv.native, "ladder_rows", lambda *a, **kw: None)
        want = tv.prepare_wire_batch(items, bank(), size)
    assert got.native and not want.native
    return got, want


def _assert_same_staging(got, want, size):
    for field, dtype, shape in (
        ("wire", np.uint8, (size, 96)),
        ("a_idx", np.int32, (size,)),
        ("precheck", np.bool_, (size,)),
    ):
        g, w = getattr(got, field), getattr(want, field)
        assert g.dtype == w.dtype == dtype and g.shape == w.shape == shape, field
        assert g.flags.c_contiguous, field
        assert g.tobytes() == w.tobytes(), field
    assert (got.ladder is None) == (want.ladder is None)
    if got.ladder is not None:
        for g, w in zip(got.ladder, want.ladder):
            assert g.dtype == w.dtype and g.tobytes() == w.tobytes()


def _with_s(it, s: int):
    return BatchItem(it.pubkey, it.msg, it.sig[:32] + s.to_bytes(32, "little"))


def _with_r(it, y: int, sign: int):
    return BatchItem(
        it.pubkey, it.msg, (y | sign << 255).to_bytes(32, "little") + it.sig[32:]
    )


@pytest.mark.parametrize("n", [1, 7, 128, 129, 512, 513, 2048])
def test_native_staging_equals_numpy_by_pile_size(n, pool, native_lib, monkeypatch):
    """Byte for byte, padded to the pile's bucket, on both sides of every
    bucket edge and of LOCK_HELD_BUCKET; rejected rows inside the pile."""
    from simple_pbft_tpu.crypto import tpu_verifier as tv

    items = [pool[i % 32] for i in range(n)]
    if n >= 7:
        items[3] = BatchItem(items[3].pubkey[:31], items[3].msg, items[3].sig)
        items[5] = _with_s(items[5], ref.L)
        items[n - 1] = _with_r(items[n - 1], ref.P, 1)
    size = tv._bucket_size(n)
    got, want = _stage_both(items, monkeypatch)
    _assert_same_staging(got, want, size)
    reject = {3, 5, n - 1} if n >= 7 else set()
    assert got.precheck.tolist() == [
        i < n and i not in reject for i in range(size)
    ]
    assert not got.wire[n:].any() and not got.a_idx[n:].any()


@pytest.mark.parametrize("mlen", [0, 1, 111, 112, 127, 128, 239, 240, 1000])
def test_native_staging_equals_numpy_by_message_length(
    mlen, pool, native_lib, monkeypatch
):
    """SHA-512's padding edges (R || A adds 64 bytes to every message):
    the k column equals the oracle's challenge scalar as well."""
    msgs = [bytes([i]) * mlen for i in range(3)] + [b"", b"q" * 300]
    items = [_signed(1 + i % 4, m) for i, m in enumerate(msgs)]
    got, want = _stage_both(items, monkeypatch)
    _assert_same_staging(got, want, 8)
    for row, it in zip(got.wire, items):
        k = ref.challenge_scalar(it.sig[:32], it.pubkey, it.msg)
        assert row[32:64].tobytes() == k.to_bytes(32, "little")
        assert row[:32].tobytes() == it.sig[32:]
        assert row[64:].tobytes() == it.sig[:32]
    assert got.precheck.tolist() == [True] * 5 + [False] * 3


@pytest.mark.parametrize(
    "s, ok",
    [(ref.L - 1, True), (ref.L, False), (ref.L + 1, False), (2**256 - 1, False)],
    ids=["L-1", "L", "L+1", "2^256-1"],
)
def test_native_staging_rejects_s_ge_l(s, ok, pool, native_lib, monkeypatch):
    items = [pool[0], _with_s(pool[1], s), pool[2]]
    got, want = _stage_both(items, monkeypatch)
    _assert_same_staging(got, want, 8)
    assert got.precheck[:3].tolist() == [True, ok, True]


@pytest.mark.parametrize("sign", [0, 1])
@pytest.mark.parametrize(
    "y, ok", [(ref.P - 1, True), (ref.P, False), (ref.P + 1, False)],
    ids=["p-1", "p", "p+1"],
)
def test_native_staging_rejects_noncanonical_r(
    y, ok, sign, pool, native_lib, monkeypatch
):
    """R.y >= p rejects whatever bit 255 (the sign of x) says."""
    items = [_with_r(pool[0], y, sign), pool[1]]
    got, want = _stage_both(items, monkeypatch)
    _assert_same_staging(got, want, 8)
    assert got.precheck[:2].tolist() == [ok, True]


MALFORMED = {
    "31-byte key": lambda it: BatchItem(it.pubkey[:31], it.msg, it.sig),
    "63-byte signature": lambda it: BatchItem(it.pubkey, it.msg, it.sig[:63]),
    "key not a curve point": lambda it: BatchItem(_not_a_point(), it.msg, it.sig),
    "key over the bank's cap": lambda it: _signed(77, it.msg),
}


@pytest.mark.parametrize("kind", list(MALFORMED))
def test_native_staging_masks_malformed_rows(kind, pool, native_lib, monkeypatch):
    """A bank of two keys, both taken by the pile's first rows: the row is
    masked in the comb's pile on both paths, and a well-formed row whose
    key has no table is staged again for the ladder, key bytes behind it.
    A full bank decompresses nothing, so whether such a key is a curve
    point is the device's to say."""
    from simple_pbft_tpu.crypto import tpu_verifier as tv

    items = [pool[0], pool[1], MALFORMED[kind](pool[5]), pool[4]]
    got, want = _stage_both(
        items, monkeypatch,
        bank=lambda: tv.KeyBank(initial_capacity=2, max_keys=2),
    )
    _assert_same_staging(got, want, 8)
    assert got.precheck[:4].tolist() == [True, True, False, True]
    if kind in ("key over the bank's cap", "key not a curve point"):
        lad = got.ladder
        assert lad.rows.tolist() == [2]
        assert lad.wire.shape == (8, 128) and lad.precheck.tolist() == [True] + [False] * 7
        assert lad.wire[0, :96].tobytes() == got.wire[2].tobytes()
        assert lad.wire[0, 96:].tobytes() == items[2].pubkey
        assert not lad.wire[1:].any()
    else:
        assert got.ladder is None
    assert got.a_idx[:4].tolist() == [
        0, 1, 1 if kind == "63-byte signature" else 0, 0,
    ]


@pytest.mark.parametrize("n, size", [(1, 8), (130, 512), (600, 2048)])
def test_lock_held_and_lock_released_calls_give_the_same_bytes(
    n, size, pool, native_lib
):
    from simple_pbft_tpu.crypto.tpu_verifier import KeyBank

    items = [pool[i % 32] for i in range(n)]
    items[0] = _with_s(items[0], ref.L)
    pub, sig, msgs, ok, _a_idx, _fb = KeyBank().lookup_pile(items, size)
    held = native_lib.prepare_wire(pub, sig, msgs, ok, size, hold_lock=True)
    released = native_lib.prepare_wire(pub, sig, msgs, ok, size, hold_lock=False)
    assert held[0].tobytes() == released[0].tobytes()
    assert held[1].tobytes() == released[1].tobytes()
    assert held[1].sum() == n - 1


@pytest.mark.parametrize("staged_by", ["native", "numpy"])
def test_prep_counters_count(staged_by, pool, native_lib, monkeypatch):
    """native_prep_items / fallback_prep_items count the items of finished
    passes by who staged them, and ride shape_snapshot()."""
    from simple_pbft_tpu.crypto import tpu_verifier as tv

    if staged_by == "numpy":
        monkeypatch.setattr(tv.native, "prepare_wire", lambda *a, **kw: None)
    v = TpuVerifier()
    assert v.verify_batch(pool[:5]) == [True] * 5
    finish = v.dispatch_batch(pool[:3])
    counted = (5, 0) if staged_by == "native" else (0, 5)
    snap = v.shape_snapshot()
    assert (snap["native_prep_items"], snap["fallback_prep_items"]) == counted
    assert finish() == [True] * 3
    counted = (8, 0) if staged_by == "native" else (0, 8)
    snap = v.shape_snapshot()
    assert (snap["native_prep_items"], snap["fallback_prep_items"]) == counted


class _CountingLib:
    """A loaded library whose every call is written down."""

    def __init__(self, lib, calls: list, tag: str):
        self._lib, self._calls, self._tag = lib, calls, tag

    def __getattr__(self, name):
        fn = getattr(self._lib, name)

        def call(*args):
            self._calls.append((self._tag, name))
            return fn(*args)

        return call


def test_staging_is_one_native_call_and_no_numpy_loops(
    pool, native_lib, monkeypatch
):
    """The finding, without a clock: a pile of 2,048 makes exactly one
    call into the native library (through the handle that gives the
    interpreter lock up) and none of the numpy steps that each gave it up
    before; a pile of 128 goes through the lock-held handle."""
    from simple_pbft_tpu.crypto import tpu_verifier as tv

    bank = tv.KeyBank()
    for it in pool[:4]:
        bank.lookup(it.pubkey)  # table builds call the other libraries
    calls: list = []

    def counted(name, fn):
        def call(*args, **kw):
            calls.append(("numpy", name))
            return fn(*args, **kw)

        return call

    monkeypatch.setattr(
        native_lib, "_lib", _CountingLib(native_lib._lib, calls, "released"))
    monkeypatch.setattr(
        native_lib, "_lib_held", _CountingLib(native_lib._lib_held, calls, "held"))
    monkeypatch.setattr(tv, "_ge_l_np", counted("_ge_l_np", tv._ge_l_np))
    monkeypatch.setattr(tv, "_ge_p_np", counted("_ge_p_np", tv._ge_p_np))
    monkeypatch.setattr(np, "concatenate", counted("concatenate", np.concatenate))
    monkeypatch.setattr(np, "pad", counted("pad", np.pad))

    big = tv.prepare_wire_batch([pool[i % 32] for i in range(2048)], bank, 2048)
    assert calls == [("released", "prepare_wire")]
    assert big.native and big.wire.shape == (2048, 96)
    del calls[:]
    small = tv.prepare_wire_batch([pool[i % 32] for i in range(128)], bank, 128)
    assert calls == [("held", "prepare_wire")]
    assert small.native and small.wire.shape == (128, 96)
