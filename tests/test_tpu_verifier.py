"""TPU (JAX) batched verifier vs the pure-Python RFC 8032 oracle.

Covers SURVEY.md §4's crypto-plane test strategy: RFC 8032 known-answer
vectors, adversarial inputs (corrupted bits, non-canonical encodings,
wrong lengths), per-position verdict bitmaps under batching, and the
shard_map quorum step on the virtual 8-device mesh.
"""

import numpy as np
import pytest

from simple_pbft_tpu.crypto import ed25519_cpu as ref
from simple_pbft_tpu.crypto.verifier import BatchItem
from simple_pbft_tpu.crypto.tpu_verifier import (
    TpuVerifier,
    prepare_batch,
    verify_kernel,
)

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
    """wbits-bit window extraction must reassemble to the scalar for
    every supported width (the w>4 comb geometries depend on it)."""
    from simple_pbft_tpu.ops import comb

    rng = np.random.default_rng(11)
    data = rng.integers(0, 256, (16, 32), dtype=np.uint8)
    data[0, :] = 0xFF
    for w in (4, 5, 6):
        out = comb.windows_major_np(data, w)
        assert out.shape == (comb.npos_for(w), 16)
        assert (out < (1 << w)).all() and (out >= 0).all()
        for j in range(16):
            v = sum(int(out[i, j]) << (w * i) for i in range(out.shape[0]))
            assert v == int.from_bytes(bytes(data[j]), "little")


def test_fused_window5_matches_oracle():
    """The wide-window comb (fewer positions, bigger tables) must stay
    bit-exact: w=5 TpuVerifier vs the RFC 8032 oracle on a mixed batch."""
    v5 = TpuVerifier(mode="fused", window=5)
    good = [_signed(i, b"w5 %d" % i) for i in range(3)]
    tampered = BatchItem(good[0].pubkey, b"tampered", good[0].sig)
    items = good + [tampered]
    oracle = [ref.verify(i.pubkey, i.msg, i.sig) for i in items]
    assert v5.verify_batch(items) == oracle == [True, True, True, False]


def test_wire_kernel_matches_host_prep():
    """The wire kernel (raw (B, 96) bytes, on-device unpack) must be
    bit-identical to the host-prepped fused kernel for every window
    width — same verdicts on valid, tampered and padding rows."""
    import jax

    from simple_pbft_tpu.crypto.tpu_verifier import (
        KeyBank,
        prepare_comb_batch,
        prepare_wire_batch,
    )
    from simple_pbft_tpu.ops import comb

    good = [_signed(i, b"wire %d" % i) for i in range(5)]
    bad = BatchItem(good[0].pubkey, b"altered", good[0].sig)
    items = good + [bad]
    for w in (4, 5, 6):
        bank = KeyBank(mode="fused", window=w)
        hp, _ = prepare_comb_batch(items, bank)
        hp = hp.padded(8)
        s_nib, k_nib, a_idx, r_y, r_sign, pre = hp.arrays()
        tables = bank.device_tables()
        want = np.asarray(
            jax.jit(comb.fused_verify_kernel, static_argnames=("window",))(
                s_nib, k_nib, a_idx, tables, r_y, r_sign, pre, window=1 << w
            )
        )
        wp, _ = prepare_wire_batch(items, bank)
        wire, wa_idx, wpre = wp.padded(8).arrays()
        got = np.asarray(
            jax.jit(
                comb.fused_verify_wire_kernel, static_argnames=("window",)
            )(wire, wa_idx, tables, wpre, window=1 << w)
        )
        assert (got == want).all(), (w, got, want)
        assert got[: len(items)].tolist() == [True] * 5 + [False]


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


def test_keybank_cap_falls_back_to_cpu():
    """Keys beyond the bank cap must still verify correctly (CPU path),
    and the bank must not grow past max_keys."""
    from simple_pbft_tpu.crypto.tpu_verifier import KeyBank

    v = TpuVerifier()
    v._bank = KeyBank(initial_capacity=2, max_keys=2, mode=v._mode)
    items = [_signed(i, b"cap %d" % i) for i in range(4)]  # 4 distinct keys
    bad = bytearray(items[3].sig)
    bad[2] ^= 4
    items.append(BatchItem(items[3].pubkey, items[3].msg, bytes(bad)))
    assert v.verify_batch(items) == [True, True, True, True, False]
    assert len(v._bank._index) == 2


def test_overbank_fallback_agrees_with_kernel():
    """The over-bank-cap fallback must be KERNEL-EQUIVALENT (ADVICE r5):
    the same batch split between kernel rows and fallback rows shares
    one verdict bitmap, so the two paths must agree on every known edge
    vector — non-canonical S (>= L), y >= p key encodings, wrong
    lengths, tampered bits — or a crafted signature could verify on one
    replica's split and not another's. Pins both the agreement and the
    fallback CLASS (native/oracle, never OpenSSL)."""
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
    # fallback verdicts: bank capacity 1, pre-occupied by an unrelated
    # key, so EVERY edge item routes to the over-cap fallback path
    v = TpuVerifier()
    v._bank = KeyBank(initial_capacity=1, max_keys=1, mode=v._mode)
    occupier = _signed(99, b"occupier")
    assert v.verify_batch([occupier]) == [True]
    assert len(v._bank._index) == 1
    got = v.verify_batch(edge_items)
    assert got == kernel == oracle
    assert len(v._bank._index) == 1  # nothing evicted/registered
    # the fallback actually ran and is a kernel-equivalent class
    assert v._cpu_fb is not None
    assert isinstance(v._cpu_fb, (NativeEdVerifier, CpuVerifier))
    assert type(kernel_equivalent_cpu_verifier()) is type(v._cpu_fb)


@pytest.mark.parametrize("packed", [False, True], ids=["dense", "packed"])
def test_meshed_tpu_verifier_fused(packed):
    """TpuVerifier(mesh=...) fused mode: the GSPMD-sharded jit path (with
    its forced XLA accumulator — a Pallas call has no partitioning rule)
    must agree with the oracle over the 8-device mesh, in both table-row
    layouts (the table is replicated whatever its row width — this
    pre-validates the default flip if the on-chip A/B favors packing)."""
    import jax
    from jax.sharding import Mesh

    from simple_pbft_tpu.ops import comb

    comb.use_row_packing(packed)
    try:
        mesh = Mesh(np.asarray(jax.devices()[:8]), ("dp",))
        v = TpuVerifier(mesh=mesh, mode="fused")
        items = [_signed(i % 4, b"meshed %d" % i) for i in range(12)]
        forged = BatchItem(items[0].pubkey, b"not the msg", items[0].sig)
        items.append(forged)
        oracle = [ref.verify(i.pubkey, i.msg, i.sig) for i in items]
        assert v.verify_batch(items) == oracle == [True] * 12 + [False]
    finally:
        comb.use_row_packing(False)


def test_sharded_comb_quorum_step():
    """Comb-engine shard_map verify + psum tally over the 8-device mesh."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from simple_pbft_tpu.ops import comb
    from simple_pbft_tpu.crypto.tpu_verifier import KeyBank, prepare_comb_batch
    from simple_pbft_tpu.parallel import make_comb_quorum_step

    mesh = Mesh(np.asarray(jax.devices()[:8]), ("dp",))
    n_inst = 2
    items = [_signed(i % 8, b"inst vote %d" % i) for i in range(16)]
    broken = bytearray(items[0].sig)
    broken[3] ^= 1
    items[0] = BatchItem(items[0].pubkey, items[0].msg, bytes(broken))

    bank = KeyBank()
    prep, _fallback = prepare_comb_batch(items, bank)
    inst = np.arange(16, dtype=np.int32) % n_inst
    onehot = np.eye(n_inst, dtype=np.int32)[inst]
    vec = NamedSharding(mesh, P("dp"))  # (B,)
    mat = NamedSharding(mesh, P(None, "dp"))  # batch axis trailing
    repl = NamedSharding(mesh, P())
    s_nib, k_nib, a_idx, r_y, r_sign, precheck = prep.arrays()
    args = [
        jax.device_put(s_nib, mat),
        jax.device_put(k_nib, mat),
        jax.device_put(a_idx, vec),
        jax.device_put(np.asarray(bank.device_tables()), repl),
        jax.device_put(comb.base_table(), repl),
        jax.device_put(r_y, mat),
        jax.device_put(r_sign, vec),
        jax.device_put(precheck, vec),
        jax.device_put(onehot, NamedSharding(mesh, P("dp", None))),
    ]
    verdict, counts = make_comb_quorum_step(mesh)(*args)
    verdict, counts = np.asarray(verdict), np.asarray(counts)
    assert not verdict[0] and verdict[1:].all()
    assert counts.tolist() == [7, 8]


def test_sharded_quorum_step():
    """Ladder-engine shard_map verify + psum tally (fallback path)."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from simple_pbft_tpu.parallel import make_quorum_step

    mesh = Mesh(np.asarray(jax.devices()[:8]), ("dp",))
    n_inst = 2
    items = [_signed(i % 8, b"inst vote %d" % i) for i in range(16)]
    # corrupt one vote of instance 0
    broken = bytearray(items[0].sig)
    broken[3] ^= 1
    items[0] = BatchItem(items[0].pubkey, items[0].msg, bytes(broken))

    prep = prepare_batch(items)
    inst = np.arange(16, dtype=np.int32) % n_inst
    onehot = np.eye(n_inst, dtype=np.int32)[inst]
    vec = NamedSharding(mesh, P("dp"))
    mat = NamedSharding(mesh, P(None, "dp"))  # batch axis trailing
    # arg order: a_y, a_sign, r_y, r_sign, s_bits, k_bits, precheck
    specs = [mat, vec, mat, vec, mat, mat, vec]
    args = [jax.device_put(a, s) for a, s in zip(prep.arrays(), specs)]
    args.append(jax.device_put(onehot, NamedSharding(mesh, P("dp", None))))

    verdict, counts = make_quorum_step(mesh)(*args)
    verdict, counts = np.asarray(verdict), np.asarray(counts)
    assert not verdict[0] and verdict[1:].all()
    assert counts.tolist() == [7, 8]  # one invalid vote lost from instance 0


def test_pallas_accumulate_matches_xla():
    """The Pallas madd-loop kernel (interpret mode on CPU) must agree
    bit-for-bit with the XLA fori_loop path on the same batch."""
    import jax.numpy as jnp

    from simple_pbft_tpu.ops import comb
    from simple_pbft_tpu.crypto.tpu_verifier import KeyBank, prepare_comb_batch

    items = [_signed(i % 3, b"pallas %d" % i) for i in range(8)]
    broken = bytearray(items[5].sig)
    broken[9] ^= 2
    items[5] = BatchItem(items[5].pubkey, items[5].msg, bytes(broken))

    bank = KeyBank(mode="fused")
    prep, _ = prepare_comb_batch(items, bank)
    s_nib, k_nib, a_idx, r_y, r_sign, precheck = prep.arrays()
    tables = bank.device_tables()
    args = (jnp.asarray(s_nib), jnp.asarray(k_nib), jnp.asarray(a_idx),
            tables, jnp.asarray(r_y), jnp.asarray(r_sign), jnp.asarray(precheck))
    try:
        comb.use_accum_impl("xla")
        want = np.asarray(comb.fused_verify_kernel(*args))
        comb.use_accum_impl("pallas_interpret")
        got = np.asarray(comb.fused_verify_kernel(*args))
    finally:
        comb.use_accum_impl("auto")  # restore the shipped default
    assert want.tolist() == [True] * 5 + [False] + [True] * 2
    assert got.tolist() == want.tolist()


def test_row_packing_matches_oracle_and_dense():
    """Packed table rows (two 15-bit limbs per int32, 128-byte rows —
    the gather-bandwidth A/B, ops/comb.use_row_packing) must be
    bit-exact against both the RFC 8032 oracle and the dense layout,
    including invalid rows; kernels and banks built after the switch
    capture the packed shapes."""
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
    dense = TpuVerifier(mode="fused", window=5).verify_batch(items)
    comb.use_row_packing(True)
    try:
        assert comb.ROW == comb.ROW_PACKED
        packed = TpuVerifier(mode="fused", window=5).verify_batch(items)
        # the unpack must also hold INSIDE the Pallas accumulate kernel
        # (interpret mode here; the on-chip A/B runs it under Mosaic) —
        # exercised directly at a small packed batch
        comb.use_accum_impl("pallas_interpret")
        try:
            pal = TpuVerifier(mode="fused", window=4).verify_batch(items)
        finally:
            comb.use_accum_impl("auto")
    finally:
        comb.use_row_packing(False)
    assert packed == dense == oracle
    assert pal == oracle


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
