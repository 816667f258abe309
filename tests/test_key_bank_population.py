"""A key bank sized to the published population and bounded by the device
(ISSUE 34): the sizing rule as a pure function, a bank past the old
power-of-two rule with every row of a batch under a different key, and the
served path with one request outstanding a client, each against its plain
reference (crypto/ed25519_cpu.verify; the dict of acknowledged puts that
benchmark/stages.py fills).

No test here holds more than 12 keys' tables (48 MiB): the budget is
injected where the device's memory would give thousands.
"""

import asyncio
import logging
import os
import random
import re
import sys

import numpy as np
import pytest

from simple_pbft_tpu.config import make_test_committee
from simple_pbft_tpu.crypto import ed25519_cpu as ref
from simple_pbft_tpu.crypto import tpu_verifier as tv
from simple_pbft_tpu.crypto.coalesce import VerifyService
from simple_pbft_tpu.crypto.signer import Signer
from simple_pbft_tpu.crypto.verifier import BatchItem

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "benchmark")

GB16 = 16 * 2**30  # a v5e chip's memory
CAP = 12  # the injected budget, in keys: past the patched power-of-two rule, no power of two


# ---------------------------------------------------------------------------
# the sizing rule
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("population, device_bytes, want", [
    (72 + 32, GB16, 128),      # n=64, 8 clients: as before
    (24 + 32, GB16, 64),       # n=16, 8 clients: as before
    (72 + 32, None, 128),      # the CPU reports no memory: the same
    (1, GB16, 8),
    (512, GB16, 512),          # the last power of two
    (513, GB16, 640),          # the first granule
    (1064 + 32, GB16, 1152),   # n=64, 1,000 clients: 4.83 GB, no power of two
    (1064 + 32, None, 512),    # 2 GiB where the platform reports nothing
    (1064 + 32, 2**31 / tv.TABLE_SHARE, 512),  # the same budget, reported
    (5000, GB16, 1843),        # cannot fit: the device's share, no error
    (10**6, 2**40, tv.MAX_INDEXED_KEYS),  # nor past the int32 index
])
def test_bank_capacity_is_a_function_of_population_and_device(
        population, device_bytes, want):
    cap = tv.bank_capacity(population, device_bytes)
    assert cap == want
    assert cap * tv.comb.ROWS_PER_KEY * tv.comb.ROW < 2**31
    if device_bytes is not None:
        assert cap * tv.KEY_BYTES <= device_bytes * tv.TABLE_SHARE
    if population <= tv.POW2_KEYS and cap >= population:
        assert cap & (cap - 1) == 0  # the standing cells' capacities do not move


def test_a_bank_past_the_int32_index_fails_at_construction():
    with pytest.raises(ValueError, match="2\\^31 elements"):
        tv.KeyBank(initial_capacity=8, max_keys=tv.MAX_INDEXED_KEYS + 1)
    assert tv.KeyBank.MAX_KEYS == 512  # an unsized bank's bound, 2 GiB


def test_a_sized_verifier_fixes_its_capacity(monkeypatch):
    """The capacity at construction is the cap: no growth, so no sender
    can move the table's shape. No table past 8 keys is allocated."""
    monkeypatch.setattr(tv, "_device_bytes", lambda mesh: None)
    v = tv.TpuVerifier(initial_keys=5)
    assert (v._bank._cap, v._bank._max_keys) == (8, 8)
    unsized = tv.TpuVerifier()
    assert (unsized._bank._cap, unsized._bank._max_keys) == (8, 512)


# ---------------------------------------------------------------------------
# one verifier for the rest: 16 published keys on a device that holds 12
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def population():
    _cfg, keys = make_test_committee(n=4, clients=CAP)
    return [Signer(name, kp.seed) for name, kp in keys.items()]


@pytest.fixture(scope="module")
def device(population):
    """Built as node.make_verifier builds it, with the rule's two numbers
    made small: the power-of-two rule ends at 8 keys where it ends at
    512, and the device's share for tables is 12 keys, so the published
    16 + 32 are past the rule (granules) and clamped."""
    patch = pytest.MonkeyPatch()
    patch.setattr(tv, "POW2_KEYS", 8)
    patch.setattr(tv, "_device_bytes",
                  lambda mesh: int(CAP * tv.KEY_BYTES / tv.TABLE_SHARE) + 1)
    log = logging.getLogger()
    seen = []
    handler = logging.Handler()
    handler.emit = seen.append
    log.addHandler(handler)
    try:
        v = tv.TpuVerifier(initial_keys=len(population) + 32)
        v.warm_for_population([s.pub for s in population], max_sweep=128)
    finally:
        log.removeHandler(handler)
        patch.undo()
    v.warnings = [r.getMessage() for r in seen if r.levelno >= logging.WARNING]
    return v


def _signed(signer, msg: bytes) -> BatchItem:
    return BatchItem(signer.pub, msg, signer.sign(msg))


def _not_a_point(rng: random.Random) -> bytes:
    while True:
        cand = bytes([rng.randrange(256) for _ in range(31)] + [0])
        if ref.point_decompress(cand) is None:
            return cand


def _plant_seven(items, rng, other):
    """benchmark/stages.py: kernel_stage's seven failures, at seeded
    positions; `other`, row 0's signer, signs the second."""
    spots = rng.sample(range(1, len(items)), 7)  # row 0 stays good

    def edit(pos, **kw):
        it = items[pos]
        items[pos] = BatchItem(kw.get("pubkey", it.pubkey), it.msg,
                               kw.get("sig", it.sig))

    it = items[spots[0]]
    flipped = bytearray(it.sig)
    flipped[rng.randrange(64)] ^= 1 << rng.randrange(8)
    edit(spots[0], sig=bytes(flipped))
    edit(spots[1], sig=other.sign(items[spots[1]].msg))
    it = items[spots[2]]
    s_big = int.from_bytes(it.sig[32:], "little") + ref.L
    edit(spots[2], sig=it.sig[:32] + s_big.to_bytes(32, "little"))
    it = items[spots[3]]
    edit(spots[3], sig=(ref.P + 1).to_bytes(32, "little") + it.sig[32:])
    edit(spots[4], pubkey=items[spots[4]].pubkey[:31])
    edit(spots[5], sig=items[spots[5]].sig[:63])
    edit(spots[6], pubkey=_not_a_point(rng))
    return sorted(spots)


def test_clamped_bank_warns_and_holds_what_fits(device, population):
    bank = device._bank
    assert (bank._cap, bank._max_keys, len(bank._index)) == (CAP, CAP, CAP)
    assert bank._np.shape[0] == CAP and CAP & (CAP - 1)  # no power of two
    # the device table holds two rows a line
    assert bank.table_shape() == (CAP * tv.comb.ROWS_PER_KEY // 2, 128)
    assert bank.device_tables().shape == bank.table_shape()
    assert bank._np.nbytes == bank.device_tables().nbytes
    assert any("bank clamped: 16 published keys > max_keys=12" in w
               and "table-free ladder, warmed at buckets [8, 32, 128]" in w
               for w in device.warnings)
    # the comb's three buckets, then the ladder's same three
    assert [(r["program"], r["bucket"]) for r in device.warm_log] == [
        (p, b) for p in ("comb", "ladder") for b in (8, 32, 128)]
    for s in population[:CAP]:
        assert bank.lookup(s.pub) >= 0
    for s in population[CAP:]:
        assert bank.lookup(s.pub) == tv.KeyBank.UNCACHED
    snap = device.shape_snapshot()
    assert snap["bank_keys"] == snap["bank_capacity"] == CAP
    assert snap["table_bytes"] == CAP * tv.KEY_BYTES
    assert snap["bank_build_s"] > 0 and snap["post_warm_compiles"] == 0


def test_every_row_under_a_different_key_agrees_with_the_oracle(device, population):
    """12 rows, 12 keys, the benchmark's seven planted failures: verdicts
    equal RFC 8032's item for item, the pass names as many table rows as
    its valid keys, and nothing is uploaded after the warm."""
    rng = random.Random(34)
    signers = rng.sample(population[1:CAP], CAP - 1)
    items = [_signed(population[0], b"row 0")] + [
        _signed(s, b"row %d" % i) for i, s in enumerate(signers, 1)]
    assert len({it.pubkey for it in items}) == CAP
    planted = _plant_seven(items, rng, population[0])
    oracle = [ref.verify(it.pubkey, it.msg, it.sig) for it in items]
    assert [i for i, ok in enumerate(oracle) if not ok] == planted

    before = device.shape_snapshot()
    assert before["bank_uploads"] == 1
    assert device.verify_batch(items) == oracle
    after = device.shape_snapshot()
    # two rows lost their key (wrong length, no curve point) and name row 0
    rows = {device._bank._index.get(it.pubkey, 0) for it in items}
    assert len(rows) == CAP - 2
    assert after["pass_distinct_keys"] - before["pass_distinct_keys"] == CAP - 2
    assert after["bank_uploads"] == 1
    assert after["post_warm_compiles"] == after["overcap_fallback_items"] == 0


def test_the_gather_keeps_the_wanted_half_of_each_line():
    """comb._gather_rows on a table two rows a line against plain
    indexing of the same rows one a line: odd and even rows, first and
    last line."""
    comb = tv.comb
    rng = np.random.default_rng(34)
    rows = rng.integers(-2**31, 2**31, (64, comb.ROW), dtype=np.int64).astype(np.int32)
    idx = rng.integers(0, 64, (comb.NPOS, 8)).astype(np.int32)
    idx[0, :4] = [0, 1, 62, 63]
    got = np.asarray(comb._gather_rows(rows.reshape(32, comb.LINE), idx))
    assert got.shape == (comb.NPOS, comb.ROW, 8)
    assert np.array_equal(got, rows[idx].transpose(0, 2, 1))


def test_over_cap_keys_take_the_ladder_and_agree_with_the_oracle(device, population):
    """What a deployment larger than the device's share gets: the keys
    past the cap verify on the device by the table-free program, with
    the comb's verdicts, at a bucket the warm compiled."""
    items = [_signed(s, b"late %d" % i) for i, s in enumerate(population[CAP - 4:])]
    bad = bytearray(items[-1].sig)
    bad[5] ^= 4
    items[-1] = BatchItem(items[-1].pubkey, items[-1].msg, bytes(bad))
    oracle = [ref.verify(it.pubkey, it.msg, it.sig) for it in items]
    assert oracle == [True] * 7 + [False]
    before = device.shape_snapshot()
    assert device.verify_batch(items) == oracle
    after = device.shape_snapshot()
    assert after["ladder_items"] - before["ladder_items"] == 4
    assert after["ladder_passes"] - before["ladder_passes"] == 1
    assert after["overcap_fallback_items"] == 0
    assert after["bank_uploads"] == 1 and after["post_warm_compiles"] == 0


def test_a_mixed_pile_launches_both_programs_and_counts_the_uncached_rows(
        device, population):
    """A pile over banked and unbanked keys with the benchmark's seven
    planted failures on either side of the bank's edge: one pass, two
    programs, verdicts equal RFC 8032's item for item, the counters count
    the rows the ladder answered, the device ledger holds a row a program
    (which add up to the pass), and nothing compiled or went to the CPU."""
    from simple_pbft_tpu import devledger
    from simple_pbft_tpu.crypto import costmodel

    rng = random.Random(36)
    items = [_signed(population[i % len(population)], b"mixed %d" % i)
             for i in range(40)]
    planted = _plant_seven(items, rng, population[0])
    oracle = [ref.verify(it.pubkey, it.msg, it.sig) for it in items]
    assert [i for i, ok in enumerate(oracle) if not ok] == planted
    banked = set(device._bank._index)
    uncached = [i for i, it in enumerate(items)
                if it.pubkey not in banked
                and len(it.pubkey) == 32 and len(it.sig) == 64]
    assert 8 <= len(uncached) <= 16  # c8-c11's rows, and the key that is no point

    devledger.configure("mixed-pile", enabled=True)
    try:
        before = device.shape_snapshot()
        assert device.verify_batch(items) == oracle
        after = device.shape_snapshot()
        rows = [r for r in devledger.recent() if r["lane"] == "ed25519"]
        shapes = devledger.snapshot()["shapes"]
    finally:
        devledger.configure("")  # a fresh window for whoever comes next
    assert after["ladder_items"] - before["ladder_items"] == len(uncached)
    assert after["ladder_passes"] - before["ladder_passes"] == 1
    assert after["overcap_fallback_items"] == after["post_warm_compiles"] == 0
    assert after["bank_uploads"] == 1
    assert [(r["mode"], r["bucket"], r["n"]) for r in rows] == [
        ("fused", 128, 40 - len(uncached)), ("ladder", 32, len(uncached))]
    assert not any(r["compile"] for r in rows)
    assert set(shapes) == {"ed25519:fused/w4/b128", "ed25519:ladder/w4/b32"}
    lad = costmodel.parse_shape_key("ed25519:ladder/w4/b32")
    assert lad == {"lane": "ed25519", "mode": "ladder", "window": 4, "bucket": 32}
    cost = costmodel.shape_cost(lad["mode"], lad["window"], lad["bucket"])
    fused = costmodel.shape_cost("fused", 4, 128)
    assert cost["gather_bytes_per_item"] == 0 and cost["wire_bytes_per_item"] == 129
    assert 6 < cost["flops_per_item"] / fused["flops_per_item"] < 10
    assert costmodel.gather_bytes_for_shapes(shapes) == fused["gather_bytes_per_pass"]


def test_a_population_that_fits_warms_no_ladder_bucket(monkeypatch):
    """The three standing configurations: the warm compiles the comb's
    buckets and nothing else, and every pass launches one program."""
    monkeypatch.setattr(tv, "_device_bytes", lambda mesh: None)
    signers = [Signer(f"fit{i}", bytes([200 + i]) * 32) for i in range(6)]
    v = tv.TpuVerifier(initial_keys=len(signers) + 2)
    monkeypatch.setattr(v, "_ladder_fn", lambda *a: pytest.fail(
        "the ladder was launched for a population that fits its bank"))
    v.warm_for_population([s.pub for s in signers], max_sweep=32)
    assert [(r["program"], r["bucket"]) for r in v.warm_log] == [
        ("comb", 8), ("comb", 32)]
    assert {sig[0] for sig in v.shape_signatures} == {"fused"}
    items = [_signed(s, b"fits %d" % i) for i, s in enumerate(signers * 3)]
    assert v.verify_batch(items) == [True] * 18
    snap = v.shape_snapshot()
    assert snap["ladder_items"] == snap["ladder_passes"] == 0
    assert snap["post_warm_compiles"] == snap["overcap_fallback_items"] == 0
    assert v.ladder_seconds == 0.0


# ---------------------------------------------------------------------------
# the served path: every client with one put outstanding
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_clients", [40, 8])
def test_one_request_clients_against_the_reference(
        n_clients, device, population, capsys, monkeypatch):
    """benchmark/stages.py's own pump and comparison at n=4: every
    replica's state equals the dict of acknowledged puts, a sample of gets
    reads back through the reply quorum, and no reply rides a batch frame
    (groups of one stay a Reply). Eight clients are all in the 12-key
    bank; of forty, c8-c39 are past it (44 keys' tables are 176 MiB), a
    deployment larger than the device's share: their signatures verify on
    the device by the table-free ladder and nothing else moves. Either way the warm's upload stays the
    only one. Counters are read over the whole run: how much of it falls
    in the two-second window depends on what else the machine runs."""
    from simple_pbft_tpu.committee import LocalCommittee

    sys.path.insert(0, BENCH)
    try:
        import stages
    finally:
        sys.path.remove(BENCH)

    built = []
    build = LocalCommittee.build
    monkeypatch.setattr(
        LocalCommittee, "build",
        lambda **kw: built.append(build(**kw)) or built[-1])

    config = {"n": 4, "clients": n_clients, "keys": 2 * n_clients, "block": 16,
              "view_timeout_s": 30.0, "request_timeout_s": 30.0,
              "checkpoint_interval": 64, "watermark_window": 1024}
    cell = {"in_flight": n_clients, "warmup_seconds": 0.5, "trace_seconds": 1.0,
            "gets": min(16, n_clients), "drain_timeout_s": 30.0}
    banked = min(4 + n_clients, CAP)  # r0-r3, then c0 onwards
    _cfg, keys = make_test_committee(n=4, clients=n_clients)
    pubkeys = [kp.pub for kp in keys.values()]
    assert pubkeys[:banked] == [s.pub for s in population[:banked]]
    service = VerifyService(device, max_batch=128, cpu_cutoff=0)
    before = device.shape_snapshot()
    checks = stages.Checks()
    try:
        served = asyncio.run(stages.served_stage(
            cell, config, 2147483659, 2.0, service, pubkeys, checks))
        snap = service.snapshot()
    finally:
        service.close()
    out = capsys.readouterr().out
    assert not checks.problems, checks.problems
    assert f"clients={n_clients} in_flight={n_clients}" in out
    assert "replicas_agree=4" in out
    assert served["window"]["failed"] == 0
    # every pump, so every client, had a put acknowledged
    assert int(re.search(r"distinct_keys=(\d+)", out).group(1)) >= n_clients

    (com,) = built
    clients = stages._summed(c.metrics for c in com.clients)
    replicas = stages._summed(r.metrics for r in com.replicas)
    assert clients["reply_frames"] >= n_clients * com.cfg.weak_quorum
    assert clients["reply_entries_batched"] == 0
    assert replicas.get("reply_entries_batched", 0) == 0
    assert replicas["reply_frames_sent"] == (
        replicas.get("replies_sent", 0) + replicas.get("spec_replies_sent", 0))
    assert clients.get("request_timeouts", 0) == clients.get("retransmissions", 0) == 0
    assert snap["cpu_reroute_items"] == 0

    after = snap["device_shapes"]
    assert after["bank_uploads"] == 1 and after["post_warm_compiles"] == 0
    assert after["bank_keys"] == CAP
    assert after["overcap_fallback_items"] == 0
    over_cap = after["ladder_items"] - before["ladder_items"]
    assert (over_cap > 0) == (4 + n_clients > CAP)
    assert ("verify.ladder" in served["spans"]) == (4 + n_clients > CAP)
    # a gauge is still over the window, and the counter beside it is not
    assert served["counters"]["verify"]["device_shapes.bank_keys"] == 0
    passes = snap["device_passes"]
    named = after["pass_distinct_keys"] - before["pass_distinct_keys"]
    # a pass never names more keys than the served committee has banked
    assert 0 < passes <= named <= passes * banked
