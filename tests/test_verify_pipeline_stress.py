"""Concurrency stress for the verify pipeline's KeyBank (VERDICT r3
next-round #9, SURVEY §5 sanitizers row).

The replica runtime overlaps consecutive sweeps' signature verifies in
separate executor threads, so KeyBank.lookup/lookup_pile/device_tables
race: an unlocked check-then-append once could map one pubkey onto
another's table row — every later signature from that key failing (or,
adversarially, verifying against the wrong key). These tests hammer the
locked paths from multiple threads with an adversarial fresh-key spray
through the max_keys/UNCACHED boundary and then audit the bank:

- every cached pubkey maps to a UNIQUE row, and the row's table content
  bit-exactly matches a freshly built table for that key;
- keys beyond the cap consistently report UNCACHED (the ladder), never
  a stolen row;
- invalid keys stay -1 and the negative cache stays bounded;
- a two-thread TpuVerifier pipeline returns the same verdict bitmap as
  the CPU oracle under the race.
"""

import threading

import numpy as np
import pytest

from simple_pbft_tpu.crypto import ed25519_cpu as ref
from simple_pbft_tpu.crypto.verifier import BatchItem


def _keys(n, tag=0):
    out = []
    for i in range(n):
        seed = bytes([tag, i % 256, (i >> 8) % 256]) + b"\x5a" * 29
        out.append((seed, ref.public_key(seed)))
    return out


def test_keybank_races_never_alias_rows():
    from simple_pbft_tpu.ops import comb
    from simple_pbft_tpu.crypto.tpu_verifier import KeyBank

    bank = KeyBank(initial_capacity=4, max_keys=24)
    committee = _keys(16, tag=1)
    spray = _keys(40, tag=2)  # 8 more fit under the cap; the rest UNCACHED
    bad = [bytes([i]) * 32 for i in range(8)]  # mostly non-points
    # committee keys are registered at deployment time (replica startup
    # warms the bank); the adversarial spray then fights over the
    # REMAINING capacity — cached rows must never move or alias
    baseline = {pk: bank.lookup(pk) for _, pk in committee}
    assert all(0 <= i < 24 for i in baseline.values())
    errors = []
    results: dict = dict(baseline)
    res_lock = threading.Lock()

    def worker(wid):
        try:
            for i in range(250):  # 4 workers x 250 = 1k iterations
                seed_pk = committee[(wid + i) % len(committee)]
                idx = bank.lookup(seed_pk[1])
                if not (0 <= idx < 24):
                    errors.append(f"committee key got {idx}")
                with res_lock:
                    prev = results.setdefault(seed_pk[1], idx)
                    if prev != idx:
                        errors.append(f"row moved {prev} -> {idx}")
                if i % 5 == 0:
                    s = spray[(wid * 13 + i) % len(spray)]
                    j = bank.lookup(s[1])
                    if j == -1:
                        errors.append("valid spray key reported invalid")
                if i % 7 == 0:
                    b = bank.lookup(bad[(wid + i) % len(bad)])
                    # a random 32-byte string is a point ~50% of the time;
                    # it must never be both cached and invalid
                    if b == -1 and bad[(wid + i) % len(bad)] in bank._index:
                        errors.append("key both cached and invalid")
        except Exception as e:  # noqa: BLE001
            errors.append(repr(e))

    threads = [threading.Thread(target=worker, args=(w,)) for w in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors[:5]

    # audit: unique rows, and each cached row's content matches a fresh
    # single-threaded build of that key's table (catches silent aliasing)
    idxs = list(bank._index.values())
    assert len(idxs) == len(set(idxs)), "row collision"
    assert len(bank._index) <= 24
    for pk, idx in list(bank._index.items())[:8]:
        pt = ref.point_decompress(pk)
        fresh = comb.fused_table_np(pt)
        assert np.array_equal(bank._np[idx], fresh), "aliased table row"
    # spray keys beyond the cap must be UNCACHED, consistently
    over = [pk for _, pk in spray if pk not in bank._index]
    assert over, "cap never reached — spray too small"
    for pk in over[:4]:
        assert bank.lookup(pk) == KeyBank.UNCACHED


def test_two_thread_verify_pipeline_matches_oracle():
    """Two threads interleave verify_batch on one TpuVerifier (the
    replica pipeline's exact shape) with fresh keys appearing mid-run;
    verdicts must match the CPU oracle bit-for-bit."""
    jax = pytest.importorskip("jax")
    from simple_pbft_tpu import force_cpu

    force_cpu()
    from simple_pbft_tpu.crypto.tpu_verifier import TpuVerifier

    v = TpuVerifier()
    keys = _keys(12, tag=3)
    batches = []
    for b in range(8):
        items, want = [], []
        for i in range(8):
            seed, pk = keys[(b * 5 + i) % len(keys)]
            msg = b"stress %d %d" % (b, i)
            sig = ref.sign(seed, msg)
            if (b + i) % 3 == 0:  # corrupt a third of them
                sig = sig[:-1] + bytes([sig[-1] ^ 1])
                want.append(False)
            else:
                want.append(True)
            items.append(BatchItem(pk, msg, sig))
        batches.append((items, want))

    failures = []

    def run(wid):
        for k, (items, want) in enumerate(batches):
            if k % 2 != wid:
                continue
            got = v.verify_batch(items)
            if [bool(x) for x in got] != want:
                failures.append((wid, k, got, want))

    ts = [threading.Thread(target=run, args=(w,)) for w in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not failures, failures[:2]
