"""Round-5 ADVICE satellite fixes (ISSUE 1).

- OpenSSLVerifier's parsed-key cache stops inserting at MAX_KEYS instead
  of clearing: committee keys stay resident under adversarial fresh-key
  churn (mirrors NativeEdVerifier._row_for's policy).
"""

import numpy as np
import pytest


# ---------------------------------------------------------------------------
# OpenSSLVerifier key-cache policy
# ---------------------------------------------------------------------------


class _FakeParsedKey:
    def __init__(self, raw):
        self.raw = raw

    def verify(self, sig, msg):
        if sig != msg:
            raise ValueError("bad")


def _openssl_with_fake_loader(max_keys):
    """OpenSSLVerifier with the `cryptography` loader mocked so the
    cache POLICY is testable on hosts without the wheel (this container:
    the wheel is absent and the real __init__ would ImportError)."""
    from simple_pbft_tpu.crypto.verifier import BatchItem, OpenSSLVerifier

    v = OpenSSLVerifier.__new__(OpenSSLVerifier)
    loads = []

    def load(raw):
        loads.append(raw)
        return _FakeParsedKey(raw)

    v._load = load
    v._cache = {}
    v.MAX_KEYS = max_keys
    return v, loads, BatchItem


def test_openssl_cache_stops_inserting_at_cap_keeps_committee_keys():
    v, loads, BatchItem = _openssl_with_fake_loader(max_keys=4)
    committee = [bytes([i]) * 32 for i in range(4)]
    # committee keys land early and fill the cache
    v.verify_batch([BatchItem(pk, b"m", b"m") for pk in committee])
    assert sorted(v._cache) == sorted(committee)
    # adversarial churn: 50 fresh keys — none may enter, none may evict
    churn = [bytes([100 + i]) * 32 for i in range(50)]
    out = v.verify_batch([BatchItem(pk, b"m", b"m") for pk in churn])
    assert out == [True] * 50  # still verified, just not cached
    assert sorted(v._cache) == sorted(committee)  # keys stayed resident
    # committee traffic after the storm: zero new parses (cache hits)
    n_loads = len(loads)
    v.verify_batch([BatchItem(pk, b"m2", b"m2") for pk in committee])
    assert len(loads) == n_loads


def test_openssl_cache_churn_costs_attacker_not_committee():
    v, loads, BatchItem = _openssl_with_fake_loader(max_keys=2)
    a, b = b"\x01" * 32, b"\x02" * 32
    v.verify_batch([BatchItem(a, b"m", b"m"), BatchItem(b, b"m", b"m")])
    # the same over-cap key re-parses per batch (bounded memory), the
    # resident keys never do
    evil = b"\xee" * 32
    for _ in range(3):
        v.verify_batch([BatchItem(evil, b"m", b"m"), BatchItem(a, b"m", b"m")])
    assert loads.count(evil) == 3
    assert loads.count(a) == 1
