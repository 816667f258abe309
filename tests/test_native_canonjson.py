"""Differential tests: native canonical-JSON encoder vs the json module.

canonical_json is the wire format AND the digest/signing preimage of
every consensus message — a single byte of divergence between the native
encoder (native/canonjson.cpp) and json.dumps(sort_keys=True,
separators=(",", ":")) would fork the committee. These tests enforce
byte-exact equivalence over adversarial content (control characters,
astral planes, lone surrogates, huge ints, deep nesting, non-ASCII and
empty keys) plus real message traffic, and pin the fallback contract for
out-of-subset input.
"""

import json
import random

import pytest

from simple_pbft_tpu import native
from simple_pbft_tpu.messages import (
    Commit,
    NewView,
    PrePrepare,
    Reply,
    ReplyBatch,
    Request,
    ViewChange,
    canonical_json,
)

pytestmark = pytest.mark.skipif(
    not native.canonjson_available(), reason="native canonjson unavailable"
)


def _dumps(obj) -> bytes:
    return json.dumps(obj, sort_keys=True, separators=(",", ":")).encode()


NASTY_STRINGS = [
    "",
    "plain ascii",
    '"quotes" and \\backslashes\\',
    "\x00\x01\x1f\x7f",
    "\b\f\n\r\t",
    "é ü ß π ₿ €",
    "߿ࠀ￿",
    "astral \U0001f600 \U0010fffd",
    "\ud800 lone high",  # lone surrogates survive Python strs
    "lone low \udfff",
    "मिश्रित scripts 混合 نصوص",
]


def test_differential_handcrafted():
    cases = [
        None, True, False, 0, -1, 1, 2**31, -(2**63), 2**63 - 1,
        2**200, -(2**200),
        [], {}, [[]], [{}, []],
        {"": ""}, {"a": None}, {"0": 0, "00": 0, "a b": 1},
        {k: i for i, k in enumerate(NASTY_STRINGS[1:])},
        *NASTY_STRINGS,
        {"nested": [{"deep": [{"er": [1, None, True, "x"]}]}]},
    ]
    for obj in cases:
        assert native.canonjson_encode(obj) == _dumps(obj), repr(obj)[:80]


def test_differential_fuzz():
    rng = random.Random(0xC0FFEE)

    def gen(depth):
        r = rng.random()
        if depth >= 5 or r < 0.35:
            return rng.choice(
                [
                    rng.choice(NASTY_STRINGS),
                    rng.randint(-(2**70), 2**70),
                    rng.randint(-100, 100),
                    None,
                    True,
                    False,
                ]
            )
        if r < 0.65:
            return [gen(depth + 1) for _ in range(rng.randint(0, 4))]
        return {
            rng.choice(NASTY_STRINGS) + str(rng.randint(0, 9)): gen(depth + 1)
            for _ in range(rng.randint(0, 4))
        }

    for _ in range(500):
        obj = gen(0)
        assert native.canonjson_encode(obj) == _dumps(obj), repr(obj)[:120]


def test_real_message_traffic_byte_exact():
    msgs = [
        Request(client_id="c0", timestamp=1785448550156039,
                operation="put kéy   value \U0001f600"),
        PrePrepare(view=3, seq=99, digest="ab" * 32,
                   block=[{"kind": "request", "client_id": "c1",
                           "timestamp": 5, "operation": "x", "sender": "c1",
                           "sig": "cd" * 64}]),
        Commit(view=0, seq=1, digest="00" * 32, bls_share="ff" * 48),
        Reply(view=2, seq=7, client_id="c9", timestamp=42, result="ok",
              superseded=1, mac="aa" * 16),
        ReplyBatch(view=2, seq=7, client_id="c9", spec=1,
                   timestamps=[1785448550156039, 1785448550156040],
                   results=["ok", "v\u00e9 \"q\""], mac="aa" * 16),
        ViewChange(new_view=4, stable_seq=64,
                   checkpoint_proof=[{"kind": "checkpoint", "seq": 64,
                                      "state_digest": "ee" * 32}],
                   prepared_proofs=[]),
        NewView(new_view=4, viewchange_proof=[], pre_prepares=[]),
    ]
    for m in msgs:
        d = m.to_dict()
        assert native.canonjson_encode(d) == _dumps(d)
        # the integrated path returns the same bytes (whichever encoder ran)
        assert canonical_json(d) == _dumps(d)


def test_int_subclass_matches_json_repr_semantics():
    """json.dumps formats ints via int.__repr__ regardless of subclass
    overrides; the native encoder must do the same or an int subclass
    with a hostile __str__ would produce divergent digests (and invalid
    JSON) only on natively-equipped replicas."""

    class EvilInt(int):
        def __str__(self):
            return "EVIL"

        __repr__ = __str__

    for v in (EvilInt(7), EvilInt(2**80), EvilInt(-(2**90))):
        obj = {"a": v}
        assert native.canonjson_encode(obj) == _dumps(obj)


def test_out_of_subset_falls_back():
    # floats and non-str keys are not wire types: native returns None and
    # the integrated canonical_json still answers via the json module
    assert native.canonjson_encode({"f": 1.5}) is None
    assert native.canonjson_encode({1: "x"}) is None
    assert canonical_json({"f": 1.5}) == _dumps({"f": 1.5})


def test_encoder_bound_on_depth():
    deep = obj = []
    for _ in range(200):
        inner = []
        obj.append(inner)
        obj = inner
    assert native.canonjson_encode(deep) is None  # RecursionError -> None
    assert canonical_json(deep) == _dumps(deep)  # fallback still answers
