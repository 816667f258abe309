"""The certificate deployment (``benchmark/configs/pbft-n256-bls-qc.json``,
cell ``n256-qc-inflight8``) held to what the program builds, to the plain
reference and to its spans, on the CPU.

The benchmark's own modules (``benchmark/run.py``, ``stages.py``,
``bls_ref.py``) are imported as ``benchmark/tests/conftest.py`` puts them on
``sys.path``: the repository root and ``benchmark/``.
"""

import argparse
import asyncio
import json
import os
import random
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
for _path in (REPO, BENCH):
    if _path not in sys.path:
        sys.path.insert(0, _path)

import bls_ref  # noqa: E402
import run  # noqa: E402
import stages  # noqa: E402

from simple_pbft_tpu import spans  # noqa: E402
from simple_pbft_tpu.committee import LocalCommittee  # noqa: E402
from simple_pbft_tpu.consensus import qc as qc_mod  # noqa: E402
from simple_pbft_tpu.crypto import bls  # noqa: E402

V5E_BYTES_LIMIT = 16_909_336_064  # a v5e's memory_stats()["bytes_limit"]
CELL = "n256-qc-inflight8"
METRICS = ("qc_share_sign_us_per_req", "qc_share_sign_mean_us",
           "qc_aggregate_mean_ms", "qc_pairing_mean_ms", "qc_queue_mean_ms",
           "qc_lane_submissions_per_req")


def _drive(coro, timeout=240):
    return asyncio.run(asyncio.wait_for(coro, timeout))


def test_the_n256_file_states_what_the_program_builds():
    from simple_pbft_tpu.config import make_test_committee
    from simple_pbft_tpu.crypto.tpu_verifier import bank_capacity

    config = run.load("configs", "pbft-n256-bls-qc")
    cfg, keys = make_test_committee(n=256, clients=8, qc_mode=True)
    stages.check_config(config, cfg, len(keys))
    assert stages.qc_mode(config) is True and cfg.qc_mode is True
    assert config["f"] == cfg.f == 85 and cfg.quorum == 171
    assert config["verifier"]["signing_keys"] == len(keys) == 264
    # node.make_verifier sizes the bank for the population plus 32
    assert config["verifier"]["key_bank"] == bank_capacity(
        264 + 32, V5E_BYTES_LIMIT)
    cell = run.load("workloads", CELL)
    assert cell["config"] == "pbft-n256-bls-qc" and cell["chips"] == 1
    assert cell["in_flight"] == config["clients"] == 8
    assert {spec["name"] for spec in run.metric_specs(CELL)} >= set(METRICS)
    for other in ("n64-inflight8", "n16-inflight8"):
        assert not {s["name"] for s in run.metric_specs(other)} & set(METRICS)


def _swapped(keys, cert) -> str:
    """``cert``'s aggregate with one signer's share made over another
    payload (the next view): the signer set and the payload stay."""
    sks = {r: bls.keygen(keys[r].seed)[0] for r in cert.signers}
    odd = cert.signers[len(cert.signers) // 2]
    raws = [bytes.fromhex(qc_mod.sign_share(
        sks[r], cert.phase, cert.view + (r == odd), cert.seq, cert.digest))
        for r in cert.signers]
    return bls.aggregate_signatures(raws).hex()


def test_a_certificate_committee_matches_the_plain_reference():
    """n=16 (f=5, quorum 11): seeded puts through Client.submit; every
    replica's state is the dict of acknowledged puts, and every certificate
    the primary formed is what the reference says, with and without one
    share over another payload."""
    n, f, quorum = 16, 5, 11
    rng = random.Random(43)
    reference: dict = {}
    formed: dict = {}

    async def scenario():
        com = LocalCommittee.build(n=n, clients=2, qc_mode=True,
                                   view_timeout=60.0)
        assert com.cfg.f == f and com.cfg.quorum == quorum
        primary = com.replica("r0")
        real_on_qc = primary._on_qc

        async def on_qc(cert):
            formed[(cert.phase, cert.view, cert.seq)] = cert
            await real_on_qc(cert)

        primary._on_qc = on_qc
        for c in com.clients:
            c.request_timeout = 60.0

        async def writer(idx: int) -> None:
            client = com.clients[idx]
            for turn in range(4):
                key = f"k{idx}.{turn % 3}"
                value = f"v{rng.getrandbits(48):012x}"
                assert await client.submit(f"put {key} {value}") == "ok"
                reference[key] = value

        com.start()
        try:
            await asyncio.gather(writer(0), writer(1))
            # a speculative quorum acknowledges before the final execution
            last = primary.next_seq - 1
            for _ in range(600):
                if all(r.executed_seq >= last for r in com.replicas):
                    break
                await asyncio.sleep(0.05)
        finally:
            await com.stop()
        return com

    com = _drive(scenario())
    for r in com.replicas:
        assert json.loads(r.app.snapshot()) == reference, r.id
    assert formed and {phase for phase, _v, _s in formed} == {"prepare",
                                                              "commit"}
    for cert in formed.values():
        assert len(set(cert.signers)) >= quorum
        fields = (cert.phase, cert.view, cert.seq, cert.digest, cert.signers)
        assert bls_ref.certificate_valid(n, f, *fields, cert.agg_sig)
        assert not bls_ref.certificate_valid(n, f, *fields,
                                             _swapped(com.keys, cert))


def _committee_spans(qc_mode: bool, monkeypatch):
    """One n=4 committee's puts; the span summaries it left, and how many
    shares were signed and certificates built meanwhile."""
    made = {"shares": 0, "certs": 0}
    real_sign, real_build = qc_mod.sign_share, qc_mod.build_qc

    def sign_share(*args):
        made["shares"] += 1
        return real_sign(*args)

    def build_qc(*args):
        cert = real_build(*args)
        made["certs"] += cert is not None
        return cert

    monkeypatch.setattr(qc_mod, "sign_share", sign_share)
    monkeypatch.setattr(qc_mod, "build_qc", build_qc)

    async def scenario():
        com = LocalCommittee.build(n=4, clients=1, qc_mode=qc_mode,
                                   view_timeout=30.0)
        com.clients[0].request_timeout = 30.0
        spans.configure("test")
        com.start()
        try:
            for i in range(3):
                assert await com.clients[0].submit(f"put s{i} {i}") == "ok"
            await asyncio.sleep(0.3)
        finally:
            await com.stop()

    _drive(scenario())
    return spans.stage_summaries(), made


def test_the_certificate_spans_count_each_share_and_each_build(monkeypatch):
    summaries, made = _committee_spans(True, monkeypatch)
    assert made["shares"] > 0 and made["certs"] >= 6  # two phases, 3 blocks
    assert summaries[spans.QC_SIGN_SHARE]["count"] == made["shares"]
    assert summaries[spans.QC_AGGREGATE]["count"] == made["certs"]
    assert summaries[spans.QC_SIGN_SHARE]["mean"] > 0


def test_a_plain_committee_records_no_certificate_spans(monkeypatch):
    summaries, made = _committee_spans(False, monkeypatch)
    assert made == {"shares": 0, "certs": 0}
    assert spans.QC_SIGN_SHARE not in summaries
    assert spans.QC_AGGREGATE not in summaries


@pytest.fixture(scope="module")
def rehearsal_qc():
    """What a rehearsal-qc run's served_stage returned (the spy of
    benchmark/tests/test_config_keys.py), and its result line."""
    served: dict = {}
    real = stages.served_stage

    async def spy(*args, **kwargs):
        served.update(await real(*args, **kwargs))
        return served

    stages.served_stage = spy
    try:
        result = run.run(argparse.Namespace(
            workload="rehearsal-qc", seed=3000000043, seconds=3.0, trace=0))
    finally:
        stages.served_stage = real
    return result, served


@pytest.mark.parametrize("name", METRICS)
def test_each_certificate_metric_reads_a_rehearsal(rehearsal_qc, name):
    result, served = rehearsal_qc
    assert result["correct"] is True and result["failed"] == 0
    seen = {"spans": served["spans"], "counters": served["counters"],
            "trace": {},
            "divisors": {"committed": served["window"]["committed"]}}
    spec = run.load("metrics", name)
    assert spec["cells"] == [CELL] and spec["layer"] == "certificates"
    value = run.read_metric(spec, seen)
    assert value is not None and value > 0
    if name == "qc_share_sign_us_per_req":
        # the span is nested in the loop.sign_vote accumulator
        sign_vote = run.read_metric(run.load("metrics",
                                             "loop_sign_vote_us_per_req"), seen)
        assert value <= sign_vote
