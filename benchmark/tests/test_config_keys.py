"""A configuration's stated keys are built or refused: the certificate
committee (``signatures: bls12-381-qc``) end to end on the CPU, its set-up
gate against the plain reference, a refusal for every key the harness
cannot build, and the manifest's files against what the program builds."""

import json
import os
import re

import pytest

import bls_ref
import run
import stages

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
V5E_BYTES_LIMIT = 16_909_336_064  # a v5e's memory_stats()["bytes_limit"]


def _args(workload, seed, seconds="3"):
    return ["--workload", workload, "--seed", seed, "--seconds", seconds,
            "--trace", "0"]


def _run(capsys, monkeypatch, workload, seed):
    """One rehearsal run; its result line, its output, and what
    served_stage returned (the counters over the window)."""
    served: dict = {}
    real = stages.served_stage

    async def spy(*args, **kwargs):
        served.update(await real(*args, **kwargs))
        return served

    monkeypatch.setattr(stages, "served_stage", spy)
    run.main(_args(workload, seed))
    out = capsys.readouterr().out
    return json.loads(out.strip().splitlines()[-1]), out, served


def test_a_certificate_committee_runs_end_to_end(capsys, monkeypatch):
    result, out, served = _run(capsys, monkeypatch, "rehearsal-qc",
                               "3000000023")
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert "signatures=bls12-381-qc" in out and "check: failed" not in out
    assert "qc: compared=8" in out
    counters = served["counters"]
    assert counters["qc"]["submitted"] > 0
    assert counters["qc"]["verified_true"] > 0
    assert counters["wire"]["qc.sent_msgs"] > 0
    assert counters["replicas"]["qcs_formed"] > 0
    seen = {"counters": counters, "divisors": {}}
    spec = {"name": "qc_batches", "source": "counter:qc.batches"}
    assert run.read_metric(spec, seen) == counters["qc"]["batches"] > 0


def test_a_plain_committee_has_no_certificate_counters(capsys, monkeypatch):
    from simple_pbft_tpu.consensus import qc

    monkeypatch.setattr(qc, "_lane", None)  # as in a fresh process
    result, out, served = _run(capsys, monkeypatch, "rehearsal", "3000000029")
    assert result["correct"] is True
    assert "signatures=ed25519" in out and "qc:" not in out
    assert served["counters"]["qc"] == {}
    assert not any(k.startswith("qc.") for k in served["counters"]["wire"])
    spec = {"name": "qc_batches", "source": "counter:qc.batches"}
    assert run.read_metric(spec, {"counters": served["counters"]}) is None
    assert qc.lane_snapshot() is None  # the harness made no lane


def test_a_flipped_planted_certificate_verdict_is_not_correct(
        capsys, monkeypatch):
    from simple_pbft_tpu.crypto import bls

    real = bls.verify_aggregates_batch
    flipped = []

    def lenient(entries):
        got = real(entries)
        if not flipped and False in got:
            got[got.index(False)] = True
            flipped.append(True)
        return got

    monkeypatch.setattr(bls, "verify_aggregates_batch", lenient)
    result, out, _served = _run(capsys, monkeypatch, "rehearsal-qc",
                                "3000000031")
    assert flipped and result["correct"] is False
    assert "lane says True, oracle says False" in out


def test_the_served_committee_is_held_to_the_file(capsys, monkeypatch):
    """The committee served_stage builds and times is checked itself, not
    only the key set-up's: one that runs without speculation is refused."""
    from simple_pbft_tpu.committee import LocalCommittee

    real = LocalCommittee.build

    def unspeculative(**kwargs):
        return real(speculative=False, **kwargs)

    monkeypatch.setattr(LocalCommittee, "build", staticmethod(unspeculative))
    with pytest.raises(SystemExit, match=r"speculative is 'default' where "
                       r"the harness builds False"):
        run.main(_args("rehearsal", "3000000041", "1"))


def _with(config, key, value):
    doc = json.loads(json.dumps(config))
    if key.startswith("verifier."):
        doc["verifier"][key.split(".", 1)[1]] = value
    else:
        doc[key] = value
    return doc


@pytest.mark.parametrize("key, value", [
    ("f", 2),
    ("transport", "tcp"),
    ("processes", 4),
    ("message_delay_ms", 50),
    ("app", "Ledger"),
    ("speculative", "off"),
    ("signatures", "ecdsa-p256"),
    ("verifier.backend", "cpu"),
    ("fault_schedule", "crash r0 at 10 s"),
    ("verifier.key_bank", 128),
])
def test_a_key_the_harness_cannot_build_is_refused(key, value, monkeypatch):
    real = run.load

    def doctored(kind, name):
        doc = real(kind, name)
        return _with(doc, key, value) if kind == "configs" else doc

    monkeypatch.setattr(run, "load", doctored)
    with pytest.raises(SystemExit, match=rf"(^|\s){re.escape(key)} is ") \
            as refused:
        run.main(_args("rehearsal", "3000000037", "1"))
    assert repr(value) in str(refused.value) or key == "fault_schedule"
    if key == "message_delay_ms":
        assert "R5(c)" in str(refused.value)


def _manifest_configs():
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        return [c["name"] for c in json.load(fh)["configs"]]


@pytest.mark.parametrize("name", _manifest_configs())
def test_the_manifest_states_what_the_program_builds(name):
    """Offline, no chip: every stated key but those the device holds,
    and the bank the v5e builds for the published population."""
    from simple_pbft_tpu.config import make_test_committee
    from simple_pbft_tpu.crypto.tpu_verifier import bank_capacity

    config = run.load("configs", name)
    cfg, _keys = make_test_committee(n=config["n"], clients=1)
    population = config["n"] + config["clients"]
    stages.check_config(config, cfg, population)
    vcfg = config["verifier"]
    assert vcfg["signing_keys"] == population
    # node.make_verifier sizes the bank for the population plus 32
    assert vcfg["key_bank"] == bank_capacity(population + 32, V5E_BYTES_LIMIT)
    assert stages.qc_mode(config) is False


@pytest.mark.parametrize("name", ["rehearsal-n4", "rehearsal-n4-bls-qc"])
def test_the_rehearsal_files_state_what_the_cpu_builds(name):
    from simple_pbft_tpu.config import make_test_committee
    from simple_pbft_tpu.crypto.tpu_verifier import bank_capacity

    config = run.load("configs", name)
    qc = stages.qc_mode(config)
    cfg, keys = make_test_committee(n=config["n"], clients=config["clients"],
                                    qc_mode=qc)
    stages.check_config(config, cfg, len(keys))
    assert config["verifier"]["key_bank"] == bank_capacity(len(keys) + 32, None)
    assert qc == (name == "rehearsal-n4-bls-qc")


def test_the_reference_agrees_with_the_programs_scheme():
    """bls_ref derives the keys, the payload and the aggregate on its own;
    on a certificate the program built it says valid, and on each kind the
    gate plants it says invalid."""
    from simple_pbft_tpu.config import make_test_committee
    from simple_pbft_tpu.consensus import qc
    from simple_pbft_tpu.crypto import bls
    from simple_pbft_tpu.messages import qc_payload

    cfg, keys = make_test_committee(n=7, clients=1, qc_mode=True)
    for rid in cfg.replica_ids:
        assert bls.keygen(keys[rid].seed)[0] == bls_ref.secret(rid)
    assert bls_ref.payload("commit", 3, 9, "ab" * 32) == qc_payload(
        "commit", 3, 9, "ab" * 32)
    sks = {r: bls.keygen(keys[r].seed)[0] for r in cfg.replica_ids}
    shares = {r: qc.sign_share(sks[r], "prepare", 2, 11, "cd" * 32)
              for r in ("r0", "r2", "r3", "r5", "r6")}
    cert = qc.build_qc("prepare", 2, 11, "cd" * 32, shares, cfg.quorum)

    def valid(**change):
        fields = dict(phase=cert.phase, view=cert.view, seq=cert.seq,
                      digest=cert.digest, signers=cert.signers,
                      agg_hex=cert.agg_sig)
        fields.update(change)
        return bls_ref.certificate_valid(7, 2, **fields)

    assert valid() is True and qc.verify_qc(cfg, cert) is True
    assert valid(digest="ce" * 32) is False
    assert valid(view=3) is False
    assert valid(signers=["r0", "r2", "r3", "r5", "r1"]) is False
    assert valid(signers=["r0", "r2", "r3", "r5"]) is False  # under 2f+1
    assert valid(signers=["r0", "r2", "r3", "r5", "r5"]) is False
    assert valid(signers=["r0", "r2", "r3", "r5", "r9"]) is False
    assert valid(agg_hex="zz") is False
    assert valid(phase="view") is False
