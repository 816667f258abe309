"""BLS12-381 quorum certificates: the set-up gate and its comparison.

``gate`` builds a seeded batch of ``BATCH`` certificates in set-up, each
2f+1 shares over a QC payload of its own, with three kinds of failure
planted, and verifies the batch through the path the replicas
use (the process lane, ``consensus/qc.py: qc_lane()``). ``compare`` holds
those verdicts, item for item, to the plain reference (``bls_ref.py``)
once the window has closed, so the reference's time is not set-up.
"""

from __future__ import annotations

import hashlib
import random
import time

import bls_ref
from stages import Checks, say

PLANTED = ("share over another payload",
           "names a signer whose share it lacks",
           "digest altered after aggregation")
BATCH = 8  # the planted kinds and five good certificates


def gate(seed: int, cfg, keys) -> list:
    """[(kind, certificate, lane verdict)] for the seeded batch."""
    from simple_pbft_tpu.consensus import qc
    from simple_pbft_tpu.crypto import bls

    rng = random.Random(f"qc:{seed}")
    quorum = cfg.quorum
    ids = list(cfg.replica_ids)
    signers = sorted(rng.sample(ids, quorum), key=ids.index)
    outsider = next(r for r in ids if r not in signers)
    sks = {r: bls.keygen(keys[r].seed)[0] for r in signers + [outsider]}
    spots = dict(zip(rng.sample(range(BATCH), len(PLANTED)), PLANTED))
    certs = []
    for i in range(BATCH):
        phase = ("prepare", "commit")[i % 2]
        view, seq = rng.randrange(1 << 20), 1 + rng.randrange(1 << 30)
        digest = hashlib.sha256(b"benchmark qc %d %d" % (seed, i)).hexdigest()
        kind = spots.get(i, "good")
        shares = {r: qc.sign_share(sks[r], phase, view, seq, digest)
                  for r in signers}
        if kind == PLANTED[0]:
            r = rng.choice(signers)
            shares[r] = qc.sign_share(sks[r], phase, view + 1, seq, digest)
        cert = qc.build_qc(phase, view, seq, digest, shares, quorum)
        if kind == PLANTED[1]:
            r = rng.choice(signers)
            raws = [bytes.fromhex(shares[s]) for s in signers if s != r]
            raws.append(bytes.fromhex(
                qc.sign_share(sks[outsider], phase, view, seq, digest)))
            cert.agg_sig = bls.aggregate_signatures(raws).hex()
        elif kind == PLANTED[2]:
            cert.digest = hashlib.sha256(cert.digest.encode()).hexdigest()
        certs.append((kind, cert))

    lane = qc.qc_lane()
    t0 = time.perf_counter()
    futures = [lane.submit(cfg, cert) for _kind, cert in certs]
    verdicts = [fut.result(timeout=300) for fut in futures]
    say("qc", batch=BATCH, signers=quorum, planted=len(PLANTED),
        lane_ms=round((time.perf_counter() - t0) * 1e3, 1))
    return [(kind, cert, got) for (kind, cert), got in zip(certs, verdicts)]


def compare(checked: list, config: dict, checks: Checks) -> None:
    """Each lane verdict against the plain reference."""
    if not checked:
        return
    n = config["n"]
    f = config.get("f", (n - 1) // 3)
    t0 = time.perf_counter()
    for pos, (kind, cert, got) in enumerate(checked):
        want = bls_ref.certificate_valid(n, f, cert.phase, cert.view, cert.seq,
                                         cert.digest, cert.signers,
                                         cert.agg_sig)
        checks.expect(got == want,
                      f"qc: certificate {pos} ({kind}) lane says {got}, "
                      f"oracle says {want}")
        checks.expect(want == (kind == "good"),
                      f"qc: certificate {pos} ({kind}) is not what it was "
                      f"built to be: oracle says {want}")
    say("qc", compared=len(checked),
        reference_ms=round((time.perf_counter() - t0) * 1e3, 1))
