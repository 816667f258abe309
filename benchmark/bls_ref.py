"""The plain reference for a BLS12-381 quorum certificate.

A certificate names 2f+1 distinct committee members and carries one G1
point. It is valid exactly when that point is the sum of the named
members' signatures over the certificate's payload. The benchmark knows
every member's secret scalar (a test committee's keys follow from the
member's name), so the sum is (sk_1 + ... + sk_k) * H(payload): one
hash to the curve and one scalar multiple, compared byte for byte. No
pairing is needed, and nothing of the program is imported: the field,
the curve, the hash to G1 and the key rule are written out here from the
scheme's definition (min-sig BLS, signatures in G1, try-and-increment
hashing with the program's domain tag).

For a point in the prime-order subgroup this is the pairing check's
verdict: e(sigma, g2) = e(H(m), sum pk_i) holds iff sigma = (sum sk_i) H(m),
since the pairing is non-degenerate and pk_i = sk_i g2. A point outside
the subgroup, off the curve or malformed differs from the expected point,
so both reject it.
"""

from __future__ import annotations

import hashlib
import json

P = 0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFAAAB
R = 0x73EDA753299D7D483339D80809A1D80553BDA402FFFE5BFEFFFFFFFF00000001
H_EFF_G1 = 0x396C8C005555E1568C00AAAB0000AAAB  # G1's cofactor
DST_SIG = b"SIMPLE_PBFT_BLS_SIG_"
PHASES = ("prepare", "commit", "checkpoint")


def _add(p1, p2):
    """Affine addition on y^2 = x^3 + 4 over Fp; None is infinity."""
    if p1 is None:
        return p2
    if p2 is None:
        return p1
    (x1, y1), (x2, y2) = p1, p2
    if x1 == x2:
        if (y1 + y2) % P == 0:
            return None
        lam = 3 * x1 * x1 * pow(2 * y1, -1, P) % P
    else:
        lam = (y2 - y1) * pow(x2 - x1, -1, P) % P
    x3 = (lam * lam - x1 - x2) % P
    return x3, (lam * (x1 - x3) - y1) % P


def _mul(pt, k: int):
    acc = None
    for bit in bin(k)[2:]:
        acc = _add(acc, acc)
        if bit == "1":
            acc = _add(acc, pt)
    return acc


def hash_to_g1(msg: bytes):
    ctr = 0
    while True:
        pre = DST_SIG + ctr.to_bytes(4, "big") + msg
        x = int.from_bytes(hashlib.sha256(pre).digest()
                           + hashlib.sha256(pre + b"\x01").digest(), "big") % P
        y2 = (x * x * x + 4) % P
        y = pow(y2, (P + 1) // 4, P)  # P = 3 mod 4
        if y * y % P == y2:
            out = _mul((x, min(y, P - y)), H_EFF_G1)
            if out is not None:
                return out
        ctr += 1


def secret(member: str) -> int:
    """A test committee member's BLS scalar: its 32-byte seed is its name
    repeated, and the scalar is SHA-512 of the keygen tag and the seed,
    reduced mod r (0 becomes 1)."""
    seed = (member.encode() * 32)[:32]
    sk = int.from_bytes(hashlib.sha512(b"SIMPLE_PBFT_BLS_KEYGEN" + seed)
                        .digest(), "big") % R
    return sk or 1


def payload(phase: str, view: int, seq: int, digest: str) -> bytes:
    """The bytes a share signs: sorted keys, no whitespace, ASCII."""
    return json.dumps({"digest": digest, "phase": phase, "seq": seq,
                       "view": view}, sort_keys=True,
                      separators=(",", ":")).encode()


def certificate_valid(n: int, f: int, phase: str, view: int, seq: int,
                      digest: str, signers, agg_hex: str) -> bool:
    """Whether 2f+1 or more distinct members of r0..r<n-1> signed the
    payload and ``agg_hex`` is the sum of their signatures."""
    members = {f"r{i}" for i in range(n)}
    if (phase not in PHASES or len(set(signers)) != len(signers)
            or len(signers) < 2 * f + 1
            or not set(signers) <= members):
        return False
    try:
        agg = bytes.fromhex(agg_hex)
    except ValueError:
        return False
    scalar = sum(secret(s) for s in signers) % R
    want = _mul(hash_to_g1(payload(phase, view, seq, digest)), scalar)
    if want is None:
        return False
    return agg == want[0].to_bytes(48, "big") + want[1].to_bytes(48, "big")
