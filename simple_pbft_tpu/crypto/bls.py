"""BLS12-381 aggregate signatures — the 256-node quorum-certificate path.

BASELINE.md config 4: at committee sizes where even batched Ed25519 means
verifying hundreds of votes per quorum certificate, BLS aggregation
collapses a whole QC to ONE pairing check: every replica signs the same
(view, seq, digest) payload, signatures aggregate by point addition, and

    e(agg_sig, G2) == e(H(m), agg_pk)

verifies the entire certificate at once. The reference has no signatures
at all (SURVEY.md §2.1); this module is new framework infrastructure,
implemented from the curve up because the environment ships no pairing
library:

- Fp -> Fp2 -> Fp6 -> Fp12 tower (u^2 = -1, v^3 = u+1, w^2 = v).
- G1: y^2 = x^3 + 4 over Fp; G2: y^2 = x^3 + 4(u+1) over Fp2 (M-twist).
- Optimal ate pairing: Miller loop over the BLS parameter
  x = -0xd201000000010000, naive final exponentiation f^((p^12-1)/r).
  Pure Python bigints here; the verify entry points route through the
  native C++ library (native/bls381.cpp — Montgomery 6x64 limbs, same
  tower and Miller structure, ~12x faster: ~60 ms vs ~750 ms per
  aggregate check) and fall back to this module when no toolchain is
  present. The two paths are differentially tested against each other
  (tests/test_bls.py). (A TPU pairing is exploratory future work; the
  seam keeps it pluggable.)
- Min-sig variant: signatures in G1 (96 B uncompressed), pubkeys in G2
  (192 B) — QCs ship signatures, so signatures get the small group.
- Rogue-key defense: proof-of-possession (sign your own pubkey under a
  separate domain tag). Committee setup must verify PoPs before trusting
  an aggregate (verify_pop), matching the draft-irtf-cfrg-bls-signature
  PoP scheme's structure.

Correctness is anchored by algebraic self-tests (tests/test_bls.py):
generator orders, tower inverses, pairing bilinearity
e(aP, bQ) = e(P, Q)^{ab}, and aggregate soundness under wrong-key /
wrong-message corruption.
"""

from __future__ import annotations

import hashlib
import secrets
from typing import List, Optional, Sequence, Tuple

# -- base field / curve constants -------------------------------------------

P = 0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFAAAB
R_ORDER = 0x73EDA753299D7D483339D80809A1D80553BDA402FFFE5BFEFFFFFFFF00000001
BLS_X = 0xD201000000010000  # |x|; the BLS parameter itself is -x
H_EFF_G1 = 0x396C8C005555E1568C00AAAB0000AAAB  # G1 cofactor

G1_GEN = (
    0x17F1D3A73197D7942695638C4FA9AC0FC3688C4F9774B905A14E3A3F171BAC586C55E83FF97A1AEFFB3AF00ADB22C6BB,
    0x08B3F481E3AAA0F1A09E30ED741D8AE4FCF5E095D5D00AF600DB18CB2C04B3EDD03CC744A2888AE40CAA232946C5E7E1,
)
G2_GEN = (
    (
        0x024AA2B2F08F0A91260805272DC51051C6E47AD4FA403B02B4510B647AE3D1770BAC0326A805BBEFD48056C8C121BDB8,
        0x13E02B6052719F607DACD3A088274F65596BD0D09920B61AB5DA61BBDC7F5049334CF11213945D57E5AC7D055D042B7E,
    ),
    (
        0x0CE5D527727D6E118CC9CDC6DA2E351AADFD9BAA8CBDD3A76D429A695160D12C923AC9CC3BACA289E193548608B82801,
        0x0606C4A02EA734CC32ACD2B02BC28B99CB3E287E85A763AF267492AB572E99AB3F370D275CEC1DA1AAA9075FF05F79BE,
    ),
)

DST_SIG = b"SIMPLE_PBFT_BLS_SIG_"
DST_POP = b"SIMPLE_PBFT_BLS_POP_"


def _native():
    """The C++ pairing library (native/bls381.cpp, ~12x this module's
    bigint path per verify) — lazily imported so the pure-Python module
    stays importable standalone; every verify falls back here when the
    toolchain is absent."""
    from .. import native

    return native


# -- Fp2 = Fp[u]/(u^2+1) -----------------------------------------------------
# Elements are (a, b) = a + b*u with a, b in Fp.


def f2_add(x, y):
    return ((x[0] + y[0]) % P, (x[1] + y[1]) % P)


def f2_sub(x, y):
    return ((x[0] - y[0]) % P, (x[1] - y[1]) % P)


def f2_neg(x):
    return ((-x[0]) % P, (-x[1]) % P)


def f2_mul(x, y):
    a0, a1 = x
    b0, b1 = y
    return ((a0 * b0 - a1 * b1) % P, (a0 * b1 + a1 * b0) % P)


def f2_sq(x):
    a0, a1 = x
    return ((a0 + a1) * (a0 - a1) % P, 2 * a0 * a1 % P)


def f2_muls(x, s: int):
    return (x[0] * s % P, x[1] * s % P)


def f2_inv(x):
    a0, a1 = x
    d = pow(a0 * a0 + a1 * a1, P - 2, P)
    return (a0 * d % P, (-a1 * d) % P)


F2_ZERO = (0, 0)
F2_ONE = (1, 0)
XI = (1, 1)  # v^3 = xi = 1 + u


def f2_mul_xi(x):
    """x * (1+u)."""
    a0, a1 = x
    return ((a0 - a1) % P, (a0 + a1) % P)


# -- Fp6 = Fp2[v]/(v^3 - xi) -------------------------------------------------
# Elements are (c0, c1, c2) = c0 + c1*v + c2*v^2, ci in Fp2.

F6_ZERO = (F2_ZERO, F2_ZERO, F2_ZERO)
F6_ONE = (F2_ONE, F2_ZERO, F2_ZERO)


def f6_add(x, y):
    return (f2_add(x[0], y[0]), f2_add(x[1], y[1]), f2_add(x[2], y[2]))


def f6_sub(x, y):
    return (f2_sub(x[0], y[0]), f2_sub(x[1], y[1]), f2_sub(x[2], y[2]))


def f6_neg(x):
    return (f2_neg(x[0]), f2_neg(x[1]), f2_neg(x[2]))


def f6_mul(x, y):
    a0, a1, a2 = x
    b0, b1, b2 = y
    t00 = f2_mul(a0, b0)
    t11 = f2_mul(a1, b1)
    t22 = f2_mul(a2, b2)
    c0 = f2_add(t00, f2_mul_xi(f2_add(f2_mul(a1, b2), f2_mul(a2, b1))))
    c1 = f2_add(f2_add(f2_mul(a0, b1), f2_mul(a1, b0)), f2_mul_xi(t22))
    c2 = f2_add(f2_add(f2_mul(a0, b2), f2_mul(a2, b0)), t11)
    return (c0, c1, c2)


def f6_mul_v(x):
    """x * v: (c0, c1, c2) -> (xi*c2, c0, c1)."""
    return (f2_mul_xi(x[2]), x[0], x[1])


def f6_inv(x):
    a0, a1, a2 = x
    t0 = f2_sub(f2_sq(a0), f2_mul_xi(f2_mul(a1, a2)))
    t1 = f2_sub(f2_mul_xi(f2_sq(a2)), f2_mul(a0, a1))
    t2 = f2_sub(f2_sq(a1), f2_mul(a0, a2))
    delta = f2_add(
        f2_mul(a0, t0),
        f2_mul_xi(f2_add(f2_mul(a1, t2), f2_mul(a2, t1))),
    )
    dinv = f2_inv(delta)
    return (f2_mul(t0, dinv), f2_mul(t1, dinv), f2_mul(t2, dinv))


# -- Fp12 = Fp6[w]/(w^2 - v) -------------------------------------------------
# Elements are (d0, d1) = d0 + d1*w, di in Fp6.

F12_ONE = (F6_ONE, F6_ZERO)


def f12_mul(x, y):
    a0, a1 = x
    b0, b1 = y
    t0 = f6_mul(a0, b0)
    t1 = f6_mul(a1, b1)
    c0 = f6_add(t0, f6_mul_v(t1))
    c1 = f6_add(f6_mul(a0, b1), f6_mul(a1, b0))
    return (c0, c1)


def f12_sq(x):
    return f12_mul(x, x)


def f12_conj(x):
    """Conjugation a - b*w = Frobenius^6 (used for the negative BLS x)."""
    return (x[0], f6_neg(x[1]))


def f12_inv(x):
    a0, a1 = x
    d = f6_inv(f6_sub(f6_mul(a0, a0), f6_mul_v(f6_mul(a1, a1))))
    return (f6_mul(a0, d), f6_neg(f6_mul(a1, d)))


def f12_pow(x, e: int):
    out = F12_ONE
    base = x
    while e:
        if e & 1:
            out = f12_mul(out, base)
        base = f12_sq(base)
        e >>= 1
    return out


# -- curve points ------------------------------------------------------------
# Affine tuples; None is the point at infinity. Generic over the field via
# the (add, sub, mul, sq, inv, ...) ops passed in — G1 uses Fp ints, G2
# uses Fp2 pairs. Jacobian coordinates for scalar multiplication.


class _Curve:
    """y^2 = x^3 + b over a field given by its op table."""

    def __init__(self, b, zero, one, add, sub, neg, mul, sq, inv, muls):
        self.b = b
        self.zero, self.one = zero, one
        self.add, self.sub, self.neg = add, sub, neg
        self.mul, self.sq, self.inv, self.muls = mul, sq, inv, muls

    def is_on_curve(self, pt) -> bool:
        if pt is None:
            return True
        x, y = pt
        return self.sq(y) == self.add(self.mul(self.sq(x), x), self.b)

    def add_pts(self, p1, p2):
        if p1 is None:
            return p2
        if p2 is None:
            return p1
        x1, y1 = p1
        x2, y2 = p2
        if x1 == x2:
            if y1 != y2:
                return None
            if y1 == self.zero:
                return None
            lam = self.mul(
                self.muls(self.sq(x1), 3), self.inv(self.muls(y1, 2))
            )
        else:
            lam = self.mul(self.sub(y2, y1), self.inv(self.sub(x2, x1)))
        x3 = self.sub(self.sub(self.sq(lam), x1), x2)
        y3 = self.sub(self.mul(lam, self.sub(x1, x3)), y1)
        return (x3, y3)

    def neg_pt(self, pt):
        if pt is None:
            return None
        return (pt[0], self.neg(pt[1]))

    def mul_pt(self, pt, k: int):
        """Double-and-add in Jacobian coordinates (one inversion total).
        `k` is used as-is (callers reduce mod r when appropriate; the
        cofactor-clearing multiply must NOT be reduced)."""
        if pt is None or k == 0:
            return None
        if k < 0:
            return self.neg_pt(self.mul_pt(pt, -k))
        X, Y, Z = pt[0], pt[1], self.one
        acc = None  # (X, Y, Z) or None
        for bit in bin(k)[2:]:
            if acc is not None:
                acc = self._jdbl(acc)
            if bit == "1":
                acc = (X, Y, Z) if acc is None else self._jadd(acc, (X, Y, Z))
        if acc is None:
            return None
        Xa, Ya, Za = acc
        zi = self.inv(Za)
        zi2 = self.sq(zi)
        return (self.mul(Xa, zi2), self.mul(Ya, self.mul(zi2, zi)))

    def _jdbl(self, p):
        X1, Y1, Z1 = p
        A = self.sq(X1)
        B = self.sq(Y1)
        C = self.sq(B)
        D = self.muls(
            self.sub(self.sub(self.sq(self.add(X1, B)), A), C), 2
        )
        E = self.muls(A, 3)
        F = self.sq(E)
        X3 = self.sub(F, self.muls(D, 2))
        Y3 = self.sub(self.mul(E, self.sub(D, X3)), self.muls(C, 8))
        Z3 = self.muls(self.mul(Y1, Z1), 2)
        return (X3, Y3, Z3)

    def _jadd(self, p, q):
        X1, Y1, Z1 = p
        X2, Y2, Z2 = q
        Z1Z1 = self.sq(Z1)
        Z2Z2 = self.sq(Z2)
        U1 = self.mul(X1, Z2Z2)
        U2 = self.mul(X2, Z1Z1)
        S1 = self.mul(self.mul(Y1, Z2), Z2Z2)
        S2 = self.mul(self.mul(Y2, Z1), Z1Z1)
        if U1 == U2:
            if S1 != S2:
                # p + (-p): infinity — encode as Z = 0 then handled by
                # caller via exception; in-subgroup scalar mults never hit
                # this mid-ladder for k < r
                raise ZeroDivisionError("point at infinity in ladder")
            return self._jdbl(p)
        H = self.sub(U2, U1)
        I = self.sq(self.muls(H, 2))
        J = self.mul(H, I)
        rr = self.muls(self.sub(S2, S1), 2)
        V = self.mul(U1, I)
        X3 = self.sub(self.sub(self.sq(rr), J), self.muls(V, 2))
        Y3 = self.sub(
            self.mul(rr, self.sub(V, X3)), self.muls(self.mul(S1, J), 2)
        )
        Z3 = self.muls(self.mul(H, self.mul(Z1, Z2)), 2)
        return (X3, Y3, Z3)


def _fp_ops():
    return dict(
        zero=0,
        one=1,
        add=lambda a, b: (a + b) % P,
        sub=lambda a, b: (a - b) % P,
        neg=lambda a: (-a) % P,
        mul=lambda a, b: a * b % P,
        sq=lambda a: a * a % P,
        inv=lambda a: pow(a, P - 2, P),
        muls=lambda a, s: a * s % P,
    )


G1 = _Curve(b=4, **_fp_ops())
G2 = _Curve(
    b=f2_muls(XI, 4),  # 4(1+u)
    zero=F2_ZERO,
    one=F2_ONE,
    add=f2_add,
    sub=f2_sub,
    neg=f2_neg,
    mul=f2_mul,
    sq=f2_sq,
    inv=f2_inv,
    muls=f2_muls,
)


# -- pairing -----------------------------------------------------------------


def _untwist(q):
    """E'(Fp2) -> E(Fp12): (x', y') -> (x'/w^2, y'/w^3).

    With w^2 = v and v^3 = xi this lands on y^2 = x^3 + 4. Inverses of w
    powers: 1/v = v^2/xi, so x'/w^2 = x' * v^2/xi (an Fp6 scalar) and
    y'/w^3 = y' * v^2/xi * 1/w with 1/w = w/v = w * v^2/xi.
    """
    x2, y2 = q
    xi_inv = f2_inv(XI)
    # x'/w^2 = x'/v = x' * v^2/xi — the v^2 slot of the Fp6 part
    x6 = (F2_ZERO, F2_ZERO, f2_mul(x2, xi_inv))
    x12 = (x6, F6_ZERO)
    # y'/w^3 = y'/(v*w) = y' * (v/xi) * w — the v^1 slot of the w part
    y6 = (F2_ZERO, f2_mul(y2, xi_inv), F2_ZERO)
    y12 = (F6_ZERO, y6)
    return (x12, y12)


def _embed_fp(a: int):
    """Fp -> Fp12."""
    return (((a % P, 0), F2_ZERO, F2_ZERO), F6_ZERO)


def _f12_point_from_g1(p):
    return (_embed_fp(p[0]), _embed_fp(p[1]))


def f12_add_el(x, y):
    return (f6_add(x[0], y[0]), f6_add(x[1], y[1]))


def f12_sub_el(x, y):
    return (f6_sub(x[0], y[0]), f6_sub(x[1], y[1]))


def _linefunc(r1, r2, pt):
    """Evaluate the line through r1, r2 (Fp12 points) at pt. Mirrors the
    textbook Miller-loop line function with its three cases (chord,
    tangent, vertical)."""
    x1, y1 = r1
    x2, y2 = r2
    xt, yt = pt
    if x1 != x2:
        lam = f12_mul(f12_sub_el(y2, y1), f12_inv(f12_sub_el(x2, x1)))
        return f12_sub_el(
            f12_mul(lam, f12_sub_el(xt, x1)), f12_sub_el(yt, y1)
        )
    if y1 == y2:
        three_x2 = f12_mul(_embed_fp(3), f12_mul(x1, x1))
        lam = f12_mul(three_x2, f12_inv(f12_mul(_embed_fp(2), y1)))
        return f12_sub_el(
            f12_mul(lam, f12_sub_el(xt, x1)), f12_sub_el(yt, y1)
        )
    return f12_sub_el(xt, x1)  # vertical line


_E12 = _Curve(
    b=(((4, 4), F2_ZERO, F2_ZERO), F6_ZERO),  # unused for adds below
    zero=(F6_ZERO, F6_ZERO),
    one=F12_ONE,
    add=f12_add_el,
    sub=f12_sub_el,
    neg=lambda x: (f6_neg(x[0]), f6_neg(x[1])),
    mul=f12_mul,
    sq=f12_sq,
    inv=f12_inv,
    muls=lambda x, s: f12_mul(x, _embed_fp(s)),
)

FINAL_EXP = (P**12 - 1) // R_ORDER


def pairing(p1, q2) -> Tuple:
    """e(P, Q) for P in G1, Q in G2 (affine tuples; None = infinity).
    Returns an Fp12 element (F12_ONE for degenerate inputs)."""
    f = _miller(p1, q2)
    return f12_pow(f, FINAL_EXP)


def _miller(p1, q2):
    if p1 is None or q2 is None:
        return F12_ONE
    q = _untwist(q2)
    pt = _f12_point_from_g1(p1)
    f = F12_ONE
    r = q
    for bit in bin(BLS_X)[3:]:
        f = f12_mul(f12_sq(f), _linefunc(r, r, pt))
        r = _E12.add_pts(r, r)
        if bit == "1":
            f = f12_mul(f, _linefunc(r, q, pt))
            r = _E12.add_pts(r, q)
    return f12_conj(f)  # BLS parameter is negative


def pairings_equal(a1, a2, b1, b2) -> bool:
    """e(a1, a2) == e(b1, b2) via one shared final exponentiation:
    e(a1, a2) * e(-b1, b2) == 1."""
    if a1 is None or a2 is None:
        return b1 is None or b2 is None
    if b1 is None or b2 is None:
        return False
    f = f12_mul(_miller(a1, a2), _miller(G1.neg_pt(b1), b2))
    return f12_pow(f, FINAL_EXP) == F12_ONE


# -- hash to G1 (try-and-increment + cofactor clearing) ----------------------


def hash_to_g1(msg: bytes, dst: bytes = DST_SIG):
    ctr = 0
    while True:
        h = hashlib.sha256(dst + ctr.to_bytes(4, "big") + msg).digest()
        h2 = hashlib.sha256(dst + ctr.to_bytes(4, "big") + msg + b"\x01").digest()
        x = int.from_bytes(h + h2, "big") % P
        y2 = (x * x * x + 4) % P
        y = pow(y2, (P + 1) // 4, P)  # p % 4 == 3
        if y * y % P == y2:
            pt = (x, min(y, P - y))
            out = G1.mul_pt(pt, H_EFF_G1)  # clear cofactor into the subgroup
            if out is not None:
                return out
        ctr += 1


# -- BLS signature scheme (min-sig: signatures in G1, pubkeys in G2) ---------

G1_BYTES = 96  # uncompressed x || y, 48 B each, big-endian
G2_BYTES = 192  # x0 || x1 || y0 || y1


def _g1_to_bytes(pt) -> bytes:
    if pt is None:
        return b"\x00" * G1_BYTES
    return pt[0].to_bytes(48, "big") + pt[1].to_bytes(48, "big")


def _g1_from_bytes(raw: bytes):
    if len(raw) != G1_BYTES:
        return None
    if raw == b"\x00" * G1_BYTES:
        return None  # infinity encoding — rejected by verifiers below
    x = int.from_bytes(raw[:48], "big")
    y = int.from_bytes(raw[48:], "big")
    if x >= P or y >= P:
        return None
    pt = (x, y)
    if not G1.is_on_curve(pt):
        return None
    return pt


def _g2_to_bytes(pt) -> bytes:
    if pt is None:
        return b"\x00" * G2_BYTES
    (x0, x1), (y0, y1) = pt
    return b"".join(v.to_bytes(48, "big") for v in (x0, x1, y0, y1))


def _g2_from_bytes(raw: bytes):
    if len(raw) != G2_BYTES:
        return None
    if raw == b"\x00" * G2_BYTES:
        return None
    vals = [int.from_bytes(raw[i * 48 : (i + 1) * 48], "big") for i in range(4)]
    if any(v >= P for v in vals):
        return None
    pt = ((vals[0], vals[1]), (vals[2], vals[3]))
    if not G2.is_on_curve(pt):
        return None
    return pt


def keygen(seed: bytes) -> Tuple[int, bytes]:
    """seed (>=32 bytes) -> (secret scalar, pubkey bytes)."""
    if len(seed) < 32:
        raise ValueError("BLS seed must be >= 32 bytes")
    sk = int.from_bytes(
        hashlib.sha512(b"SIMPLE_PBFT_BLS_KEYGEN" + seed).digest(), "big"
    ) % R_ORDER
    if sk == 0:
        sk = 1
    pk = _native().bls_pubkey(sk)
    if pk is None:
        pk = _g2_to_bytes(G2.mul_pt(G2_GEN, sk))
    return sk, pk


def sign(sk: int, msg: bytes) -> bytes:
    s = _native().bls_sign(sk, msg, DST_SIG)
    if s is not None:
        return s
    return _g1_to_bytes(G1.mul_pt(hash_to_g1(msg), sk))


def sign_many(sks: Sequence[int], msgs: Sequence[bytes]) -> List[bytes]:
    """``[sign(sk, m) for sk, m in zip(sks, msgs)]``, byte for byte, with
    one native call for the pile; an item the native path refuses takes
    ``sign``'s own fallback."""
    got = _native().bls_sign_many(sks, msgs, DST_SIG)
    if got is None:
        got = [None] * len(sks)
    return [s if s is not None else sign(sk, m)
            for s, sk, m in zip(got, sks, msgs)]


def pop_prove(sk: int, pubkey: bytes) -> bytes:
    """Proof of possession: sign your own pubkey under the PoP domain."""
    s = _native().bls_sign(sk, pubkey, DST_POP)
    if s is not None:
        return s
    return _g1_to_bytes(G1.mul_pt(hash_to_g1(pubkey, DST_POP), sk))


def _subgroup_check_g1(pt) -> bool:
    try:
        return G1.mul_pt(pt, R_ORDER - 1) == G1.neg_pt(pt)
    except ZeroDivisionError:  # hit infinity mid-ladder: order divides r-1
        return False


def _subgroup_check_g2(pt) -> bool:
    try:
        return G2.mul_pt(pt, R_ORDER - 1) == G2.neg_pt(pt)
    except ZeroDivisionError:
        return False


def pop_verify(pubkey: bytes, pop: bytes) -> bool:
    r = _native().bls_verify_one(pubkey, pubkey, pop, DST_POP, check_pk=True)
    if r is not None:
        return r
    pk = _g2_from_bytes(pubkey)
    sig = _g1_from_bytes(pop)
    if pk is None or sig is None:
        return False
    if not (_subgroup_check_g2(pk) and _subgroup_check_g1(sig)):
        return False
    return pairings_equal(sig, G2_GEN, hash_to_g1(pubkey, DST_POP), pk)


def verify(pubkey: bytes, msg: bytes, sig: bytes) -> bool:
    r = _native().bls_verify_one(pubkey, msg, sig, DST_SIG, check_pk=False)
    if r is not None:
        return r
    pk = _g2_from_bytes(pubkey)
    s = _g1_from_bytes(sig)
    if pk is None or s is None:
        return False
    if not _subgroup_check_g1(s):
        return False
    return pairings_equal(s, G2_GEN, hash_to_g1(msg), pk)


def aggregate_signatures(sigs: Sequence[bytes]) -> Optional[bytes]:
    acc = None
    for raw in sigs:
        pt = _g1_from_bytes(raw)
        if pt is None:
            return None
        acc = G1.add_pts(acc, pt)
    return _g1_to_bytes(acc) if acc is not None else None


def aggregate_pubkeys(pubkeys: Sequence[bytes]):
    acc = None
    for raw in pubkeys:
        pt = _g2_from_bytes(raw)
        if pt is None:
            return None
        acc = G2.add_pts(acc, pt)
    return acc


def verify_aggregate(
    pubkeys: Sequence[bytes], msg: bytes, agg_sig: bytes
) -> bool:
    """ONE pairing check for a whole quorum certificate: every listed
    pubkey signed `msg` (same-message aggregation; callers must have
    verified each pubkey's proof of possession at setup — rogue-key
    defense)."""
    if not pubkeys:
        return False
    r = _native().bls_verify_aggregate(pubkeys, msg, agg_sig, DST_SIG)
    if r is not None:
        return r
    s = _g1_from_bytes(agg_sig)
    if s is None or not _subgroup_check_g1(s):
        return False
    agg_pk = aggregate_pubkeys(pubkeys)
    if agg_pk is None:
        return False
    return pairings_equal(s, G2_GEN, hash_to_g1(msg), agg_pk)


# -- batched aggregate verification (QC-plane fast path) ---------------------
#
# k pending quorum certs over the SAME signer set collapse to TWO Miller
# loops via a random linear combination: with secret 128-bit coefficients
# r_i drawn per check,
#
#     e(sum r_i * sig_i, G2) == e(sum r_i * H(m_i), agg_pk)
#
# holds for honest certs by bilinearity, and an invalid cert slips through
# only if its error component happens to cancel under coefficients chosen
# AFTER the certs were fixed — probability 2^-128 per check. Certs with
# different signer sets group separately (two Miller loops per distinct
# set; under consensus traffic the quorum is almost always the same 2f+1
# replicas, so the common case is one group). A failed group check falls
# back to halving: log2(k) RLC checks isolate one bad cert instead of k
# full pairings (the certificate-level analog of qc.bisect_bad_shares).

RLC_SCALAR_BITS = 128

#: one batch entry: (signer pubkeys, signed payload, aggregate signature)
BatchEntry = Tuple[Sequence[bytes], bytes, bytes]


def _rlc_scalar() -> int:
    """Secret nonzero random coefficient — must be unpredictable to the
    cert producer or the soundness argument collapses."""
    return 1 + secrets.randbelow((1 << RLC_SCALAR_BITS) - 1)


def _rlc_check(pk_set: Tuple[bytes, ...], ents: List[BatchEntry]) -> bool:
    """One RLC multi-pairing over entries sharing a signer set. False
    means "at least one cert is bad OR an input was structurally
    rejected" — callers split and retry, bottoming out at single-cert
    verify_aggregate, so a structural reject can never mislabel a good
    sibling."""
    rands = [_rlc_scalar() for _ in ents]
    r = _native().bls_verify_batch_rlc(
        list(pk_set),
        [e[1] for e in ents],
        [e[2] for e in ents],
        rands,
        DST_SIG,
    )
    if r is not None:
        return r
    # pure-Python fallback (differential oracle for the native path)
    s_acc = None
    m_acc = None
    for (_, msg, agg_sig), ri in zip(ents, rands):
        sig_pt = _g1_from_bytes(agg_sig)
        if sig_pt is None or not _subgroup_check_g1(sig_pt):
            return False
        s_acc = G1.add_pts(s_acc, G1.mul_pt(sig_pt, ri))
        m_acc = G1.add_pts(m_acc, G1.mul_pt(hash_to_g1(msg), ri))
    if s_acc is None or m_acc is None:
        # degenerate combination (vanishing accumulator): cannot certify
        # anything from it — force the per-cert path
        return False
    agg_pk = aggregate_pubkeys(pk_set)
    if agg_pk is None:
        return False
    return pairings_equal(s_acc, G2_GEN, m_acc, agg_pk)


def _resolve_group(
    entries: Sequence[BatchEntry],
    pk_set: Tuple[bytes, ...],
    idxs: List[int],
    out: List[bool],
) -> None:
    """Fill verdicts for one signer-set group: one RLC check when it
    holds, halving recursion when it fails (a single bad cert in k costs
    ~2*log2(k) batch checks, not k pairings)."""
    if len(idxs) == 1:
        i = idxs[0]
        out[i] = verify_aggregate(list(pk_set), entries[i][1], entries[i][2])
        return
    if _rlc_check(pk_set, [entries[i] for i in idxs]):
        for i in idxs:
            out[i] = True
        return
    mid = len(idxs) // 2
    _resolve_group(entries, pk_set, idxs[:mid], out)
    _resolve_group(entries, pk_set, idxs[mid:], out)


def verify_aggregates_batch(entries: Sequence[BatchEntry]) -> List[bool]:
    """Per-cert verdicts for k pending quorum certificates, batched: 2
    Miller loops per distinct signer set instead of 2 per cert, with a
    halving fallback isolating bad certs when a group check fails.
    Differentially tested against single-cert verify_aggregate
    (tests/test_bls_batch.py)."""
    out = [False] * len(entries)
    groups: "dict[Tuple[bytes, ...], List[int]]" = {}
    for i, (pks, _msg, _sig) in enumerate(entries):
        if not pks:
            continue  # structurally empty signer set: stays False
        groups.setdefault(tuple(pks), []).append(i)
    for pk_set, idxs in groups.items():
        _resolve_group(entries, pk_set, idxs, out)
    return out


def verify_aggregates_all(entries: Sequence[BatchEntry]) -> bool:
    """All-or-nothing batch check: True iff EVERY cert verifies. On any
    group failure it returns False WITHOUT bisecting — the certificate-
    validation path (a NEW-VIEW's embedded QCs) needs only the boolean,
    and early rejection keeps a Byzantine certificate stuffed with
    fabricated aggregates at one batch check, not k pairings."""
    groups: "dict[Tuple[bytes, ...], List[BatchEntry]]" = {}
    for ent in entries:
        if not ent[0]:
            return False
        groups.setdefault(tuple(ent[0]), []).append(ent)
    for pk_set, ents in groups.items():
        if len(ents) == 1:
            if not verify_aggregate(list(pk_set), ents[0][1], ents[0][2]):
                return False
        elif not _rlc_check(pk_set, ents):
            return False
    return True
