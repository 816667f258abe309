"""GF(2^255 - 19) arithmetic in int32 limbs — the TPU field kernel.

TPUs have no 64-bit integer multiply, so field elements are represented as
17 limbs of 15 bits each (17 * 15 = 255 exactly) held in int32. The radix
is chosen so that:

- a limb product fits int32: (2^15 + eps)^2 < 2^31;
- the schoolbook convolution never overflows: each 30-bit product is split
  into (lo = p & 0x7fff, hi = p >> 15) before accumulation, so a column
  sums at most 17 lo-terms (< 2^15) + 17 hi-terms (< 2^16) < 2^21;
- the reduction fold is a clean multiply-by-19: limb position 17 has
  weight 2^255 ≡ 19 (mod p), so high columns fold back as `col * 19`.

Layout: a field element is an int32 array `(17, ...)` — the LIMB axis
leads and batch axes trail. This is the TPU-native choice: XLA maps the
minor-most axis to the 128-wide vector lanes, so with batch minor a
(17, B) element wastes nothing (B is a lane multiple), while the previous
batch-major (B, 17) form padded 17 -> 128 lanes and made every hot-path
intermediate ~7.5x larger in HBM.

All functions are shape-polymorphic over TRAILING batch dimensions and
pure jnp — jittable, vmappable, shardable. Carry ripples are expressed as
tiny unrolled loops over the 17 limbs (static Python loops; the batch
dimension fills the VPU lanes, so per-limb sequential carries vectorize
across the batch).

Normal form ("weak"): limbs 1..16 in [0, 2^15); limb 0 in [0, 2^15 + 19].
`to_canonical` produces the unique representative < p for comparisons and
encoding.

This fills the crypto hot path that the reference lacks entirely (no
signatures anywhere in /root/reference — SURVEY.md §2.1); it is new,
TPU-first code, not a port.
"""

from __future__ import annotations

from typing import List

import jax.numpy as jnp
import numpy as np
from jax import lax

NLIMB = 17
RADIX = 15
MASK = (1 << RADIX) - 1  # 0x7fff
P_INT = 2**255 - 19

DTYPE = jnp.int32


def _int_to_limbs_np(v: int) -> np.ndarray:
    """Host-side: Python int -> (17,) int32 limb array."""
    out = np.zeros(NLIMB, dtype=np.int32)
    for i in range(NLIMB):
        out[i] = v & MASK
        v >>= RADIX
    assert v == 0, "value exceeds 255 bits"
    return out


def _limbs_to_int_np(limbs: np.ndarray) -> int:
    """Host-side inverse (for tests/debug); limb axis leading."""
    v = 0
    for i in reversed(range(NLIMB)):
        # .item(): exact for scalars AND size-1 batch dims (a bare int()
        # on an ndim>0 array is a numpy DeprecationWarning on its way to
        # a TypeError), and loudly fails on a real batch instead of
        # silently folding it
        v = (v << RADIX) | int(np.asarray(limbs[i, ...]).item())
    return v


def bcast(c: np.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """Reshape a (17,) limb constant so it broadcasts against x's
    trailing batch axes: (17,) -> (17, 1, ..., 1)."""
    return jnp.asarray(c).reshape((NLIMB,) + (1,) * (x.ndim - 1))


def const(v: int) -> jnp.ndarray:
    """Embed a Python int < 2^255 as a constant limb array (17,)."""
    return jnp.asarray(_int_to_limbs_np(v % P_INT))


ZERO = _int_to_limbs_np(0)
ONE = _int_to_limbs_np(1)
P_LIMBS = _int_to_limbs_np(P_INT)


def zeros_like(x: jnp.ndarray) -> jnp.ndarray:
    return jnp.zeros_like(x)


# ---------------------------------------------------------------------------
# Carry propagation / normalization
# ---------------------------------------------------------------------------


def _ripple(x: jnp.ndarray) -> jnp.ndarray:
    """One sequential carry pass: limbs -> [0, 2^15), carry-out folded in
    as *19 on limb 0 (2^255 ≡ 19 mod p). Exact but latency-bound (17
    dependent steps) — used only by `normalize_strict` / `to_canonical`,
    never on the hot path."""
    outs: List[jnp.ndarray] = []
    c = jnp.zeros_like(x[0])
    for i in range(NLIMB):
        t = x[i] + c
        outs.append(t & MASK)
        c = t >> RADIX
    outs[0] = outs[0] + 19 * c
    return jnp.stack(outs, axis=0)


def normalize_strict(x: jnp.ndarray) -> jnp.ndarray:
    """Two sequential carry passes -> strict weak form (limbs 1..16 in
    [0, 2^15), limb0 < 2^15 + 19). Needed before to_canonical's
    borrow-ripple subtraction, which assumes in-range limbs."""
    return _ripple(_ripple(x))


def _carry_pass(x: jnp.ndarray) -> jnp.ndarray:
    """One PARALLEL carry pass over the whole limb axis (5 vectorized VPU
    ops, no sequential dependency across limbs): every limb sheds its
    carry to its neighbor simultaneously; the top carry folds into limb 0
    as *19."""
    c = x >> RADIX
    shifted = jnp.concatenate([19 * c[-1:], c[:-1]], axis=0)
    return (x & MASK) + shifted


def normalize(x: jnp.ndarray) -> jnp.ndarray:
    """Two parallel carry passes -> relaxed weak form. Hot-path invariant
    (inputs nonnegative, limbs < 2^26 — the mul-fold bound):

    - pass 1: carries < 2^11, so limbs < 2^15 + 2^11 (limb 0 gets 19*c
      < 2^16.3, still < 2^17);
    - pass 2: carries <= 2 (limb 1 gets <= 2^2), so limbs land in
      [0, 2^15 + 2^11) with limb 0 < 2^15 + 19*2.

    Relaxed-weak inputs keep the next mul exact in int32:
    (2^15 + 2^11)^2 < 1.14 * 2^30 < 2^31, and the lo/hi column sums stay
    17*(2^15 + 1.14*2^16) < 2^21. `to_canonical` re-normalizes strictly,
    so comparisons are unaffected.
    """
    return _carry_pass(_carry_pass(x))


def to_canonical(x: jnp.ndarray) -> jnp.ndarray:
    """Weak form -> unique representative in [0, p)."""
    x = normalize_strict(x)
    # weak value < 2^255 + 18 < 2p, so at most one subtraction of p needed —
    # but limb0 may hold up to 2^15+18 (value can slightly exceed 2^255-1),
    # subtract with borrow and select.
    p_limbs = jnp.asarray(P_LIMBS)
    for _ in range(2):
        diff = []
        b = jnp.zeros_like(x[0])
        for i in range(NLIMB):
            t = x[i] - p_limbs[i] - b
            b = (t >> 31) & 1  # 1 if negative
            diff.append(t + (b << RADIX))
        diff_arr = jnp.stack(diff, axis=0)
        ge_p = (b == 0)[None]
        x = jnp.where(ge_p, diff_arr, x)
    return x


# ---------------------------------------------------------------------------
# Ring ops
# ---------------------------------------------------------------------------


def add(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Sum < 2^16 + 2^12 per limb, so ONE parallel carry pass suffices
    (carries <= 2) to return to relaxed weak form."""
    return _carry_pass(a + b)


def _two_p(x: jnp.ndarray) -> jnp.ndarray:
    """2p as limbs, built from scalars via iota/where: only limb 0
    differs from 2*MASK. Constructed (not embedded as a concrete array)
    so Pallas kernels using sub/neg don't capture array constants."""
    i = lax.broadcasted_iota(jnp.int32, (NLIMB,) + (1,) * (x.ndim - 1), 0)
    return jnp.where(i == 0, 2 * (2**RADIX - 19), 2 * MASK)


def sub(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """a - b, computed as a + 2p - b to stay nonnegative (< 2^17 per
    limb, one carry pass)."""
    return _carry_pass(a + _two_p(a) - b)


def neg(a: jnp.ndarray) -> jnp.ndarray:
    return _carry_pass(_two_p(a) - a)


def mul_padacc(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Field multiply via 17 shifted broadcast rows (pad-accumulate).

    Each of the 17 partial rows is a broadcast multiply a_i * b ->
    (17, ...), split into lo/hi, and padded into its column offset of a
    (35, ...) accumulator. With the limb axis MAJOR the pads are extent
    changes on the slowest-varying axis — no lane relayout — and all
    elementwise ops fuse in XLA; the batch stays resident in the vector
    lanes. This is the hot-path multiply.
    """
    nb = a.ndim - 1
    acc = jnp.zeros((2 * NLIMB + 1,) + a.shape[1:], dtype=a.dtype)
    for i in range(NLIMB):
        p = a[i : i + 1] * b  # (17, ...)
        lo = p & MASK
        hi = p >> RADIX
        acc = acc + jnp.pad(lo, [(i, NLIMB - i + 1)] + [(0, 0)] * nb)
        acc = acc + jnp.pad(hi, [(i + 1, NLIMB - i)] + [(0, 0)] * nb)
    # fold: column 17+t has weight 2^255 * 2^(15t) ≡ 19 * 2^(15t);
    # column 34 (top hi) is always zero since hi of a_16*b_16 lands at 33
    out = acc[:NLIMB] + 19 * acc[NLIMB : 2 * NLIMB]
    return normalize(out)


def mul_skew(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Field multiply via the materialized outer product + skew reduction.

    Materializes a (17, 17, ...) product tensor; the antidiagonal sums use
    the skew trick (pad rows to 35 and reshape, so element (i, j) lands in
    column i + j). Compact in HLO (~25 ops/mul vs ~135 for padacc) so the
    ~300-multiply exponentiation chains use it (`_chain_mul`) to keep
    compile times bounded.
    """
    prod = a[:, None] * b[None, :]  # (17, 17, ...)
    nb = prod.ndim - 2

    def anti(m):
        padded = jnp.pad(m, [(0, 0), (0, NLIMB + 1)] + [(0, 0)] * nb)
        flat = padded.reshape((NLIMB * (2 * NLIMB + 1),) + m.shape[2:])
        skewed = flat[: NLIMB * 2 * NLIMB].reshape(
            (NLIMB, 2 * NLIMB) + m.shape[2:]
        )
        return skewed.sum(axis=0)  # (34, ...)

    lo_cols = anti(prod & MASK)
    hi_cols = anti(prod >> RADIX)
    cols = lo_cols + jnp.pad(hi_cols[:-1], [(1, 0)] + [(0, 0)] * nb)
    out = cols[:NLIMB] + 19 * cols[NLIMB:]
    return normalize(out)


# The hot-path field multiply (see mul_padacc docstring).
mul = mul_padacc

# The exponentiation chains unroll ~300 sequential multiplies on tiny
# (often (17, 1)) operands — runtime-negligible but compile-dominating.
# They use the compact skew form (~25 HLO ops/mul vs ~135) so compile
# times do not balloon 5-10x.
_chain_mul = mul_skew


def sq(a: jnp.ndarray) -> jnp.ndarray:
    return mul(a, a)


def mul_small(a: jnp.ndarray, k: int) -> jnp.ndarray:
    """Multiply by a small positive scalar (k < 2^15)."""
    return normalize(a * k)


# ---------------------------------------------------------------------------
# Exponentiation chains (ref10-style addition chains — 254 squarings,
# ~12 multiplies; vs ~510 multiplies for binary square-and-multiply)
# ---------------------------------------------------------------------------


def _sqn(x: jnp.ndarray, n: int) -> jnp.ndarray:
    """x^(2^n) via n squarings (fori_loop keeps the XLA graph small)."""
    if n <= 4:
        for _ in range(n):
            x = _chain_mul(x, x)
        return x
    return lax.fori_loop(0, n, lambda _, v: _chain_mul(v, v), x)


def _chain_250(x: jnp.ndarray):
    """Shared prefix: returns (x^(2^250 - 1), x^11, x^2)."""
    z2 = _chain_mul(x, x)
    z8 = _sqn(z2, 2)
    z9 = _chain_mul(x, z8)
    z11 = _chain_mul(z2, z9)
    z22 = _chain_mul(z11, z11)
    z_5_0 = _chain_mul(z9, z22)  # x^(2^5 - 1)
    z_10_5 = _sqn(z_5_0, 5)
    z_10_0 = _chain_mul(z_10_5, z_5_0)  # x^(2^10 - 1)
    z_20_10 = _sqn(z_10_0, 10)
    z_20_0 = _chain_mul(z_20_10, z_10_0)
    z_40_20 = _sqn(z_20_0, 20)
    z_40_0 = _chain_mul(z_40_20, z_20_0)
    z_50_10 = _sqn(z_40_0, 10)
    z_50_0 = _chain_mul(z_50_10, z_10_0)
    z_100_50 = _sqn(z_50_0, 50)
    z_100_0 = _chain_mul(z_100_50, z_50_0)
    z_200_100 = _sqn(z_100_0, 100)
    z_200_0 = _chain_mul(z_200_100, z_100_0)
    z_250_50 = _sqn(z_200_0, 50)
    z_250_0 = _chain_mul(z_250_50, z_50_0)  # x^(2^250 - 1)
    return z_250_0, z11, z2


def invert(x: jnp.ndarray) -> jnp.ndarray:
    """x^(p-2) = x^(2^255 - 21): multiplicative inverse (0 -> 0)."""
    z_250_0, z11, _ = _chain_250(x)
    return _chain_mul(_sqn(z_250_0, 5), z11)


def pow22523(x: jnp.ndarray) -> jnp.ndarray:
    """x^((p-5)/8) = x^(2^252 - 3) — the square-root helper exponent."""
    z_250_0, _, _ = _chain_250(x)
    return _chain_mul(_sqn(z_250_0, 2), x)


# ---------------------------------------------------------------------------
# Predicates / conversion helpers
# ---------------------------------------------------------------------------


def eq(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Canonical equality -> bool (...,)."""
    return jnp.all(to_canonical(a) == to_canonical(b), axis=0)


def is_zero(a: jnp.ndarray) -> jnp.ndarray:
    return jnp.all(to_canonical(a) == 0, axis=0)


def parity(a: jnp.ndarray) -> jnp.ndarray:
    """Low bit of the canonical representative (the Edwards sign bit)."""
    return to_canonical(a)[0] & 1


def select(cond: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """cond ? a : b, broadcasting cond (...,) over the leading limb axis."""
    return jnp.where(cond[None], a, b)


# ---------------------------------------------------------------------------
# Byte -> window/limb conversion: vectorized numpy for the host's table
# build (ops/comb.py), and its device twin, which the verify kernel runs
# on the raw wire bytes.
# ---------------------------------------------------------------------------


def extract_windows_np(data: np.ndarray, wbits: int, count: int) -> np.ndarray:
    """(n, 32) uint8 little-endian -> (count, n) int32: window i holds
    bits [i*wbits, (i+1)*wbits) of the 256-bit value, position-major (the
    device layout, produced directly so hot prep paths never transpose).

    View the bytes as four little-endian uint64 words and extract each
    window with two shifts — `count` vectorized ops total vs an
    unpackbits expansion to 256 int32 lanes per item (~10x faster at
    batch 8k). Windows extending past bit 255 are naturally truncated.
    Generic in `wbits` like its device twin extract_windows_dev, which
    the kernel calls with 4 (scalar windows) and 15 (R's limbs)."""
    words = np.ascontiguousarray(data).view("<u8")  # (n, 4)
    mask = np.uint64((1 << wbits) - 1)
    out = np.empty((count, data.shape[0]), dtype=np.int32)
    for i in range(count):
        bitpos = i * wbits
        w, s = bitpos >> 6, bitpos & 63
        v = words[:, w] >> np.uint64(s)
        if s > 64 - wbits and w + 1 < 4:  # window straddles a word boundary
            v = v | (words[:, w + 1] << np.uint64(64 - s))
        out[i] = (v & mask).astype(np.int32)
    return out


def bytes32_to_limbs_major_np(data: np.ndarray) -> np.ndarray:
    """(n, 32) uint8 little-endian -> (17, n) int32 limbs of the low 255
    bits (bit 255 — the sign bit — is excluded), limb-major."""
    return extract_windows_np(data, RADIX, NLIMB)


def extract_windows_dev(data: jnp.ndarray, wbits: int, count: int) -> jnp.ndarray:
    """Device-side twin of extract_windows_np: (n, 32) uint8 wire bytes ->
    (count, n) int32 windows, inside jit.

    Exists so the verify kernel can take RAW wire bytes: the host then
    transfers 32 bytes per scalar instead of `count` int32 windows (3.3x
    fewer bytes over the host->device link). TPUs have no 64-bit lanes, so instead of the numpy
    version's uint64 word trick each window gathers its (at most) three
    covering bytes and shifts in int32 — all static indexing, fused by
    XLA into the kernel prologue."""
    b = data.astype(jnp.int32)  # (n, 32)
    bitpos = np.arange(count) * wbits
    lo = bitpos >> 3
    sh = jnp.asarray(bitpos & 7, dtype=jnp.int32)
    parts = []
    for k in range(3):  # wbits<=15 and sh<=7 => a window spans <=3 bytes
        idx = np.minimum(lo + k, 31)
        byte = b[:, idx]  # (n, count) static gather
        byte = jnp.where(jnp.asarray(lo + k <= 31), byte, 0)
        left = jnp.maximum(8 * k - sh, 0)  # k=0 only ever shifts right
        right = jnp.maximum(sh - 8 * k, 0)
        parts.append((byte << left) >> right)
    v = parts[0] | parts[1] | parts[2]
    return (v & ((1 << wbits) - 1)).T.astype(jnp.int32)


def bytes32_to_limbs_np(data: np.ndarray) -> np.ndarray:
    """(n, 32) uint8 little-endian -> (n, 17) int32 limbs (batch-major
    form for host-side table building; see bytes32_to_limbs_major_np)."""
    return bytes32_to_limbs_major_np(data).T
