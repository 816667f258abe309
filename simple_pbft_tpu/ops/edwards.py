"""Edwards25519 point arithmetic on TPU (extended coordinates, a = -1).

Points are int32 arrays of shape (4, 17, ...): stacked (X, Y, Z, T) limb
vectors with x = X/Z, y = Y/Z, T = XY/Z. Like the field layer
(ops/field25519.py), the limb axis leads and batch axes trail so the batch
fills the 128-wide vector lanes. The design constraint is XLA: no
data-dependent control flow, every row runs the same fixed sequence.

Formulas: unified add-2008-hwcd-3 and dbl-2008-hwcd (same formulas the CPU
oracle in crypto/ed25519_cpu.py uses, so both planes agree bit-for-bit).

Who runs what: the table-free verify program (ops/ladder.py) calls
`decompress` on every row's key, and its windowed ladder is these
formulas with a group operation's independent multiplies stacked into one
call; `point_add`, `point_double` and `compress` are the plain forms the
tests hold that ladder (and the comb's mixed add) to.
"""

from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp
import numpy as np

from . import field25519 as fe
from ..crypto import ed25519_cpu as ref

# -- constants (limb form, derived from the CPU module's verified ints) ----

D2_INT = (2 * ref.D) % ref.P
SQRT_M1 = fe._int_to_limbs_np(ref.SQRT_M1)
D_LIMBS = fe._int_to_limbs_np(ref.D)
D2_LIMBS = fe._int_to_limbs_np(D2_INT)


def _point_const(p: Tuple[int, int, int, int]) -> np.ndarray:
    return np.stack([fe._int_to_limbs_np(c % ref.P) for c in p])


IDENTITY = _point_const(ref.IDENTITY)  # (4, 17)

# -- coordinate accessors ---------------------------------------------------


def _unpack(p: jnp.ndarray):
    return p[0], p[1], p[2], p[3]


def _pack(x, y, z, t) -> jnp.ndarray:
    return jnp.stack([x, y, z, t], axis=0)


# -- group law --------------------------------------------------------------


def point_add(p: jnp.ndarray, q: jnp.ndarray) -> jnp.ndarray:
    """Unified addition (add-2008-hwcd-3); mirrors ed25519_cpu.point_add."""
    x1, y1, z1, t1 = _unpack(p)
    x2, y2, z2, t2 = _unpack(q)
    a = fe.mul(fe.sub(y1, x1), fe.sub(y2, x2))
    b = fe.mul(fe.add(y1, x1), fe.add(y2, x2))
    c = fe.mul(fe.mul(t1, fe.bcast(D2_LIMBS, t1)), t2)
    d = fe.mul_small(fe.mul(z1, z2), 2)
    e = fe.sub(b, a)
    f = fe.sub(d, c)
    g = fe.add(d, c)
    h = fe.add(b, a)
    return _pack(fe.mul(e, f), fe.mul(g, h), fe.mul(f, g), fe.mul(e, h))


def point_double(p: jnp.ndarray) -> jnp.ndarray:
    """Doubling (dbl-2008-hwcd); mirrors ed25519_cpu.point_double."""
    x1, y1, z1, _ = _unpack(p)
    a = fe.sq(x1)
    b = fe.sq(y1)
    c = fe.mul_small(fe.sq(z1), 2)
    h = fe.add(a, b)
    e = fe.sub(h, fe.sq(fe.add(x1, y1)))
    g = fe.sub(a, b)
    f = fe.add(c, g)
    return _pack(fe.mul(e, f), fe.mul(g, h), fe.mul(f, g), fe.mul(e, h))


def point_neg(p: jnp.ndarray) -> jnp.ndarray:
    """-(x, y) = (-x, y); T = xy negates too."""
    x, y, z, t = _unpack(p)
    return _pack(fe.neg(x), y, z, fe.neg(t))


# -- compression / decompression -------------------------------------------


def compress(p: jnp.ndarray):
    """-> (y_limbs canonical (17, ...), x_parity (...,)) — the wire form is
    y with the sign bit of x in bit 255 (RFC 8032 §5.1.2)."""
    x, y, z, _ = _unpack(p)
    zinv = fe.invert(z)
    xa = fe.mul(x, zinv)
    ya = fe.mul(y, zinv)
    return fe.to_canonical(ya), fe.parity(xa)


def decompress(y_limbs: jnp.ndarray, sign: jnp.ndarray):
    """Recover (4, 17, ...) extended point from canonical y and sign bit.

    RFC 8032 §5.1.3: x^2 = (y^2-1)/(d y^2+1); the square root and the
    inversion share one exponentiation: x = u v^3 (u v^7)^((p-5)/8).
    Returns (point, ok) with ok False when x^2 is a non-residue or when
    x = 0 with sign = 1. Mirrors ed25519_cpu._recover_x (callers must
    ensure y < p — host-side canonicality check).
    """
    one = fe.bcast(fe.ONE, y_limbs)
    yy = fe.sq(y_limbs)
    u = fe.sub(yy, one)  # y^2 - 1
    v = fe.add(fe.mul(yy, fe.bcast(D_LIMBS, yy)), one)
    v3 = fe.mul(fe.sq(v), v)
    v7 = fe.mul(fe.sq(v3), v)
    x = fe.mul(fe.mul(u, v3), fe.pow22523(fe.mul(u, v7)))
    vxx = fe.mul(v, fe.sq(x))
    ok_direct = fe.eq(vxx, u)
    ok_twist = fe.eq(vxx, fe.neg(u))
    x = fe.select(ok_twist, fe.mul(x, fe.bcast(SQRT_M1, x)), x)
    ok = ok_direct | ok_twist
    x = fe.to_canonical(x)
    x_is_zero = fe.is_zero(x)
    ok = ok & ~(x_is_zero & (sign == 1))
    # match the requested sign
    flip = (x[0] & 1) != sign
    x = fe.select(flip, fe.neg(x), x)
    t = fe.mul(x, y_limbs)
    z = jnp.broadcast_to(one, y_limbs.shape)
    return _pack(x, y_limbs, z, t), ok
