"""Table-free Ed25519 verification: the program for a key with no table.

The comb kernel (ops/comb.py) needs 4 MiB of precomputed table a key, and
a device holds so many (crypto/tpu_verifier.bank_capacity: 1,814 on a
v5e). A deployment with more signers than that still verifies every
signature on the device: a row whose key has no table brings the key's 32
wire bytes along and this program does the whole of
[S]B + [k](-A) == R itself:

- **decompress A on the device** (edwards.decompress, one exponentiation
  chain a row) and negate it. The host keeps nothing per key and runs no
  bigint square root: an unknown key costs it a dict miss. A key that is
  no curve point, or whose y is not canonical, rejects here, and its row
  runs the ladder on the identity so that no off-curve point reaches the
  formulas (their Z must stay nonzero for the batch inversion).
- **16 multiples of -A a row**, 0..15, built by a scan of mixed adds and
  held in cached form (Y-X, Y+X, 2dT, 2Z), so that a ladder add is 8
  field multiplies.
- **one 64-window Straus ladder**, most significant window first: four
  doublings, one add of the row's own multiple chosen by k's window (a
  select chain, no gather), one mixed add of B's multiple chosen by S's
  window. B's 16 multiples are a constant (4 KiB of Niels rows), fetched
  for all 64 positions in the comb's one flat gather.
- the comb's own ending (comb._encode_and_compare): one batch inversion,
  compare with R's wire bytes. R is never decompressed.

A row costs about 3,400 field multiplies (64 x (4 x 8 + 8 + 7) in the
ladder, 280 to decompress, 130 for the multiples) where a table row costs
450. Same layout as the comb: limb-major, batch-minor, constant shape,
no data-dependent control flow; every loop is a fori_loop or a scan, so
the program is small to compile.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
from jax import lax

from . import comb
from . import edwards as ed
from . import field25519 as fe
from ..crypto import ed25519_cpu as ref

WBITS = comb.WBITS  # both scalars' window, the comb's
NPOS = comb.NPOS
WINDOW = comb.WINDOW
ROW_BYTES = 128  # a staged row: S (32) ‖ k (32) ‖ R (32) ‖ A (32)

# field multiplies a row, for crypto/costmodel.py: decompression (the
# chain's 252 squarings and 12 multiplies, 14 around it), the 16 multiples
# (16 mixed adds of 7, one wasted; 16 conversions of 1; A's own Niels
# form 1), the ladder, and the ending (2 to normalise, 3 of inversion)
FIELD_MULS = 278 + 16 * 7 + 16 + 1 + NPOS * (WBITS * 8 + 8 + 7) + 5


def _base_lines_np() -> np.ndarray:
    """j B for j in 0..15 as Niels rows, two a line (comb._gather_rows'
    table layout): (WINDOW / 2, LINE) int32."""
    pts, acc = [], ref.IDENTITY
    for _ in range(WINDOW):
        pts.append(acc)
        acc = ref.point_add(acc, ref.B)
    return comb._batch_affine_niels_np(pts).reshape(WINDOW // 2, comb.LINE)


BASE_LINES = _base_lines_np()


# Inside the ladder a point is (17, 4, B): limb, coordinate (X, Y, Z, T),
# batch. A group operation's independent field multiplies are then ONE
# call of fe.mul over a stacked (17, 4, B) operand pair, so a doubling is
# two calls where edwards.point_double is eight: a quarter of the program
# to compile, and a quarter of the device's launches a window.


def _stack(*elems: jnp.ndarray) -> jnp.ndarray:
    return jnp.stack(elems, axis=1)


def _finish(e, f, g, h) -> jnp.ndarray:
    """(E, F, G, H) of the hwcd formulas -> (EF, GH, FG, EH)."""
    return fe.mul(_stack(e, g, f, e), _stack(f, h, g, h))


def _double(p: jnp.ndarray) -> jnp.ndarray:
    """edwards.point_double (dbl-2008-hwcd) on the stacked form."""
    x, y, z = p[:, 0], p[:, 1], p[:, 2]
    s = _stack(x, y, z, fe.add(x, y))
    sq = fe.mul(s, s)
    a, b = sq[:, 0], sq[:, 1]
    h = fe.add(a, b)
    g = fe.sub(a, b)
    return _finish(fe.sub(h, sq[:, 3]), fe.add(fe.mul_small(sq[:, 2], 2), g), g, h)


def _cached(p: jnp.ndarray) -> jnp.ndarray:
    """Stacked extended point -> cached (Y-X, Y+X, 2dT, 2Z), the addend
    form that leaves _add_cached 8 multiplies."""
    x, y, z, t = p[:, 0], p[:, 1], p[:, 2], p[:, 3]
    return _stack(
        fe.sub(y, x), fe.add(y, x), fe.mul(t, fe.bcast(ed.D2_LIMBS, t)),
        fe.mul_small(z, 2),
    )


def _add_cached(p: jnp.ndarray, c: jnp.ndarray) -> jnp.ndarray:
    """edwards.point_add (add-2008-hwcd-3), second operand cached."""
    x, y, z, t = p[:, 0], p[:, 1], p[:, 2], p[:, 3]
    m = fe.mul(_stack(fe.sub(y, x), fe.add(y, x), t, z), c)
    a, b, cc, d = m[:, 0], m[:, 1], m[:, 2], m[:, 3]
    return _finish(fe.sub(b, a), fe.sub(d, cc), fe.add(d, cc), fe.add(b, a))


def _madd(p: jnp.ndarray, niels: jnp.ndarray) -> jnp.ndarray:
    """comb.madd: mixed add of affine Niels rows (y+x ‖ y-x ‖ 2dxy;
    the first 51 of a table row)."""
    x, y, z, t = p[:, 0], p[:, 1], p[:, 2], p[:, 3]
    n = fe.NLIMB
    m = fe.mul(
        _stack(fe.add(y, x), fe.sub(y, x), t),
        _stack(niels[:n], niels[n : 2 * n], niels[2 * n : 3 * n]),
    )
    a, b, c = m[:, 0], m[:, 1], m[:, 2]
    d = fe.mul_small(z, 2)
    return _finish(fe.sub(a, b), fe.sub(d, c), fe.add(d, c), fe.add(a, b))


def _select(idx: jnp.ndarray, table: jnp.ndarray) -> jnp.ndarray:
    """table[idx] a row: table (WINDOW, 17, 4, B), idx (B,). A chain of
    selects, which vectorises over the batch where a gather would not."""
    out = table[0]
    for i in range(1, table.shape[0]):
        out = jnp.where((idx == i)[None, None], table[i], out)
    return out


def double_scalar_mul_base(
    s_windows: jnp.ndarray, k_windows: jnp.ndarray, q_niels: jnp.ndarray
) -> jnp.ndarray:
    """[s]B + [k]Q, extended (4, 17, B).

    s_windows, k_windows: (NPOS, B) int32 4-bit windows, least
    significant first (fe.extract_windows_dev). q_niels: Q's affine
    Niels rows (y+x ‖ y-x ‖ 2dxy), (3 * 17, B). One shared run of
    doublings, one add a scalar a window."""
    ident = jnp.moveaxis(comb._ident_like(k_windows[0]), 0, 1)

    def multiple(p, _):
        return _madd(p, q_niels), _cached(p)

    _, q_table = lax.scan(multiple, ident, None, length=WINDOW)
    b_rows = comb._gather_rows(jnp.asarray(BASE_LINES), s_windows)

    def window(t, acc):
        i = NPOS - 1 - t
        acc = lax.fori_loop(0, WBITS, lambda _, p: _double(p), acc)
        acc = _add_cached(acc, _select(k_windows[i], q_table))
        return _madd(acc, b_rows[i])

    return jnp.moveaxis(lax.fori_loop(0, NPOS, window, ident), 1, 0)


def _neg_key_niels(a_y: jnp.ndarray, a_sign: jnp.ndarray):
    """A's wire form -> (-A as affine Niels rows (51, B), ok (B,)). A row
    that is not ok carries the identity's rows (1, 1, 0)."""
    a, ok = ed.decompress(a_y, a_sign)
    # y >= p: the oracle's _recover_x refuses it before any arithmetic
    ok = ok & jnp.all(fe.to_canonical(a_y) == a_y, axis=0)
    x, y, t = a[0], a[1], a[3]
    one = jnp.broadcast_to(fe.bcast(fe.ONE, y), y.shape)
    # -A = (-x, y): y + (-x), y - (-x), 2d (-x) y
    ypx = fe.select(ok, fe.sub(y, x), one)
    ymx = fe.select(ok, fe.add(y, x), one)
    xy2d = fe.select(
        ok, fe.mul(fe.neg(t), fe.bcast(ed.D2_LIMBS, t)), jnp.zeros_like(y))
    return jnp.concatenate([ypx, ymx, xy2d], axis=0), ok


def ladder_verify_wire_kernel(
    wire: jnp.ndarray,  # (B, 128) uint8 — S ‖ k ‖ R ‖ A raw bytes
    precheck: jnp.ndarray,  # (B,) bool — host-side validity mask
) -> jnp.ndarray:
    """The table-free verify program: raw bytes in, verdicts out. Accepts
    and rejects what comb.fused_verify_wire_kernel does for the same row
    under a tabled key, and what crypto/ed25519_cpu.verify does."""
    s_w = fe.extract_windows_dev(wire[:, 0:32], WBITS, NPOS)
    k_w = fe.extract_windows_dev(wire[:, 32:64], WBITS, NPOS)
    r_y = fe.extract_windows_dev(wire[:, 64:96], fe.RADIX, fe.NLIMB)
    r_sign = wire[:, 95].astype(jnp.int32) >> 7
    a_y = fe.extract_windows_dev(wire[:, 96:128], fe.RADIX, fe.NLIMB)
    a_sign = wire[:, 127].astype(jnp.int32) >> 7
    q_niels, ok = _neg_key_niels(a_y, a_sign)
    p = double_scalar_mul_base(s_w, k_w, q_niels)
    return comb._encode_and_compare(p, r_y, r_sign, precheck & ok)
