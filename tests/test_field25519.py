"""Field/point kernels vs. the exact-integer CPU oracle.

The jnp limb arithmetic (ops/field25519.py, ops/edwards.py) must agree with
Python bignum math on every operation — these are known-answer tests over
random and adversarial (boundary) inputs, run on the 8-virtual-device CPU
backend (conftest.py) exactly as they jit on TPU.

Device layout convention: limb axis FIRST, batch axes trailing — a batch
of field elements is (17, n), a batch of points (4, 17, n).
"""

import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from simple_pbft_tpu.crypto import ed25519_cpu as ref
from simple_pbft_tpu.ops import edwards as ed
from simple_pbft_tpu.ops import field25519 as fe

P = ref.P
rng = random.Random(1234)

BOUNDARY = [0, 1, 2, 19, P - 1, P - 2, P - 19, 2**255 - 19 - 1, 2**254, ref.D]


def limbs(v: int) -> jnp.ndarray:
    return jnp.asarray(fe._int_to_limbs_np(v % P))


def limb_batch(vals) -> jnp.ndarray:
    """ints -> (17, n) limb-first batch."""
    return jnp.asarray(np.stack([fe._int_to_limbs_np(v % P) for v in vals], axis=1))


def unlimbs(a) -> int:
    return fe._limbs_to_int_np(np.asarray(a))


def rand_elems(n):
    return [rng.randrange(P) for _ in range(n)]


class TestFieldOps:
    def test_roundtrip(self):
        for v in BOUNDARY + rand_elems(20):
            assert unlimbs(limbs(v)) == v % P

    def test_bytes32_to_limbs_window_extraction(self):
        # the uint64-window fast path must agree with direct bit math on
        # the low 255 bits (bit 255, the sign bit, excluded)
        import numpy as np

        rng = np.random.default_rng(7)
        data = rng.integers(0, 256, (50, 32), dtype=np.uint8)
        data[0, :] = 0xFF  # all-ones boundary
        data[1, :] = 0
        out = fe.bytes32_to_limbs_major_np(data)
        assert out.shape == (fe.NLIMB, 50)
        for j in range(50):
            v = int.from_bytes(bytes(data[j]), "little") & ((1 << 255) - 1)
            assert fe._limbs_to_int_np(out[:, j : j + 1]) == v

    def test_nibbles_major_layout(self):
        # the 4-bit windows the comb tables are indexed by, position-major
        import numpy as np

        from simple_pbft_tpu.ops import comb

        rng = np.random.default_rng(8)
        data = rng.integers(0, 256, (20, 32), dtype=np.uint8)
        out = fe.extract_windows_np(data, comb.WBITS, comb.NPOS)
        assert out.shape == (comb.NPOS, 20)
        for j in range(20):
            v = int.from_bytes(bytes(data[j]), "little")
            got = sum(int(out[i, j]) << (4 * i) for i in range(comb.NPOS))
            assert got == v

    def test_two_p_constant_encodes_2p(self):
        # _two_p builds 2p from scalars (Pallas kernels must not capture
        # array constants); pin it against the exact integer
        import numpy as np

        tp = np.asarray(fe._two_p(jnp.zeros((fe.NLIMB, 1), jnp.int32)))
        assert fe._limbs_to_int_np(tp) == 2 * fe.P_INT

    def test_add_sub_mul(self):
        vals = BOUNDARY + rand_elems(30)
        b_vals = list(reversed(vals))
        a, b = limb_batch(vals), limb_batch(b_vals)
        add = jax.jit(fe.add)(a, b)
        sub = jax.jit(fe.sub)(a, b)
        mul = jax.jit(fe.mul)(a, b)
        for i, (x, y) in enumerate(zip(vals, b_vals)):
            assert unlimbs(fe.to_canonical(add[:, i])) == (x + y) % P
            assert unlimbs(fe.to_canonical(sub[:, i])) == (x - y) % P
            assert unlimbs(fe.to_canonical(mul[:, i])) == (x * y) % P

    def test_mul_impls_agree(self):
        vals = BOUNDARY + rand_elems(10)
        a, b = limb_batch(vals), limb_batch(list(reversed(vals)))
        skew = jax.jit(fe.mul_skew)(a, b)
        padacc = jax.jit(fe.mul_padacc)(a, b)
        for i in range(len(vals)):
            assert unlimbs(fe.to_canonical(skew[:, i])) == unlimbs(
                fe.to_canonical(padacc[:, i])
            )

    def test_mul_worst_case_limbs(self):
        # all-ones limbs (maximum column sums) must not overflow int32
        top = jnp.asarray(np.full(fe.NLIMB, fe.MASK, dtype=np.int32))
        for mul in (fe.mul_padacc, fe.mul_skew):
            got = fe.to_canonical(mul(top, top))
            assert unlimbs(got) == (((1 << 255) - 1) ** 2) % P

    def test_invert(self):
        vals = [0, 1, 2, P - 1] + rand_elems(5)
        batch = limb_batch(vals)
        out = jax.jit(fe.invert)(batch)
        for i, v in enumerate(vals):
            want = pow(v, P - 2, P) if v else 0
            assert unlimbs(fe.to_canonical(out[:, i])) == want

    def test_pow22523(self):
        vals = [1, 2] + rand_elems(5)
        batch = limb_batch(vals)
        out = jax.jit(fe.pow22523)(batch)
        for i, v in enumerate(vals):
            assert unlimbs(fe.to_canonical(out[:, i])) == pow(v, (P - 5) // 8, P)

    def test_eq_parity_zero(self):
        a = limbs(5)
        b = fe.add(limbs(P - 1), limbs(6))  # 5 via wraparound
        assert bool(fe.eq(a, b))
        assert not bool(fe.eq(a, limbs(6)))
        assert bool(fe.is_zero(fe.sub(a, b)))
        for v in [0, 1, 2, P - 1] + rand_elems(5):
            assert int(fe.parity(limbs(v))) == v % 2


def pt(p_int):
    return jnp.asarray(ed._point_const(p_int))


def pt_batch(pts):
    """points -> (4, 17, n)."""
    return jnp.asarray(np.stack([ed._point_const(p) for p in pts], axis=-1))


def affine(p) -> tuple:
    x, y, z, t = [unlimbs(fe.to_canonical(p[i])) for i in range(4)]
    zi = pow(z, P - 2, P)
    return (x * zi % P, y * zi % P)


class TestPointOps:
    def rand_point(self):
        k = rng.randrange(ref.L)
        return ref.point_mul(k, ref.B), k

    def test_add_double(self):
        p_ref, _ = self.rand_point()
        q_ref, _ = self.rand_point()
        got = affine(jax.jit(ed.point_add)(pt(p_ref), pt(q_ref)))
        assert got == ref.point_to_affine(ref.point_add(p_ref, q_ref))
        got = affine(jax.jit(ed.point_double)(pt(p_ref)))
        assert got == ref.point_to_affine(ref.point_double(p_ref))

    def test_add_identity_cases(self):
        p_ref, _ = self.rand_point()
        ident = jnp.asarray(ed.IDENTITY)
        assert affine(ed.point_add(pt(p_ref), ident)) == ref.point_to_affine(p_ref)
        assert affine(ed.point_add(ident, ident)) == (0, 1)
        assert affine(ed.point_double(ident)) == (0, 1)
        # P + (-P) = identity
        assert affine(ed.point_add(pt(p_ref), ed.point_neg(pt(p_ref)))) == (0, 1)

    def test_double_scalar_mul(self):
        """ops/ladder.py's windowed Straus ladder, [s]B + [k]Q, against
        bigint scalar multiplication: random scalars, and the windows'
        edges (0, 15 in every window, the top window alone)."""
        from simple_pbft_tpu.ops import comb, ladder

        qs = []
        for s, k in [(None, None), (None, None), (0, 0), (2**256 - 1, 1),
                     (1, 2**256 - 1), (15 << 252, 15 << 252), (ref.L - 1, 0),
                     (0, ref.L - 1)]:
            q_ref, _ = self.rand_point()
            qs.append((q_ref,
                       rng.randrange(ref.L) if s is None else s,
                       rng.randrange(ref.L) if k is None else k))

        def windows(vals):
            data = np.stack([np.frombuffer(v.to_bytes(32, "little"), np.uint8)
                             for v in vals])
            return jnp.asarray(fe.extract_windows_np(data, ladder.WBITS, ladder.NPOS))

        # Q's affine Niels rows, as the comb's tables hold a point
        q_niels = jnp.asarray(comb._batch_affine_niels_np(
            [q for q, _, _ in qs])[:, : 3 * fe.NLIMB].T)
        got = jax.jit(ladder.double_scalar_mul_base)(
            windows([s for _, s, _ in qs]), windows([k for _, _, k in qs]),
            q_niels)
        assert got.shape == (4, fe.NLIMB, len(qs))
        for i, (q_ref, s, k) in enumerate(qs):
            want = ref.point_add(ref.point_mul(s, ref.B), ref.point_mul(k, q_ref))
            assert affine(got[:, :, i]) == ref.point_to_affine(want)
            t = unlimbs(fe.to_canonical(got[3, :, i]))
            x, y, z = (unlimbs(fe.to_canonical(got[c, :, i])) for c in range(3))
            assert (t * z - x * y) % P == 0  # T = XY/Z survives the ladder

    def test_stacked_group_law_is_the_plain_one(self):
        """ladder._double, _add_cached and _madd (a group operation's
        multiplies stacked into one call) against edwards.point_double,
        point_add and the oracle, identity and a point's own negative
        included."""
        from simple_pbft_tpu.ops import comb, ladder

        pts = [self.rand_point()[0] for _ in range(3)] + [ref.IDENTITY]
        qts = [self.rand_point()[0], ref.IDENTITY, None, pts[0]]
        x, y, z, t = pts[2]
        qts[2] = ((-x) % P, y, z, (-t) % P)
        p_arr, q_arr = pt_batch(pts), pt_batch(qts)
        stacked = jnp.moveaxis(p_arr, 0, 1)  # (17, 4, n)

        def unstack(a):
            return jnp.moveaxis(a, 1, 0)

        dbl = unstack(jax.jit(ladder._double)(stacked))
        add = unstack(jax.jit(
            lambda p, q: ladder._add_cached(p, ladder._cached(q))
        )(stacked, jnp.moveaxis(q_arr, 0, 1)))
        niels = jnp.asarray(
            comb._batch_affine_niels_np(qts)[:, : 3 * fe.NLIMB].T)
        madd = unstack(jax.jit(ladder._madd)(stacked, niels))
        plain_dbl = jax.jit(ed.point_double)(p_arr)
        plain_add = jax.jit(ed.point_add)(p_arr, q_arr)
        for i, (p_ref, q_ref) in enumerate(zip(pts, qts)):
            assert affine(dbl[:, :, i]) == affine(plain_dbl[:, :, i]) \
                == ref.point_to_affine(ref.point_double(p_ref))
            want = ref.point_to_affine(ref.point_add(p_ref, q_ref))
            assert affine(add[:, :, i]) == affine(plain_add[:, :, i]) == want
            assert affine(madd[:, :, i]) == want

    def test_compress_decompress_roundtrip(self):
        pts = [self.rand_point()[0] for _ in range(4)]
        wires = np.stack(
            [np.frombuffer(ref.point_compress(p), dtype=np.uint8) for p in pts]
        )
        y_limbs = jnp.asarray(fe.bytes32_to_limbs_np(wires).T)  # (17, n)
        sign = jnp.asarray((wires[:, 31] >> 7).astype(np.int32))
        point, ok = jax.jit(ed.decompress)(y_limbs, sign)
        y_out, x_par = jax.jit(ed.compress)(point)
        for i, p_ref in enumerate(pts):
            enc = int.from_bytes(wires[i].tobytes(), "little")
            assert bool(ok[i])
            assert affine(point[:, :, i]) == ref.point_to_affine(p_ref)
            assert unlimbs(y_out[:, i]) == enc & ((1 << 255) - 1)
            assert int(x_par[i]) == enc >> 255

    def test_decompress_invalid(self):
        ys = list(range(2, 14))
        y_arr = limb_batch(ys)
        zero_sign = jnp.zeros(len(ys), dtype=jnp.int32)
        _, ok = jax.jit(ed.decompress)(y_arr, zero_sign)
        flags = [ref._recover_x(y, 0) is not None for y in ys]
        assert any(not f for f in flags)  # some non-residues in range
        for i, f in enumerate(flags):
            assert bool(ok[i]) == f

    def test_decompress_zero_x_sign(self):
        # y = 1 -> x = 0; sign bit 1 must be rejected (non-canonical)
        y_arr = limb_batch([1, 1])
        signs = jnp.asarray([1, 0], dtype=jnp.int32)
        _, ok = jax.jit(ed.decompress)(y_arr, signs)
        assert not bool(ok[0])
        assert bool(ok[1])
