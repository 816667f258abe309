"""Fused-comb Ed25519 verification — the one verify kernel.

PBFT gives the verifier structure the TPU can exploit:

- **Pubkeys are a small committee set**, reused across every vote. So the
  host decompresses each pubkey once (exact bigint math) and uploads a
  per-key *fused dual-scalar table*: T[i][ws][wk] = (ws * 16^i) B +
  (wk * 16^i) (−A) for i in 0..63 and ws, wk in 0..15, in Niels form
  (y+x, y−x, 2dxy). [S]B + [k](−A) is then 64 table lookups + 64 mixed
  adds — **zero doublings**.
- **R never needs decompressing**: instead of comparing points in
  extended coordinates ([S]B − [k]A == R), compute P = [S]B + [k](−A),
  normalize to affine with ONE inversion amortized over the whole batch
  (tree-structured Montgomery batch inversion — log2(B) levels of batched
  multiplies, a single scalar invert chain at the root), and compare P's
  canonical encoding (y limbs + x parity) against R's wire bytes. A
  non-canonical or off-curve R simply never matches.

TPU-native data layout (what makes this fast, not just op-lean):

- Tables live in HBM as rows: one (64,) int32 row per Niels entry
  = [y+x limbs | y−x limbs | 2dxy limbs | pad] — so fetching an entry is
  one dense 256-byte row read. All 64 positions' rows for the whole batch
  are fetched in ONE flat `jnp.take`, not 64 per-position gathers in a
  loop.
- Compute arrays are limb-major / batch-minor ((17, B), see
  ops/field25519.py): the batch fills the 128-wide vector lanes, making
  the 64-iteration madd loop VPU-dense.

Per-signature device cost: 64 mixed adds (7 field muls each) + ~3 muls
of batch inversion ≈ 450 field muls.

Everything stays constant-shape: 64 nibble positions whatever the scalar,
identity entries for zero nibbles, verdicts masked by host prechecks.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
import numpy as np
from jax import lax

from . import field25519 as fe
from ..crypto import ed25519_cpu as ref

WBITS = 4  # scalar window width
NPOS = 256 // WBITS  # comb positions covering 256-bit scalars: 64
WINDOW = 1 << WBITS  # entries per scalar per position: 16
ROWS_PER_KEY = NPOS * WINDOW * WINDOW  # one key's table: 16,384 rows
ROW = 64  # Niels row: 3*17 int32 limbs + 13 pad to a 256B row
LINE = 2 * ROW  # the device table's line: two rows, the TPU's 128 lanes

# ---------------------------------------------------------------------------
# Host-side table construction (exact Python bigints -> limb rows)
# ---------------------------------------------------------------------------


def _pack_rows_np(vals: np.ndarray) -> np.ndarray:
    """(n, 3, 17) int32 Niels limbs -> (n, ROW) rows: one int32 per
    limb, 13 pad words — a 256-byte row of which 204 bytes are payload."""
    n = vals.shape[0]
    out = np.zeros((n, ROW), dtype=np.int32)
    out[:, : 3 * fe.NLIMB] = vals.reshape(n, 3 * fe.NLIMB)
    return out


def _batch_affine_niels_np(points) -> np.ndarray:
    """Extended bigint points -> (n, ROW) Niels rows, with ONE
    modular inversion for the whole list (host Montgomery batch trick) and
    vectorized int->limb conversion. comb_table-scale builds do tens of
    thousands of entries per key; per-entry Fermat inversions would cost
    seconds per key."""
    n = len(points)
    zs = [p[2] for p in points]
    prefix = [1] * (n + 1)
    for i, z in enumerate(zs):
        prefix[i + 1] = prefix[i] * z % ref.P
    inv_all = pow(prefix[n], ref.P - 2, ref.P)
    zinv = [0] * n
    for i in range(n - 1, -1, -1):
        zinv[i] = prefix[i] * inv_all % ref.P
        inv_all = inv_all * zs[i] % ref.P
    vals = np.zeros((n, 3, 32), dtype=np.uint8)
    for i, (p, zi) in enumerate(zip(points, zinv)):
        x = p[0] * zi % ref.P
        y = p[1] * zi % ref.P
        vals[i, 0] = np.frombuffer(((y + x) % ref.P).to_bytes(32, "little"), np.uint8)
        vals[i, 1] = np.frombuffer(((y - x) % ref.P).to_bytes(32, "little"), np.uint8)
        vals[i, 2] = np.frombuffer(
            (2 * ref.D * x * y % ref.P).to_bytes(32, "little"), np.uint8
        )
    limbs = fe.bytes32_to_limbs_np(vals.reshape(n * 3, 32)).reshape(n, 3, fe.NLIMB)
    return _pack_rows_np(limbs)


def _point_neg(p: ref.Point) -> ref.Point:
    x, y, z, t = p
    return ((-x) % ref.P, y, z, (-t) % ref.P)


def fused_table_np(point: ref.Point) -> np.ndarray:
    """(ROWS_PER_KEY, ROW) rows of one key's fused table:
    row[i*256 + ws*16 + wk] = (ws * 16^i) B + (wk * 16^i) (−A).

    One row fetch + ONE mixed add per window position evaluates
    [S]B + [k](−A). ~4.2 MB per key; keys are few (a committee) and
    endlessly reused, so the build amortizes; KeyBank caps total memory.
    """
    # Native fast path (native/ed25519.cpp): the same build in C++ group
    # arithmetic, ~80x the Python bigint loop — the difference between a
    # sub-second and a half-minute cold KeyBank at n=64. Output is
    # affine-Niels field-element BYTES; the vectorized bytes->limb
    # conversion below is shared with the Python path, so both produce
    # bit-identical rows.
    from .. import native

    x, y = ref.point_to_affine(point)
    a_xy = np.frombuffer(
        x.to_bytes(32, "little") + y.to_bytes(32, "little"), dtype=np.uint8
    )
    nb = native.ed25519_fused_table(a_xy, WBITS)
    if nb is not None:
        n = nb.shape[0]
        limbs = fe.bytes32_to_limbs_np(
            nb.reshape(n * 3, 32)
        ).reshape(n, 3, fe.NLIMB)
        return _pack_rows_np(limbs)

    pts = []
    base_b = ref.B
    base_a = _point_neg(point)
    for i in range(NPOS):
        row_b = ref.IDENTITY
        for ws in range(WINDOW):
            acc = row_b
            for wk in range(WINDOW):
                pts.append(acc)
                acc = ref.point_add(acc, base_a)
            row_b = ref.point_add(row_b, base_b)
        for _ in range(WBITS):  # bases <- 16 * bases
            base_b = ref.point_double(base_b)
            base_a = ref.point_double(base_a)
    return _batch_affine_niels_np(pts)


# ---------------------------------------------------------------------------
# Device kernel pieces (limb-major, batch-minor)
# ---------------------------------------------------------------------------


def _row_niels(rows: jnp.ndarray):
    """Table rows (ROW, ...) -> (ypx, ymx, xy2d) limb arrays (17, ...)."""
    n = fe.NLIMB
    return rows[:n], rows[n : 2 * n], rows[2 * n : 3 * n]


def _madd_tuple(x1, y1, z1, t1, rows):
    """Mixed add on coordinate tuples: extended (17, ...) x4 +
    Niels rows (ROW, ...). ref10-style ge_madd — 7 field muls. Same group
    law as edwards.point_add with Z2 = 1 and the Niels components
    precomputed. Tuple form so the Pallas loop carries register-resident
    coordinates without stack/unstack churn."""
    ypx, ymx, xy2d = _row_niels(rows)
    a = fe.mul(fe.add(y1, x1), ypx)
    b = fe.mul(fe.sub(y1, x1), ymx)
    c = fe.mul(xy2d, t1)
    d = fe.mul_small(z1, 2)
    e = fe.sub(a, b)
    f = fe.sub(d, c)
    g = fe.add(d, c)
    h = fe.add(a, b)
    return fe.mul(e, f), fe.mul(g, h), fe.mul(f, g), fe.mul(e, h)


def madd(p: jnp.ndarray, rows: jnp.ndarray) -> jnp.ndarray:
    """Mixed add: extended (4, 17, ...) + Niels rows (ROW, ...)."""
    x, y, z, t = _madd_tuple(p[0], p[1], p[2], p[3], rows)
    return jnp.stack([x, y, z, t], axis=0)


_IDENT_LIMBS: Optional[np.ndarray] = None


def ref_identity_limbs() -> np.ndarray:
    global _IDENT_LIMBS
    if _IDENT_LIMBS is None:
        _IDENT_LIMBS = np.stack(
            [fe._int_to_limbs_np(c % ref.P) for c in ref.IDENTITY]
        )
    return _IDENT_LIMBS


def _ident_like(batch_ref: jnp.ndarray) -> jnp.ndarray:
    """(4, 17, B) identity accumulator. Derived from a batch-varying array
    (not a broadcast constant) so the loop carry inherits the data's
    varying manual axes under shard_map."""
    ident = jnp.asarray(ref_identity_limbs())[:, :, None]  # (4, 17, 1)
    return ident + (batch_ref * 0)[None, None]


def _gather_rows(flat_table: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """One flat fetch of every position's row, staged position-major.

    flat_table: (M/2, LINE), rows 2i and 2i+1 side by side on line i.
    idx: (NPOS, B) row indices. -> (NPOS, ROW, B).
    A single big `take` keeps the gather dense (the per-position-in-loop
    form is ~20x slower on TPU); the transpose to batch-minor happens once
    here, not per position.

    Why two rows a line. The TPU holds an (M, 64) int32 array
    column-major (its compact layout: 64 words would pad to a 128-lane
    line), and a row gather wants it row-major, so a program over that
    shape first copies the WHOLE table into the padded row-major form: a
    temporary of twice the table, written every pass (a third to two
    thirds of a pass's device time at a few hundred MB; 9.7 GB beside a
    4.8 GB table). Two rows fill the 128 lanes, the array is compact and
    row-major at once, and the program fetches the line and keeps the
    half it wants: twice the gathered bytes (268 MB at the largest
    bucket), no copy.
    """
    npos, b = idx.shape
    flat = idx.reshape(-1)
    lines = jnp.take(flat_table, flat >> 1, axis=0)  # (NPOS*B, LINE)
    odd = (flat & 1).astype(jnp.bool_)[:, None]
    rows = jnp.where(odd, lines[:, ROW:], lines[:, :ROW])
    return rows.reshape(npos, b, ROW).transpose(0, 2, 1)


def fused_accumulate(
    s_windows: jnp.ndarray,
    k_windows: jnp.ndarray,
    row_base: jnp.ndarray,
    f_flat: jnp.ndarray,
) -> jnp.ndarray:
    """[S]B + [k](−A) via the fused dual-scalar table: one row fetch + one
    mixed add per window position (NPOS = 64 in all).

    s_windows, k_windows: (NPOS, B) int32. row_base: (B,) int32 =
    key_index * ROWS_PER_KEY. f_flat: (n_keys*ROWS_PER_KEY/2, LINE).

    The madd loop runs either as plain XLA (fori_loop) or as a Pallas
    kernel that keeps the accumulator and every field-mul intermediate in
    VMEM across all positions; `_resolve_accum_impl` says which.
    """
    npos = s_windows.shape[0]
    pos = jnp.arange(npos, dtype=jnp.int32)[:, None]
    idx = row_base[None, :] + pos * (WINDOW * WINDOW) + s_windows * WINDOW + k_windows
    rows_all = _gather_rows(f_flat, idx)  # (npos, ROW, B)
    impl = _resolve_accum_impl()
    if impl in ("pallas", "pallas_interpret"):
        return _madd_loop_pallas(rows_all, interpret=impl == "pallas_interpret")
    acc0 = _ident_like(s_windows[0])

    def body(i, acc):
        return madd(acc, rows_all[i])

    return lax.fori_loop(0, npos, body, acc0)


# ---------------------------------------------------------------------------
# Pallas madd-loop: the whole 64-position accumulation as ONE kernel.
#
# The XLA fori_loop materializes the (4, 17, B) accumulator in HBM every
# iteration and streams each field-mul intermediate through HBM when the
# fusion boundary falls badly. The Pallas kernel tiles the batch, holds the
# four coordinates in VMEM/vector registers across all 64 madds, and only
# the gathered table rows stream in — per-item HBM traffic drops to the
# 64 x 256-byte rows it can't avoid.
# ---------------------------------------------------------------------------

ACCUM_IMPL = "auto"
PALLAS_TILE = 256  # batch lanes per kernel program (rows block = 4 MiB)


def use_accum_impl(name: str) -> None:
    """The test seam over the accumulator ('auto', 'xla', 'pallas' or
    'pallas_interpret'), to be set BEFORE the kernel is traced — jit
    traces capture the choice. 'auto' is what the program runs and
    resolves at trace time from the platform: the Pallas kernel on a
    TPU, the XLA fori_loop elsewhere. 'pallas' means the Mosaic-compiled
    kernel and is an error where Mosaic cannot run; the interpreter is
    only ever what a test asks for by name ('pallas_interpret'), never
    what a backend silently gets."""
    global ACCUM_IMPL
    if name not in ("auto", "xla", "pallas", "pallas_interpret"):
        raise ValueError(
            f"accum impl must be auto|xla|pallas|pallas_interpret, got {name!r}"
        )
    ACCUM_IMPL = name


def _resolve_accum_impl() -> str:
    if ACCUM_IMPL != "auto":
        return ACCUM_IMPL
    import jax

    return "pallas" if jax.default_backend() == "tpu" else "xla"


def _madd_loop_kernel(rows_ref, out_ref):
    """Pallas body: rows_ref (npos, ROW, T) VMEM block -> out_ref
    (4*NLIMB, T) — the accumulated [S]B + [k](−A) in extended coords."""
    n = fe.NLIMB
    tile = out_ref.shape[-1]
    # identity point (0, 1, 1, 0): built from scalars via iota so the
    # kernel captures no array constants (a Pallas requirement)
    limb0 = lax.broadcasted_iota(jnp.int32, (n, tile), 0) == 0
    zero = jnp.zeros((n, tile), jnp.int32)
    one = jnp.where(limb0, 1, 0)

    def body(i, acc):
        return _madd_tuple(*acc, rows_ref[i])

    x, y, z, t = lax.fori_loop(0, rows_ref.shape[0], body, (zero, one, one, zero))
    out_ref[0 * n : 1 * n] = x
    out_ref[1 * n : 2 * n] = y
    out_ref[2 * n : 3 * n] = z
    out_ref[3 * n : 4 * n] = t


def _madd_loop_pallas(rows_all: jnp.ndarray, interpret: bool) -> jnp.ndarray:
    """(npos, ROW, B) gathered rows -> (4, 17, B) accumulator.

    `interpret` is the caller's explicit choice (tests); it is never
    inferred from the backend, so a run that says "pallas" ran Mosaic."""
    import jax
    from jax.experimental import pallas as pl

    if not interpret and jax.default_backend() != "tpu":
        raise RuntimeError(
            "accum='pallas' is the Mosaic-compiled kernel and needs a TPU "
            f"backend (have {jax.default_backend()!r}); use 'xla', or "
            "'pallas_interpret' in a test"
        )
    npos, b = rows_all.shape[0], rows_all.shape[-1]
    tile = min(PALLAS_TILE, b)
    assert b % tile == 0, (b, tile)
    out = pl.pallas_call(
        _madd_loop_kernel,
        out_shape=jax.ShapeDtypeStruct((4 * fe.NLIMB, b), jnp.int32),
        grid=(b // tile,),
        in_specs=[
            pl.BlockSpec((npos, ROW, tile), lambda i: (0, 0, i)),
        ],
        out_specs=pl.BlockSpec((4 * fe.NLIMB, tile), lambda i: (0, i)),
        interpret=interpret,
    )(rows_all)
    return out.reshape(4, fe.NLIMB, b)


def _interleave(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """(17, m), (17, m) -> (17, 2m) alternating a0 b0 a1 b1 ..."""
    return jnp.stack([a, b], axis=2).reshape(a.shape[0], -1)


CHAIN_WIDTH = 128  # one full VREG of lanes: the Fermat chain is as cheap
# on (17, 128) as on (17, 1), so the tree stops here — levels below it
# would run 64..1-wide on 128-wide vector lanes, a sequential tail that
# does ~1.3% of the pass's field muls.


def batch_invert(z: jnp.ndarray) -> jnp.ndarray:
    """Tree-structured Montgomery batch inversion: (17, B) -> (17, B).

    Pairwise products up the tree (log2(B/CHAIN_WIDTH) batched muls
    totalling ≈ B multiplies), ONE lane-parallel Fermat chain across the
    whole CHAIN_WIDTH-wide root level, then unfold back down (≈ 2B
    multiplies). Requires B a power of two and all inputs nonzero —
    guaranteed for Z coordinates of complete Edwards formulas.
    """
    n = z.shape[1]
    assert n & (n - 1) == 0, "batch_invert requires a power-of-two batch"
    levels = []
    cur = z
    while cur.shape[1] > CHAIN_WIDTH:
        levels.append(cur)
        cur = fe.mul(cur[:, 0::2], cur[:, 1::2])
    inv = fe.invert(cur)  # the only exponentiation chain, all lanes busy
    for lev in reversed(levels):
        left, right = lev[:, 0::2], lev[:, 1::2]
        inv = _interleave(fe.mul(inv, right), fe.mul(inv, left))
    return inv


def _encode_and_compare(
    p: jnp.ndarray, r_y: jnp.ndarray, r_sign: jnp.ndarray, precheck: jnp.ndarray
) -> jnp.ndarray:
    """Affine-normalize the accumulator (batch inversion) and compare its
    canonical encoding against R's wire bytes."""
    zinv = batch_invert(p[2])
    x_aff = fe.mul(p[0], zinv)
    y_aff = fe.mul(p[1], zinv)
    ok = fe.eq(y_aff, r_y) & (fe.parity(x_aff) == r_sign)
    return ok & precheck


def fused_verify_kernel(
    s_windows: jnp.ndarray,  # (NPOS, B) int32 — S scalar windows
    k_windows: jnp.ndarray,  # (NPOS, B) int32 — challenge scalar windows
    a_index: jnp.ndarray,  # (B,) int32 — key row into the fused table bank
    f_table: jnp.ndarray,  # (n_keys*ROWS_PER_KEY/2, LINE) Niels rows
    r_y: jnp.ndarray,  # (17, B) int32 — R's canonical y limbs
    r_sign: jnp.ndarray,  # (B,) int32 — R's x sign bit
    precheck: jnp.ndarray,  # (B,) bool — host-side validity mask
) -> jnp.ndarray:
    """Batched verify via the fused comb: one row fetch + one madd per
    window position. The body fused_verify_wire_kernel calls once the
    wire bytes are unpacked."""
    p = fused_accumulate(s_windows, k_windows, a_index * ROWS_PER_KEY, f_table)
    return _encode_and_compare(p, r_y, r_sign, precheck)


def fused_verify_wire_kernel(
    wire: jnp.ndarray,  # (B, 96) uint8 — S (32) ‖ k (32) ‖ R (32) raw bytes
    a_index: jnp.ndarray,  # (B,) int32 — key row into the fused table bank
    f_table: jnp.ndarray,  # (n_keys*ROWS_PER_KEY/2, LINE) Niels rows
    precheck: jnp.ndarray,  # (B,) bool — host-side validity mask
) -> jnp.ndarray:
    """The verify kernel: RAW wire bytes in, one (B, 96) uint8 array per
    batch; scalar-window extraction, R limb decomposition and the sign
    bit all happen on device (fe.extract_windows_dev), then
    fused_verify_kernel.

    ~100 bytes/item cross the host->device link (int32 windows + limbs
    would be ~290) and the host does no unpack work; XLA fuses the byte
    shuffling into the kernel prologue."""
    s_w = fe.extract_windows_dev(wire[:, 0:32], WBITS, NPOS)
    k_w = fe.extract_windows_dev(wire[:, 32:64], WBITS, NPOS)
    r_y = fe.extract_windows_dev(wire[:, 64:96], fe.RADIX, fe.NLIMB)
    r_sign = wire[:, 95].astype(jnp.int32) >> 7
    return fused_verify_kernel(s_w, k_w, a_index, f_table, r_y, r_sign, precheck)
