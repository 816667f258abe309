"""Static device cost model for the verify programs (ISSUE 14).

For each jit shape (mode, window, bucket) of the device ledger, what the
program draws is an analytic function of the geometry. ``fused``, the
comb over a key's table:

- **table-row gathers**: ONE Niels row per window position per item
  (the (s_nibble, k_nibble) pair indexes a joint table), fetched as the
  512 B table line that holds it and its neighbour (comb._gather_rows):
  64 lines, 32,768 B an item.
- **madds**: one mixed Edwards add per gathered row.
- **host->device wire bytes**: what the staging path ships per item
  (S||k||R + key index + precheck, 101 B).

``ladder``, the table-free program for a key with no table
(ops/ladder.py): no key-table gather at all (its 64 line fetches an item
are of B's 16 multiples, a 4 KiB constant), 64 windows of four doublings
and two adds, ``ladder.FIELD_MULS`` field multiplies an item where the
comb has 453, and 129 B an item over the link (the key's 32 bytes ride
with the row).

``tools/verify_observatory.py`` joins these per-shape constants with
the device ledger's measured per-shape dispatch counts into the bytes
the kernel gathered and the share of wall clock each stage took.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from ..ops import comb, ladder

# rough int-op cost of one mixed Edwards add on 17-limb field elements
# (~8 field muls of 17x17 limb products, mul+add each): used only for
# arithmetic-intensity context, never for a pass/fail verdict.
MADD_INT_OPS = 8 * 17 * 17 * 2
# the same rough count for one field multiply
MUL_INT_OPS = 17 * 17 * 2


def shape_cost(mode: str, window: int, bucket: int) -> Dict[str, Any]:
    """Per-item and per-pass analytic costs for one jit shape.

    ``mode`` is the ledger's spelling: ``fused`` is the comb, ``ladder``
    the table-free program; any other lane mode (the QC lane's
    ``pairing``) returns a zero-gather row so callers can sum blindly.
    ``gathers_per_item`` are fetches from a KEY's table, so the ladder
    has none.
    """
    row_bytes = comb.LINE * 4  # the line fetched for one row
    gathers = madds = wire = flops = 0
    if mode == "fused":
        gathers = madds = comb.NPOS  # joint (s, k) window: one table line/pos
        wire = 96 + 4 + 1  # S||k||R + a_idx + precheck per item
        flops = madds * MADD_INT_OPS
    elif mode == "ladder":
        madds = 2 * ladder.NPOS  # one add a scalar a window, and 256 doublings
        wire = ladder.ROW_BYTES + 1  # S||k||R||A + precheck per item
        flops = ladder.FIELD_MULS * MUL_INT_OPS
    gb_item = gathers * row_bytes
    return {
        "mode": mode,
        "window": window,
        "bucket": bucket,
        "gathers_per_item": gathers,
        "row_bytes": row_bytes,
        "gather_bytes_per_item": gb_item,
        "gather_bytes_per_pass": gb_item * bucket,
        "madds_per_item": madds,
        "flops_per_item": flops,
        "wire_bytes_per_item": wire,
    }


def parse_shape_key(key: str) -> Optional[Dict[str, Any]]:
    """``"ed25519:fused/w4/b8192"`` (the device ledger's lane-qualified
    shapes key; a bare ``"fused/w4/b8192"`` parses too) ->
    {"lane": ..., "mode": ..., "window": ..., "bucket": ...}; None if
    malformed."""
    try:
        lane, _, rest = key.rpartition(":")
        mode, w, b = rest.split("/")
        if not (w.startswith("w") and b.startswith("b")):
            return None
        return {"lane": lane, "mode": mode,
                "window": int(w[1:]), "bucket": int(b[1:])}
    except (ValueError, AttributeError):
        return None


def gather_bytes_for_shapes(shapes: Dict[str, Dict[str, int]]) -> int:
    """Total analytic table-gather bytes implied by a device-ledger
    ``shapes`` block (each row carries dispatches/items; gathers cover
    the PADDED bucket — pad rows gather garbage but still burn
    bandwidth, which is exactly why pad waste is a ledger column)."""
    total = 0
    for key, row in shapes.items():
        parsed = parse_shape_key(key)
        if parsed is None:
            continue
        cost = shape_cost(parsed["mode"], parsed["window"], parsed["bucket"])
        total += cost["gather_bytes_per_pass"] * int(row.get("dispatches", 0))
    return total
