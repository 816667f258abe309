"""chip_ladder.py — the table-free verify program at the sizes it is timed
at, on the chip, against the RFC 8032 oracle.

chip_smoke.py's ``kernel`` stage for the second program (ops/ladder.py):
a ``TpuVerifier`` whose bank is full of keys no item uses, warmed through
``warm_for_population`` with a population larger than the bank (so the
ladder's buckets are warmed as production warms them), then seeded batches
of 8,192 and 128 rows, every row under a key with no table, with every
kind of failure the benchmark plants plus the key-side edge vectors,
through ``TpuVerifier.verify_batch``, compared with
``crypto/ed25519_cpu.verify`` item for item. It also prints what each
program's pass costs at each bucket, after the warm.

    chiprun -- python chip_ladder.py            # about 3 minutes
    python chip_ladder.py --cpu-dry-run         # tiny, for debugging

The last line of stdout is one JSON object, ``{"ok": true, "device": ...}``;
any disagreement is a nonzero exit. Not a benchmark.
"""

from __future__ import annotations

import argparse
import json
import random
import sys
import time

from chip_smoke import _not_a_point, say

OCCUPIERS = 8  # keys that fill the 8-key bank and sign nothing


def edge_keys(ref) -> dict:
    """Key encodings the two programs and the oracle must treat alike."""
    small = [
        (1).to_bytes(32, "little"),                      # identity, order 1
        (ref.P - 1).to_bytes(32, "little"),              # order 2
        (0).to_bytes(32, "little"),                      # order 4
        bytes.fromhex("26e8958fc2b227b045c3f489f2ef98f0"
                      "d5dfac05d3c63339b13802886d53fc05"),  # order 8
    ]
    return {
        "A.y >= p": (ref.P + 3).to_bytes(32, "little"),
        "A.y = 2^255 - 1": b"\xff" * 31 + b"\x7f",
        "x = 0 with the sign bit set": (1 | 1 << 255).to_bytes(32, "little"),
        **{f"small-order A ({i})": k for i, k in enumerate(small)},
    }


def signed(signers, n: int, seed: int) -> list:
    from simple_pbft_tpu.crypto.verifier import BatchItem

    rng = random.Random(seed)
    items = []
    for i in range(n):
        s = signers[i % len(signers)]
        msg = b"ladder %d %d " % (seed, i) + rng.randbytes(16)
        items.append(BatchItem(s.pub, msg, s.sign(msg)))
    return items


def batch(ref, signers, n: int, seed: int):
    """n signed items, the planted edits among them -> (items, planted)."""
    from simple_pbft_tpu.crypto.verifier import BatchItem

    rng = random.Random(seed)
    items = signed(signers, n, seed)
    edges = edge_keys(ref)
    spots = rng.sample(range(n), 8 + len(edges))
    planted: dict = {}

    def plant(kind: str, **kw) -> None:
        pos = spots[len(planted)]
        it = items[pos]
        planted[pos] = kind
        items[pos] = BatchItem(kw.get("pubkey", it.pubkey), it.msg,
                               kw.get("sig", it.sig))

    it = items[spots[0]]
    flipped = bytearray(it.sig)
    flipped[rng.randrange(64)] ^= 1 << rng.randrange(8)
    plant("flipped signature byte", sig=bytes(flipped))
    it = items[spots[1]]
    other = next(s for s in signers if s.pub != it.pubkey)
    plant("signed by another key", sig=other.sign(it.msg))
    it = items[spots[2]]
    s_big = int.from_bytes(it.sig[32:], "little") + ref.L
    plant("S >= L", sig=it.sig[:32] + s_big.to_bytes(32, "little"))
    it = items[spots[3]]
    plant("non-canonical R.y",
          sig=(ref.P + 1).to_bytes(32, "little") + it.sig[32:])
    plant("wrong-length key", pubkey=items[spots[4]].pubkey[:31])
    plant("wrong-length signature", sig=items[spots[5]].sig[:63])
    plant("key not a curve point", pubkey=_not_a_point(rng))
    for kind, key in edges.items():
        plant(kind, pubkey=key)
    # the one edit that VERIFIES: under the identity as key [k]A vanishes,
    # so (R = [r]B, S = r) is a signature of any message
    r = rng.randrange(1, ref.L)
    plant("identity key, R = [r]B, S = r: accepted",
          pubkey=(1).to_bytes(32, "little"),
          sig=ref.point_compress(ref.point_mul(r, ref.B))
          + r.to_bytes(32, "little"))
    return items, planted


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cpu-dry-run", action="store_true")
    ap.add_argument("--seed", type=int, default=2147483693)
    args = ap.parse_args(argv)

    import simple_pbft_tpu

    cache = simple_pbft_tpu.enable_jit_cache()
    stamp = simple_pbft_tpu.select_platform(not args.cpu_dry_run,
                                            "chip_ladder.py")
    say("identify", compile_cache=cache, **stamp)

    from simple_pbft_tpu.config import make_test_committee
    from simple_pbft_tpu.crypto import ed25519_cpu as ref
    from simple_pbft_tpu.crypto import tpu_verifier as tv
    from simple_pbft_tpu.crypto.signer import Signer
    from simple_pbft_tpu.ops import ladder

    sizes = (32, 16) if args.cpu_dry_run else (8192, 128)
    _cfg, keys = make_test_committee(n=4, clients=OCCUPIERS + 252)
    signers = [Signer(name, kp.seed) for name, kp in keys.items()]
    device = tv.TpuVerifier(initial_keys=OCCUPIERS)
    t0 = time.perf_counter()
    device.warm_for_population([s.pub for s in signers], max_sweep=sizes[0])
    bank = device._bank
    say("warm", seconds=round(time.perf_counter() - t0, 1),
        bank_keys=len(bank._index), bank_capacity=bank._cap,
        population=len(signers), field_muls_per_ladder_row=ladder.FIELD_MULS)
    for row in device.warm_log:
        say("warm", **row)
    ok = len(bank._index) == bank._cap == OCCUPIERS
    uncached = signers[OCCUPIERS:]

    for n in sizes:
        items, planted = batch(ref, uncached, n, args.seed + n)
        before = device.shape_snapshot()
        walls = []
        for _ in range(3):
            t0 = time.perf_counter()
            got = device.verify_batch(items)
            walls.append(round((time.perf_counter() - t0) * 1e3, 2))
        after = device.shape_snapshot()
        t0 = time.perf_counter()
        want = [ref.verify(it.pubkey, it.msg, it.sig) for it in items]
        oracle_s = time.perf_counter() - t0
        wrong = [i for i in range(n) if got[i] != want[i]]
        for i in wrong[:20]:
            say("check", failed=json.dumps(
                f"item {i} ({planted.get(i, 'good')}): device {got[i]}, "
                f"oracle {want[i]}"))
        rejected = [i for i in range(n) if not want[i]]
        # every row with a well-formed key and signature took the ladder
        took = (after["ladder_items"] - before["ladder_items"]) // 3
        ok &= (not wrong and len(rejected) == len(planted) - 1
               and set(rejected) < set(planted)
               and after["overcap_fallback_items"] == 0
               and after["post_warm_compiles"] == 0 and took == n - 2)
        say("ladder", rows=n, planted=len(planted), rejected=len(rejected),
            disagreements=len(wrong), ladder_rows=took,
            verify_batch_wall_ms=json.dumps(walls),
            oracle_s=round(oracle_s, 1))

    # what a pass of each program costs at each bucket, warm: the comb on
    # rows under the occupiers' keys, the ladder on rows under the others
    pools = (("comb", signed(signers[:OCCUPIERS], sizes[0], args.seed)),
             ("ladder", signed(uncached, sizes[0], args.seed)))
    for b in [r["bucket"] for r in device.warm_log if r["program"] == "comb"]:
        for program, pool in pools:
            items = pool[:b]
            walls = []
            for _ in range(3):
                t0 = time.perf_counter()
                device.verify_batch(items)
                walls.append(round((time.perf_counter() - t0) * 1e3, 2))
            say("pass", program=program, bucket=b, wall_ms=json.dumps(walls))

    say("done", ok=str(ok).lower(), post_warm_compiles=device.post_warm_compiles)
    print(json.dumps({"ok": bool(ok), "device": {
        "platform": stamp["platform"], "kind": stamp["device_kind"],
        "count": stamp["device_count"]}}), flush=True)
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    main()
