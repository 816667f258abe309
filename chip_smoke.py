"""chip_smoke.py — the quickest proof that the served path starts on the chip.

Drives, in ONE process (a chip belongs to one process; nothing here starts
a child that imports JAX), the path a BFT operator runs:

    client -> transport/local -> consensus/replica
           -> crypto/coalesce.VerifyService -> crypto/tpu_verifier.TpuVerifier
           -> device

at ladder entry "3" of bench_consensus.py (BASELINE.json config 3): a
LocalCommittee of n=64 plain-Ed25519 replicas (f=21), block batch 256,
checkpoint_interval=64, watermark_window=1024, speculation at its default,
KVStore — with 8 signing clients (config 3 names 1,000; that is ROADMAP R1)
and 128 requests in flight, every replica sharing the one
``node.make_verifier("tpu", pubkeys)`` a node process would build: fused
kernel, w=4, key bank capacity 128 (537 MB of tables resident on the
device), warmed over all six buckets up to 8,192.

Stages, in order; any failed check or exception in any of them is a nonzero
exit, and no stage runs after a failed one:

  identify  what JAX runs on, versions, the compile cache in force, which
            natives loaded and from what, the signer backend, the
            accumulator the kernel resolves to. Not a TPU, or a native
            missing: exit nonzero.
  warm      build + warm the verifier; seconds per bucket and whether the
            persistent cache served it.
  kernel    one seeded 8,192-item batch from the committee's 72 keys with
            planted failures, through TpuVerifier.verify_batch; every
            planted item and a seeded sample of good ones equal
            crypto/ed25519_cpu.verify item for item; the lowered program
            holds the Mosaic custom call (the Pallas kernel was compiled,
            not interpreted).
  served    2,048 puts over 512 keys through Client.submit, then 64
            read-backs, each equal to the last acknowledged value; every
            replica ends at the same executed_seq and state digest; zero
            client timeouts or give-ups.
  chip      VerifyService.snapshot(): the device did the verifying and none
            of the production CPU routes (watchdog failover, quarantine,
            reroute, overload rejection, over-cap fallback) or a post-warm
            compile was taken.

What it prints (req/s, latency, device share, compile seconds) is
information stamped with the device it came from — not a benchmark.

The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.

``--cpu-dry-run`` is the only way to run it without a TPU: tiny sizes, for
tier-1 and for debugging before chip time is spent. It prints
``platform: cpu`` and ``dry_run: true`` and is never chosen automatically.
"""

from __future__ import annotations

import argparse
import asyncio
import faulthandler
import json
import random
import statistics
import sys
import time
from importlib import metadata

# the served path at full size, and the dry run's cut of it. `block` is the
# committee's max_batch (requests per block); `max_batch`/`cpu_cutoff` are
# VerifyService's (None = its adaptive default). The dry run pins the
# cutoff to 0 so its few small piles still take the device route it is
# there to exercise, and caps takes at a bucket it warms.
REAL = dict(
    n=64, clients=8, outstanding=128, block=256, puts=2048, keys=512,
    gets=64, kernel_batch=8192, sample=256, max_batch=8192, cpu_cutoff=None,
)
DRY = dict(
    n=4, clients=2, outstanding=2, block=4, puts=32, keys=8,
    gets=4, kernel_batch=32, sample=8, max_batch=32, cpu_cutoff=0,
)
# the smoke's contract is 1,200 s, compilation included: past this a
# wedge becomes every thread's stack on stderr and a nonzero exit
TIME_LIMIT_S = 1150


def say(stage: str, **fields) -> None:
    print(f"{stage}: " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


class SmokeFailure(RuntimeError):
    """A check did not hold. Never caught here: the traceback and the
    nonzero exit are the report."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def identify(dry_run: bool) -> dict:
    import jax
    import jaxlib

    import simple_pbft_tpu
    from simple_pbft_tpu import native
    from simple_pbft_tpu.crypto import signer
    from simple_pbft_tpu.ops import comb

    cache = simple_pbft_tpu.enable_jit_cache()  # before the first jit
    stamp = simple_pbft_tpu.select_platform(
        not dry_run, "chip_smoke.py (no --cpu-dry-run)"
    )
    say("identify", **stamp, dry_run=str(dry_run).lower())
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "absent"
    say("identify", jax=jax.__version__, jaxlib=jaxlib.__version__,
        libtpu=libtpu, python=sys.version.split()[0])
    say("identify", compile_cache=cache,
        jax_config=jax.config.jax_compilation_cache_dir)
    natives = native.status()
    for name, st in natives.items():
        say("identify", native=name, loaded=st["loaded"],
            built_this_run=st["built"], path=st["path"])
    missing = [name for name, st in natives.items() if not st["loaded"]]
    check(not missing, f"natives on the Python fallback: {missing}")
    say("identify",
        signer="cryptography" if signer._HAVE_OPENSSL else "pure-python",
        accum=comb._resolve_accum_impl())
    return stamp


def build_verifier(size: dict, pubkeys):
    from simple_pbft_tpu.node import make_verifier

    t0 = time.perf_counter()
    service = make_verifier(
        "tpu", pubkeys, max_batch=size["max_batch"],
        cpu_cutoff=size["cpu_cutoff"],
    )
    device = service.device
    bank = device._bank
    say("warm", seconds=round(time.perf_counter() - t0, 1),
        mode=device._mode, window=device._window, keys=len(bank._index),
        bank_capacity=bank._cap, table_mb=round(bank._np.nbytes / 1e6))
    for row in device.warm_log:
        # a hit = every compile this bucket asked for came off the disk
        hit = 0 < row["compile_requests"] == row["cache_hits"]
        say("warm", bucket=row["bucket"], seconds=row["seconds"],
            cache_hit=str(hit).lower(),
            compile_requests=row["compile_requests"],
            cache_hits=row["cache_hits"])
    return service


def _not_a_point(rng: random.Random) -> bytes:
    from simple_pbft_tpu.crypto import ed25519_cpu as ref

    while True:
        cand = bytes([rng.randrange(256) for _ in range(31)] + [0])
        if ref.point_decompress(cand) is None:
            return cand


def kernel_stage(size: dict, seed: int, keys, device, stamp: dict,
                 dry_run: bool) -> None:
    """One seeded batch with planted failures through
    TpuVerifier.verify_batch, compared with the RFC 8032 oracle."""
    from simple_pbft_tpu.crypto import ed25519_cpu as ref
    from simple_pbft_tpu.crypto.signer import Signer
    from simple_pbft_tpu.crypto.verifier import BatchItem
    from simple_pbft_tpu.ops import comb

    rng = random.Random(seed)
    batch = size["kernel_batch"]
    signers = [Signer(name, kp.seed) for name, kp in keys.items()]
    items = []
    for i in range(batch):
        s = signers[i % len(signers)]
        msg = b"chip_smoke %d %d " % (seed, i) + rng.randbytes(16)
        items.append(BatchItem(s.pub, msg, s.sign(msg)))
    spots = rng.sample(range(batch), 7)
    planted = {}

    def plant(kind: str, item: BatchItem) -> None:
        pos = spots[len(planted)]
        planted[pos] = kind
        items[pos] = item

    it = items[spots[0]]
    flipped = bytearray(it.sig)
    flipped[rng.randrange(64)] ^= 1 << rng.randrange(8)
    plant("flipped signature byte", BatchItem(it.pubkey, it.msg, bytes(flipped)))
    it = items[spots[1]]
    other = next(s for s in signers if s.pub != it.pubkey)
    plant("signed by another committee key",
          BatchItem(it.pubkey, it.msg, other.sign(it.msg)))
    it = items[spots[2]]
    s_big = int.from_bytes(it.sig[32:], "little") + ref.L
    plant("S >= L", BatchItem(
        it.pubkey, it.msg, it.sig[:32] + s_big.to_bytes(32, "little")))
    it = items[spots[3]]
    plant("non-canonical R.y", BatchItem(
        it.pubkey, it.msg, (ref.P + 1).to_bytes(32, "little") + it.sig[32:]))
    it = items[spots[4]]
    plant("wrong-length key", BatchItem(it.pubkey[:31], it.msg, it.sig))
    it = items[spots[5]]
    plant("wrong-length signature", BatchItem(it.pubkey, it.msg, it.sig[:63]))
    it = items[spots[6]]
    plant("key not a curve point", BatchItem(_not_a_point(rng), it.msg, it.sig))

    t0 = time.perf_counter()
    got = device.verify_batch(items)
    wall = time.perf_counter() - t0
    check(len(got) == batch, f"kernel: {len(got)} verdicts for {batch} items")

    # compared outside any timing
    good = [i for i in range(batch) if i not in planted]
    sample = rng.sample(good, size["sample"])
    for pos in sorted(planted) + sample:
        it = items[pos]
        want = ref.verify(it.pubkey, it.msg, it.sig)
        check(got[pos] == want,
              f"kernel: item {pos} ({planted.get(pos, 'good')}) device says "
              f"{got[pos]}, oracle says {want}")
    check(not any(got[p] for p in planted), "kernel: a planted failure verified")
    check(all(got[i] for i in good), "kernel: a good signature was rejected")
    say("kernel", batch=batch, keys=len(signers), planted=len(planted),
        sampled_good=len(sample), agrees_with_oracle="true",
        verify_batch_wall_ms=round(wall * 1e3, 1),
        device_kind=stamp["device_kind"])

    accum = comb._resolve_accum_impl()
    if dry_run:
        say("kernel", mosaic="n/a (dry run)", accum=accum)
        return
    check(accum == "pallas", f"kernel: accumulator resolved to {accum!r}")
    text = device.lowered_text(batch)
    check("tpu_custom_call" in text,
          "kernel: no Mosaic custom call in the lowered program")
    say("kernel", mosaic="tpu_custom_call present", accum=accum,
        interpret="false", pallas_tile=comb.PALLAS_TILE)


async def served_stage(size: dict, seed: int, service, pubkeys,
                       stamp: dict) -> None:
    """Writes then read-backs through Client.submit on the committee of
    bench_consensus.run_config's clean (no storm, no chaos) cell."""
    from simple_pbft_tpu.committee import LocalCommittee

    rng = random.Random(seed + 1)
    n, pumps = size["n"], size["outstanding"]
    com = LocalCommittee.build(
        n=n, clients=size["clients"], verifier_factory=lambda: service,
        max_batch=size["block"], view_timeout=30.0, checkpoint_interval=64,
        watermark_window=1024,
    )
    check([kp.pub for kp in com.keys.values()] == pubkeys,
          "served: committee keys differ from the warmed population")
    for c in com.clients:
        c.request_timeout = 30.0
    say("served", n=n, f=com.cfg.f, reply_quorum=com.cfg.weak_quorum,
        clients=len(com.clients), in_flight=pumps, block=size["block"],
        speculative=str(com.cfg.speculative).lower())

    # each pump owns its keys and writes them in sequence, so two writes
    # to one key are never in flight together and "the last acknowledged
    # value" is exact
    names = [f"k{seed}_{i}" for i in range(size["keys"])]
    plans = [[] for _ in range(pumps)]
    for i in range(size["puts"]):
        ki = i % len(names)
        plans[ki % pumps].append((names[ki], f"v{rng.getrandbits(48):x}"))
    acked: dict = {}
    latencies: list = []

    async def pump(client, plan) -> None:
        for key, value in plan:
            t0 = time.perf_counter()
            result = await client.submit(f"put {key} {value}")
            latencies.append(time.perf_counter() - t0)
            check(result == "ok", f"served: put {key} answered {result!r}")
            acked[key] = value

    com.start()
    t0 = time.perf_counter()
    await asyncio.gather(*(
        pump(com.clients[i % len(com.clients)], plan)
        for i, plan in enumerate(plans)
    ))
    put_wall = time.perf_counter() - t0
    check(len(latencies) == size["puts"] and len(acked) == len(names),
          "served: not every put was acknowledged")

    async def read_back(client, key) -> None:
        result = await client.submit(f"get {key}")
        check(result == acked[key],
              f"served: get {key} = {result!r}, last acknowledged "
              f"{acked[key]!r}")

    sample = rng.sample(names, size["gets"])
    await asyncio.gather(*(
        read_back(com.clients[i % len(com.clients)], key)
        for i, key in enumerate(sample)
    ))

    # laggards finish executing what the quorum already committed
    deadline = time.perf_counter() + 60.0
    running = [r for r in com.replicas if r._running]
    while (len({r.executed_seq for r in running}) > 1
           and time.perf_counter() < deadline):
        await asyncio.sleep(0.05)
    seqs = {r.executed_seq for r in running}
    digests = {r.app.state_digest() for r in running}
    client_metrics = {
        k: sum(c.metrics.get(k, 0) for c in com.clients)
        for k in ("request_timeouts", "retransmissions", "spec_accepted",
                  "spec_final_mismatch")
    }
    await com.stop()
    check(len(running) == n, f"served: {len(running)}/{n} replicas running")
    check(len(seqs) == 1, f"served: executed_seq differs: {sorted(seqs)}")
    check(len(digests) == 1, "served: state digests differ across replicas")
    check(client_metrics["request_timeouts"] == 0
          and client_metrics["spec_final_mismatch"] == 0,
          f"served: client trouble {client_metrics}")
    say("served", puts=size["puts"], distinct_keys=len(names),
        gets=len(sample), read_backs_equal="true",
        replicas_agree=len(running), executed_seq=seqs.pop(),
        state_digest=digests.pop()[:16], **client_metrics)
    say("served", committed_req_s=round(size["puts"] / put_wall, 1),
        median_latency_ms=round(statistics.median(latencies) * 1e3, 1),
        max_latency_ms=round(max(latencies) * 1e3, 1),
        put_wall_s=round(put_wall, 2), device_kind=stamp["device_kind"])


def chip_stage(service, stamp: dict) -> None:
    """The chip did the work: none of the routes that keep production
    alive without it was taken."""
    snap = service.snapshot()
    shapes = snap["device_shapes"]
    must_be_zero = {
        "watchdog_failovers": snap["watchdog_failovers"],
        "quarantine_entries": snap["quarantine_entries"],
        "cpu_reroute_items": snap["cpu_reroute_items"],
        "overload_rejections": snap["overload_rejections"],
        "post_warm_compiles": shapes["post_warm_compiles"],
        "overcap_fallback_items": shapes["overcap_fallback_items"],
    }
    say("chip", device_pass_items=snap["device_pass_items"],
        device_passes=snap["device_passes"], **must_be_zero)
    check(snap["device_pass_items"] > 0,
          "chip: device_pass_items == 0 — the device verified nothing")
    bad = {k: v for k, v in must_be_zero.items() if v}
    check(not bad, f"chip: a CPU route or a late compile was taken: {bad}")
    verified = snap["device_pass_items"] + snap["cpu_pass_items"]
    say("chip",
        device_share=round(snap["device_pass_items"] / verified, 4),
        cpu_pass_items=snap["cpu_pass_items"],
        mean_device_pile=round(
            snap["device_pass_items"] / snap["device_passes"], 1),
        max_coalesced=snap["max_coalesced"],
        cpu_cutoff_items=snap["cpu_cutoff"],
        round_trip_ms_ema=snap["rtt_ms_ema"],
        cpu_rate_ema=snap["cpu_rate_ema"],
        bucket_hits=json.dumps(shapes["bucket_hits"]).replace(" ", ""),
        device_kind=stamp["device_kind"])


def run_stages(size: dict, args) -> dict:
    stamp = identify(args.cpu_dry_run)

    from simple_pbft_tpu.config import make_test_committee

    _cfg, keys = make_test_committee(n=size["n"], clients=size["clients"])
    pubkeys = [kp.pub for kp in keys.values()]
    service = build_verifier(size, pubkeys)
    kernel_stage(size, args.seed, keys, service.device, stamp,
                 args.cpu_dry_run)
    asyncio.run(served_stage(size, args.seed, service, pubkeys, stamp))
    chip_stage(service, stamp)
    service.close()
    return stamp


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds the kernel batch and the request stream")
    ap.add_argument("--cpu-dry-run", action="store_true",
                    help="tiny sizes on the CPU platform; never automatic")
    args = ap.parse_args(argv)
    size = DRY if args.cpu_dry_run else REAL
    t_start = time.perf_counter()
    faulthandler.dump_traceback_later(
        TIME_LIMIT_S, exit=True, file=sys.__stderr__
    )
    try:
        stamp = run_stages(size, args)
    finally:
        faulthandler.cancel_dump_traceback_later()

    say("done", wall_s=round(time.perf_counter() - t_start, 1),
        seed=args.seed)
    result = {"ok": True}
    if args.cpu_dry_run:
        result["dry_run"] = True
    result["device"] = {
        "platform": stamp["platform"],
        "kind": stamp["device_kind"],
        "count": stamp["device_count"],
    }
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
