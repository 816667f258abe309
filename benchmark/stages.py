"""The stages of one benchmark run.

``identify``, ``build_verifier``, ``kernel_stage`` and ``chip_stage`` are
copies of chip_smoke.py's stages of the same names (proven on the v5e in
PR 21), kept here so that a later PR can change the program's scripts and
not the yardstick. Two things differ from the originals: a check that does
not hold is collected in ``Checks`` and decides ``correct`` (the smoke
raised), and the served stage pumps for a fixed time (the smoke sent a fixed
number of puts).

From the program this file takes the system under test
(``node.make_verifier``, ``LocalCommittee``, ``Client.submit``) and its
spans and counters. The traffic, the plain reference (a dict filled from
every acknowledged put), the comparison that decides ``correct`` and the
arithmetic from records to metrics are the benchmark's own.
"""

from __future__ import annotations

import asyncio
import json
import math
import random
import statistics
import sys
import time
from importlib import metadata


def say(stage: str, **fields) -> None:
    print(f"{stage}: " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


class Checks:
    """What did not hold. Empty at the end of a run = ``correct``."""

    def __init__(self) -> None:
        self.problems: list = []

    def expect(self, ok: bool, what: str) -> None:
        if not ok:
            self.problems.append(what)
            say("check", failed=json.dumps(what))


# ---------------------------------------------------------------------------
# the configuration: every stated key is built or refused
# ---------------------------------------------------------------------------

# what a signature scheme builds: the committee's qc_mode
SCHEMES = {"ed25519": False, "bls12-381-qc": True}
# the pump's one operation (served_stage)
OPERATION = "put k<i> v<12 hex digits>"
# read by the harness to build the run
BUILT = ("platform", "n", "clients", "keys", "block", "checkpoint_interval",
         "watermark_window", "view_timeout_s", "request_timeout_s",
         "signatures", "verifier")
# prose, and the kernel gate's own sizes
PASSED_OVER = ("name", "source", "why", "reduced", "assumed", "guarantees",
               "kernel_check")
# what the harness can build, for a key it does not read
FIXED = {"transport": "local", "processes": 1, "app": "KVStore",
         "message_delay_ms": 0, "speculative": "default",
         "operation": OPERATION}
VERIFIER = ("backend", "mode", "window", "max_batch", "cpu_cutoff",
            "key_bank", "signing_keys")


def qc_mode(config: dict) -> bool:
    """The committee's qc_mode for the file's ``signatures``; a file that
    states none is a plain Ed25519 one (the test committees')."""
    scheme = config.get("signatures", "ed25519")
    if scheme not in SCHEMES:
        raise SystemExit(
            f"config {config.get('name')}: signatures is {scheme!r} where "
            f"the harness builds one of {sorted(SCHEMES)}")
    return SCHEMES[scheme]


def check_config(config: dict, cfg, population: int, service=None) -> None:
    """Every key the file states is one the harness builds, and equals
    what was built: ``cfg`` is the committee's CommitteeConfig,
    ``population`` the keys handed to the verifier. Without ``service``
    (before the warm) the keys that need the device are not yet checked.
    A mismatch is a SystemExit naming the key, the file's value and the
    built value."""
    name = config.get("name")
    unknown = sorted(set(config) - set(BUILT) - set(PASSED_OVER)
                     - set(FIXED) - {"f"})
    if unknown:
        raise SystemExit(f"config {name}: {unknown[0]} is a key the harness "
                         "neither builds nor checks")
    missing = [key for key in BUILT if key not in config]
    if missing:
        raise SystemExit(f"config {name}: states no {missing[0]}")
    check_committee(config, cfg)
    for key, built in FIXED.items():
        if key in config and config[key] != built:
            _refuse(name, key, config[key], built,
                    ": delay on the served path is ROADMAP R5(c)"
                    if key == "message_delay_ms" else "")
    vcfg = config["verifier"]
    unknown = sorted(set(vcfg) - set(VERIFIER))
    if unknown:
        raise SystemExit(f"config {name}: verifier.{unknown[0]} is a key "
                         "the harness neither builds nor checks")
    built = {"backend": "tpu", "signing_keys": population}
    if service is not None:
        device = service.device
        built.update(mode=device._mode, window=device._window,
                     key_bank=device._bank._cap)
        held = len(device._bank._index)
        if held != min(population, device._bank._cap):
            _refuse(name, "verifier.signing_keys", vcfg.get("signing_keys"),
                    held, " keys in the bank")
    for key, value in built.items():
        if key in vcfg and vcfg[key] != value:
            _refuse(name, f"verifier.{key}", vcfg[key], value)


def check_committee(config: dict, cfg) -> None:
    """The committee as built holds what the file states: its ``f``, the
    speculation it runs (``default``: CommitteeConfig's own) and the
    ``signatures`` scheme (its qc_mode). Called on the key set-up's
    committee before the warm and on the one served_stage times."""
    from simple_pbft_tpu.config import CommitteeConfig

    name = config.get("name")
    if "f" in config and config["f"] != cfg.f:
        _refuse(name, "f", config["f"], cfg.f)
    if cfg.speculative != CommitteeConfig.speculative:
        _refuse(name, "speculative", config.get("speculative"),
                cfg.speculative)
    if cfg.qc_mode != qc_mode(config):
        _refuse(name, "signatures", config.get("signatures", "ed25519"),
                f"qc_mode={cfg.qc_mode}")


def _refuse(name, key: str, stated, built, why: str = "") -> None:
    raise SystemExit(f"config {name}: {key} is {stated!r} where the "
                     f"harness builds {built!r}{why}")


# ---------------------------------------------------------------------------
# set-up: identify -> warm -> kernel
# ---------------------------------------------------------------------------


def identify(platform: str, chips: int, what: str) -> dict:
    """What JAX runs on. ``platform`` is the configuration's: ``tpu`` must
    find a TPU with at least ``chips`` devices, else a nonzero exit and no
    result; ``cpu`` (the rehearsal files only) selects the CPU in-process."""
    import jax
    import jaxlib

    import simple_pbft_tpu
    from simple_pbft_tpu import native
    from simple_pbft_tpu.crypto import signer
    from simple_pbft_tpu.ops import comb

    if platform not in ("tpu", "cpu"):
        raise SystemExit(f"{what}: platform {platform!r} is neither tpu nor cpu")
    cache = simple_pbft_tpu.enable_jit_cache()  # before the first jit
    stamp = simple_pbft_tpu.select_platform(platform == "tpu", what)
    if stamp["device_count"] < chips:
        raise SystemExit(
            f"{what} needs {chips} chip(s) but JAX reports "
            f"{stamp['device_count']}")
    say("identify", **stamp)
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
    if missing:
        raise SystemExit(f"{what}: natives on the Python fallback: {missing}")
    say("identify",
        signer="cryptography" if signer._HAVE_OPENSSL else "pure-python",
        accum=comb._resolve_accum_impl())
    return stamp


def build_verifier(config: dict, pubkeys):
    """The verifier a node process would build, through the same call."""
    from simple_pbft_tpu.node import make_verifier

    vcfg = config["verifier"]
    t0 = time.perf_counter()
    service = make_verifier(
        "tpu", pubkeys, max_batch=vcfg["max_batch"],
        cpu_cutoff=vcfg["cpu_cutoff"],
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


def kernel_stage(config: dict, seed: int, keys, device, stamp: dict,
                 checks: Checks) -> None:
    """One seeded batch with planted failures through
    TpuVerifier.verify_batch, compared with the RFC 8032 oracle
    (crypto/ed25519_cpu.verify). Outside the window."""
    from simple_pbft_tpu.crypto import ed25519_cpu as ref
    from simple_pbft_tpu.crypto.signer import Signer
    from simple_pbft_tpu.crypto.verifier import BatchItem
    from simple_pbft_tpu.ops import comb

    rng = random.Random(seed)
    batch = config["kernel_check"]["batch"]
    signers = [Signer(name, kp.seed) for name, kp in keys.items()]
    items = []
    for i in range(batch):
        s = signers[i % len(signers)]
        msg = b"benchmark %d %d " % (seed, i) + rng.randbytes(16)
        items.append(BatchItem(s.pub, msg, s.sign(msg)))
    spots = rng.sample(range(batch), 7)
    planted: dict = {}

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
    checks.expect(len(got) == batch,
                  f"kernel: {len(got)} verdicts for {batch} items")

    good = [i for i in range(batch) if i not in planted]
    sample = rng.sample(good, config["kernel_check"]["sample"])
    for pos in sorted(planted) + sample:
        it = items[pos]
        want = ref.verify(it.pubkey, it.msg, it.sig)
        checks.expect(
            got[pos] == want,
            f"kernel: item {pos} ({planted.get(pos, 'good')}) device says "
            f"{got[pos]}, oracle says {want}")
    checks.expect(not any(got[p] for p in planted),
                  "kernel: a planted failure verified")
    checks.expect(all(got[i] for i in good),
                  "kernel: a good signature was rejected")
    say("kernel", batch=batch, keys=len(signers), planted=len(planted),
        sampled_good=len(sample),
        verify_batch_wall_ms=round(wall * 1e3, 1),
        device_kind=stamp["device_kind"])

    accum = comb._resolve_accum_impl()
    if stamp["platform"] != "tpu":
        say("kernel", mosaic="n/a (cpu rehearsal)", accum=accum)
        return
    checks.expect(accum == "pallas",
                  f"kernel: accumulator resolved to {accum!r}")
    text = device.lowered_text(batch)
    checks.expect("tpu_custom_call" in text,
                  "kernel: no Mosaic custom call in the lowered program")
    say("kernel", mosaic="tpu_custom_call present", accum=accum,
        pallas_tile=comb.PALLAS_TILE)


# ---------------------------------------------------------------------------
# counters: flat surfaces, read at the window's start and end
# ---------------------------------------------------------------------------


def _flatten(doc: dict, prefix: str = "") -> dict:
    """Numeric leaves of a nested dict under dotted keys."""
    out: dict = {}
    for key, value in doc.items():
        if isinstance(value, dict):
            out.update(_flatten(value, f"{prefix}{key}."))
        elif isinstance(value, (int, float)) and not isinstance(value, bool):
            out[f"{prefix}{key}"] = value
    return out


def _summed(dicts) -> dict:
    out: dict = {}
    for d in dicts:
        for key, value in d.items():
            out[key] = out.get(key, 0) + value
    return out


def counter_surfaces(com, service) -> dict:
    """The five counter surfaces a ``counter:<surface>.<key>`` reader sees:
    ``verify`` (VerifyService.snapshot()), ``wire`` (every node's
    WireAccounting, summed: totals and ``<kind>.<total>``), ``clients`` and
    ``replicas`` (their ``metrics`` dicts, summed), and ``qc`` (the
    process's certificate lane, QcVerifyLane.snapshot(); empty where no
    certificate was ever checked, as in a plain Ed25519 committee)."""
    wire: dict = {}
    for acct in com.net.wire_accts.values():
        for kind, row in acct.per_kind().items():
            for key, value in row.items():
                wire[key] = wire.get(key, 0) + value
                wire[f"{kind}.{key}"] = wire.get(f"{kind}.{key}", 0) + value
    return {
        "verify": _flatten(service.snapshot()),
        "wire": wire,
        "clients": _summed(c.metrics for c in com.clients),
        "replicas": _summed(r.metrics for r in com.replicas),
        "qc": lane_counters(),
    }


def lane_counters() -> dict:
    from simple_pbft_tpu.consensus import qc

    return _flatten(qc.lane_snapshot() or {})


def counter_deltas(before: dict, after: dict) -> dict:
    return {
        surface: {key: value - before[surface].get(key, 0)
                  for key, value in after[surface].items()}
        for surface in after
    }


# ---------------------------------------------------------------------------
# the served window
# ---------------------------------------------------------------------------


def percentile(ordered: list, share: float) -> float:
    """Nearest rank on a sorted list."""
    return ordered[max(0, math.ceil(share * len(ordered)) - 1)]


async def served_stage(cell: dict, config: dict, seed: int, seconds: float,
                       service, pubkeys, checks: Checks, profiler=None) -> dict:
    """Closed-loop puts through Client.submit for ``seconds``, after an
    unmeasured warm-up of the same traffic; then drain, read back, and hold
    the committee to its guarantees. Returns what run.py makes metrics of.

    ``profiler``, where given, has ``start()`` and ``stop()`` (blocking
    calls, run off the event loop) and is held open for the cell's
    ``trace_seconds`` in the middle of the window.
    """
    from simple_pbft_tpu import spans
    from simple_pbft_tpu.client import SupersededError
    from simple_pbft_tpu.committee import LocalCommittee

    n, pumps, n_keys = config["n"], cell["in_flight"], config["keys"]
    if pumps > n_keys:
        raise SystemExit(f"in_flight {pumps} exceeds the {n_keys} keys: two "
                         "writes to one key would be in flight together")
    qc = qc_mode(config)
    com = LocalCommittee.build(
        n=n, clients=config["clients"], verifier_factory=lambda: service,
        max_batch=config["block"], view_timeout=config["view_timeout_s"],
        checkpoint_interval=config["checkpoint_interval"],
        watermark_window=config["watermark_window"], qc_mode=qc,
    )
    if [kp.pub for kp in com.keys.values()] != pubkeys:
        raise SystemExit("served: committee keys differ from the warmed "
                         "population")
    check_committee(config, com.cfg)
    for c in com.clients:
        c.request_timeout = config["request_timeout_s"]
    say("served", n=n, f=com.cfg.f, reply_quorum=com.cfg.weak_quorum,
        clients=len(com.clients), in_flight=pumps, block=config["block"],
        speculative=str(com.cfg.speculative).lower(),
        signatures=config.get("signatures", "ed25519"))

    # each pump owns its keys and writes them in sequence, so two writes to
    # one key are never in flight together and the reference is exact. Every
    # seed gives the same sizes: only the 48 value bits differ.
    names = [f"k{i}" for i in range(n_keys)]
    reference: dict = {}  # the plain reference: last acknowledged put per key
    records: list = []    # (submitted, answered, ok) on time.perf_counter
    issuing = True

    async def pump(idx: int) -> None:
        client = com.clients[idx % len(com.clients)]
        own = names[idx::pumps]
        rng = random.Random(f"{seed}:{idx}")
        turn = 0
        while issuing:
            key = own[turn % len(own)]
            turn += 1
            value = f"v{rng.getrandbits(48):012x}"
            t0 = time.perf_counter()
            try:
                result = await client.submit(f"put {key} {value}")
            except (asyncio.TimeoutError, SupersededError) as exc:
                result = repr(exc)
            ok = result == "ok"
            if ok:
                reference[key] = value
            else:
                say("served", failed_put=key, answer=json.dumps(result))
            records.append((t0, time.perf_counter(), ok))

    loop = asyncio.get_running_loop()
    lane_before = lane_counters()
    com.start()
    tasks = [asyncio.ensure_future(pump(i)) for i in range(pumps)]
    await asyncio.sleep(cell["warmup_seconds"])

    spans.configure("benchmark")  # a fresh span surface for the window
    before = counter_surfaces(com, service)
    t_start = time.perf_counter()
    traced = stopping = None
    if profiler is None:
        await asyncio.sleep(seconds)
    else:
        held = min(cell["trace_seconds"], seconds / 2)
        await asyncio.sleep((seconds - held) / 2)
        t_trace = time.perf_counter()
        items0 = service.device_pass_items
        await loop.run_in_executor(None, profiler.start)
        await asyncio.sleep(held)
        traced = {"window_s": time.perf_counter() - t_trace,
                  "device_items": service.device_pass_items - items0}
        # collecting the trace takes seconds: off the loop, and awaited
        # only after the window has closed
        stopping = loop.run_in_executor(None, profiler.stop)
        await asyncio.sleep(max(0.0, t_start + seconds - time.perf_counter()))
    t_end = time.perf_counter()
    after = counter_surfaces(com, service)
    stage_summaries = spans.recorder().stage_summaries()
    if stopping is not None:
        await stopping

    # stop issuing and drain what is in flight
    issuing = False
    _done, late = await asyncio.wait(tasks, timeout=cell["drain_timeout_s"])
    for task in late:
        task.cancel()
    for outcome in await asyncio.gather(*tasks, return_exceptions=True):
        if isinstance(outcome, Exception):  # a cancelled pump is not one
            raise outcome

    # (b) read back through the client's reply quorum
    rng = random.Random(seed + 1)
    sample = rng.sample(sorted(reference), min(cell["gets"], len(reference)))

    async def read_back(client, key: str) -> None:
        try:
            result = await client.submit(f"get {key}")
        except (asyncio.TimeoutError, SupersededError) as exc:
            result = repr(exc)
        checks.expect(result == reference[key],
                      f"served: get {key} = {result!r}, last acknowledged "
                      f"{reference[key]!r}")

    await asyncio.gather(*(
        read_back(com.clients[i % len(com.clients)], key)
        for i, key in enumerate(sample)
    ))

    # (c) laggards finish executing what the quorum already committed
    deadline = time.perf_counter() + 60.0
    running = [r for r in com.replicas if r._running]
    while (len({r.executed_seq for r in running}) > 1
           and time.perf_counter() < deadline):
        await asyncio.sleep(0.05)
    seqs = {r.executed_seq for r in running}
    digests = {r.app.state_digest() for r in running}
    # (a) every replica's state equals the plain reference
    differing = [r.id for r in running
                 if json.loads(r.app.snapshot()) != reference]
    client_metrics = _summed(c.metrics for c in com.clients)
    await com.stop()
    if qc:
        hold_lane(lane_before, lane_counters(),
                  _summed(r.metrics for r in com.replicas), checks)

    checks.expect(not late, f"served: {len(late)} pumps did not drain")
    checks.expect(len(running) == n, f"served: {len(running)}/{n} replicas running")
    checks.expect(len(seqs) == 1, f"served: executed_seq differs: {sorted(seqs)}")
    checks.expect(len(digests) == 1, "served: state digests differ across replicas")
    checks.expect(not differing,
                  f"served: state differs from the reference on {differing}")
    checks.expect(client_metrics.get("spec_final_mismatch", 0) == 0,
                  "served: a speculative answer differed from the final one")

    window = window_metrics(records, t_start, t_end)
    say("served", window_s=round(t_end - t_start, 3),
        attempted=window["attempted"],
        acknowledged_in_window=window["acknowledged"],
        distinct_keys=len(reference), gets=len(sample),
        replicas_agree=len(running), executed_seq=sorted(seqs)[-1], **{
            k: client_metrics.get(k, 0)
            for k in ("request_timeouts", "retransmissions", "spec_accepted",
                      "spec_final_mismatch")})
    window["failed"] += len(late)
    return {
        "t_start": t_start,
        "window": window,
        "counters": counter_deltas(before, after),
        "spans": stage_summaries,
        "traced": traced,
    }


def hold_lane(before: dict, after: dict, replicas: dict,
              checks: Checks) -> None:
    """A certificate committee, from its start to its stop: every
    certificate the lane checked verified, none was shed or bisected, no
    replica received a bad one, and certificates, not plain votes, drove
    the committee."""
    delta = {key: after.get(key, 0) - before.get(key, 0)
             for key in ("submitted", "verified_true", "verified_false",
                         "overload_rejections", "structural_rejects",
                         "batch_fallbacks", "batches", "batch_items")}
    say("qc", bad_qc=replicas.get("bad_qc", 0),
        qcs_formed=replicas.get("qcs_formed", 0), **delta)
    bad = {key: delta[key] for key in ("verified_false", "overload_rejections",
                                       "structural_rejects", "batch_fallbacks")
           if delta[key]}
    if replicas.get("bad_qc", 0):
        bad["bad_qc"] = replicas["bad_qc"]
    checks.expect(not bad, f"qc: a certificate failed or was shed: {bad}")
    checks.expect(delta["submitted"] > 0 and delta["verified_true"] > 0,
                  "qc: the lane verified no certificate, so none drove the "
                  f"committee: submitted {delta['submitted']}, verified_true "
                  f"{delta['verified_true']}")


def window_metrics(records: list, t_start: float, t_end: float) -> dict:
    """From (submitted, answered, ok) records to what one window reports.

    A request belongs to the window if it was submitted inside it; its
    latency is submit to accepted reply, and the percentiles are over all
    such requests that were acknowledged, whenever the reply came.

    Throughput is acknowledged requests per second of window, where a
    request whose life straddles an edge of the window counts by the share
    of its life that lies inside. Every acknowledged request and every
    second of the window is in it. A loaded committee answers in lock step
    (all requests in flight ride one block and are acknowledged within
    20 ms of each other, every 1.6 s at n=64), so whole acknowledgements
    over a fixed window come in steps of one block, 3-5% of the count, and
    the same runs read a spread of 0% or of 5% by luck; the share by time
    is the same quantity without the steps (PERF.md, section 2).
    """
    mine = [(t1 - t0, ok) for t0, t1, ok in records if t_start <= t0 < t_end]
    latencies = sorted(lat for lat, ok in mine if ok)
    work = sum(
        (min(t1, t_end) - max(t0, t_start)) / max(t1 - t0, 1e-9)
        for t0, t1, ok in records if ok and t1 > t_start and t0 < t_end)
    out = {
        "attempted": len(mine),
        "failed": sum(1 for _lat, ok in mine if not ok),
        # whole acknowledgements inside the window, for the reader
        "acknowledged": sum(1 for _t0, t1, ok in records
                            if ok and t_start <= t1 < t_end),
        "committed": work,
        "metrics": {
            "committed_req_per_s": (work / (t_end - t_start), "req/s"),
        },
    }
    if latencies:
        out["metrics"]["commit_latency_p50_ms"] = (
            statistics.median(latencies) * 1e3, "ms")
        out["metrics"]["commit_latency_p95_ms"] = (
            percentile(latencies, 0.95) * 1e3, "ms")
    return out


# ---------------------------------------------------------------------------
# chip: the device did the verifying
# ---------------------------------------------------------------------------


def chip_stage(service, stamp: dict, checks: Checks) -> None:
    """None of the routes that keep production alive without the chip was
    taken, and nothing compiled after the warm-up."""
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
    checks.expect(snap["device_pass_items"] > 0,
                  "chip: device_pass_items == 0, the device verified nothing")
    bad = {k: v for k, v in must_be_zero.items() if v}
    checks.expect(not bad,
                  f"chip: a CPU route or a late compile was taken: {bad}")
    verified = snap["device_pass_items"] + snap["cpu_pass_items"]
    say("chip",
        device_share=round(snap["device_pass_items"] / max(verified, 1), 4),
        cpu_pass_items=snap["cpu_pass_items"],
        mean_device_pile=round(
            snap["device_pass_items"] / max(snap["device_passes"], 1), 1),
        max_coalesced=snap["max_coalesced"],
        cpu_cutoff_items=snap["cpu_cutoff"],
        round_trip_ms_ema=snap["rtt_ms_ema"],
        cpu_rate_ema=snap["cpu_rate_ema"],
        bucket_hits=json.dumps(shapes["bucket_hits"]).replace(" ", ""),
        device_kind=stamp["device_kind"])
