"""One-replica unit microbench: the measured per-core consensus ceiling.

VERDICT r4 next #5: the 10k req/s projection rested on arithmetic
(cpu_budget_r04.md) that the committee benches under-delivered by ~4x;
this converts the per-replica cost claim into a measured unit. ONE
backup replica (r1) runs the full runtime — drain sweeps, batched
signature verification, quorum tallies, ordered execution, replies —
while the rest of the committee is PRE-SIGNED traffic fed at line rate
through its transport queue. No other replica shares the core, so the
number is the per-core ceiling of the replica runtime itself (the
reference's equivalent loop is node.go's resolveMsg/routing; its one
measured configuration was hard-serialized at ~0.4 req/s, SURVEY.md §6).

Traffic per block (plain mode): one signed PrePrepare carrying `batch`
client-signed requests, then 2f+1 Prepare and 2f+1 Commit votes from
distinct peers (r1's own votes complete the quorums). QC mode: the two
votes' worth of traffic collapses to two aggregate QuorumCerts (one
pairing check each, memoized) — the certificate-size thesis in
docs/PROTOCOL.md.

Checkpoint traffic is emitted by r1 but never stabilizes (no live peers
to answer); the watermark window is sized past the run so GC never
gates progress — stated honestly in the record as checkpointing=off.

Usage: python bench_replica_unit.py [--n 100] [--blocks 16] [--batch 128]
           [--modes plain,qc] [--out bench_results/replica_unit_r05.jsonl]

Platform selection follows --verifier: ``tpu`` requires JAX's default
backend to be a TPU and exits nonzero anywhere else; ``cpu`` selects the
CPU platform in-process. Every record carries `platform`, `device_kind`
and `device_count`.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time
from typing import Dict, List

import simple_pbft_tpu


def _emit(rec: dict, out_path: str | None) -> None:
    line = json.dumps(rec)
    os.write(1, (line + "\n").encode())
    if out_path:
        if os.path.dirname(out_path):
            os.makedirs(os.path.dirname(out_path), exist_ok=True)
        with open(out_path, "a") as f:
            f.write(line + "\n")


def build_traffic(cfg, keys, n_clients: int, blocks: int, batch: int):
    """Pre-sign `blocks` full consensus rounds as wire bytes for backup
    r1. Returns (wire messages, total requests)."""
    from simple_pbft_tpu.consensus import qc as qc_mod
    from simple_pbft_tpu.crypto.signer import Signer
    from simple_pbft_tpu.messages import Commit, PrePrepare, Prepare, Request

    signers = {rid: Signer(rid, keys[rid].seed) for rid in cfg.replica_ids}
    client_ids = [f"c{i}" for i in range(n_clients)]
    csigners = {cid: Signer(cid, keys[cid].seed) for cid in client_ids}
    quorum = cfg.quorum  # 2f+1
    others = [rid for rid in cfg.replica_ids if rid != "r1"]
    bls_sks: Dict[str, int] = {}
    if cfg.qc_mode:
        from simple_pbft_tpu.crypto import bls

        for rid in cfg.replica_ids[: quorum + 1]:
            bls_sks[rid] = bls.keygen(keys[rid].seed)[0]
    wire: List[bytes] = []
    ts = {cid: 0 for cid in client_ids}
    for seq in range(1, blocks + 1):
        reqs = []
        for j in range(batch):
            cid = client_ids[j % n_clients]
            ts[cid] += 1
            r = Request(
                client_id=cid,
                timestamp=ts[cid],
                operation=f"put k{j} s{seq}",
            )
            csigners[cid].sign_msg(r)
            reqs.append(r)
        block = [r.to_dict() for r in reqs]
        pp = PrePrepare(
            view=0,
            seq=seq,
            digest=PrePrepare.block_digest(block),
            block=block,
        )
        signers["r0"].sign_msg(pp)
        wire.append(pp.to_wire())
        if not cfg.qc_mode:
            for rid in others[:quorum]:
                p = Prepare(view=0, seq=seq, digest=pp.digest)
                signers[rid].sign_msg(p)
                wire.append(p.to_wire())
            for rid in others[:quorum]:
                c = Commit(view=0, seq=seq, digest=pp.digest)
                signers[rid].sign_msg(c)
                wire.append(c.to_wire())
        else:
            for phase in ("prepare", "commit"):
                shares = {
                    rid: qc_mod.sign_share(sk, phase, 0, seq, pp.digest)
                    for rid, sk in bls_sks.items()
                }
                cert = qc_mod.build_qc(
                    phase, 0, seq, pp.digest, shares, quorum
                )
                assert cert is not None, "aggregation failed"
                signers["r0"].sign_msg(cert)
                wire.append(cert.to_wire())
    return wire, blocks * batch


async def run_mode(
    mode: str, n: int, blocks: int, batch: int, verifier: str = "cpu"
) -> dict:
    from simple_pbft_tpu.app import KVStore
    from simple_pbft_tpu.config import make_test_committee
    from simple_pbft_tpu.consensus.replica import Replica
    from simple_pbft_tpu.transport.local import LocalNetwork

    qc_mode = mode == "qc"
    n_clients = 8
    cfg, keys = make_test_committee(
        n=n,
        clients=n_clients,
        qc_mode=qc_mode,
        checkpoint_interval=64,
        watermark_window=blocks + 128,
    )
    net = LocalNetwork()
    t0 = time.perf_counter()
    wire, total_reqs = build_traffic(cfg, keys, n_clients, blocks, batch)
    prep_s = time.perf_counter() - t0

    svc = None
    if verifier == "tpu":
        # the per-replica form of the TPU thesis: one replica, verify
        # offloaded through the coalescing service (async dispatch
        # overlaps the device pass with the next sweep's decode)
        from simple_pbft_tpu.crypto.coalesce import VerifyService
        from simple_pbft_tpu.crypto.tpu_verifier import TpuVerifier

        simple_pbft_tpu.enable_jit_cache()
        dev = TpuVerifier(initial_keys=n + n_clients + 8)
        # warm budget covers a maximal drain sweep
        dev.warm_for_population(
            [kp.pub for kp in keys.values()], max_sweep=4096
        )
        svc = VerifyService(dev)

    replica = Replica(
        node_id="r1",
        cfg=cfg,
        seed=keys["r1"].seed,
        transport=net.endpoint("r1"),
        app=KVStore(),
        verifier=svc,
    )
    feeder = net.endpoint("r0")
    for raw in wire:
        await feeder.send("r1", raw)

    profiler = None
    if os.environ.get("RU_PROFILE"):
        import cProfile

        profiler = cProfile.Profile()
        profiler.enable()
    replica.start()
    t0 = time.perf_counter()
    deadline = t0 + 600.0
    while replica.executed_seq < blocks and time.perf_counter() < deadline:
        await asyncio.sleep(0.01)
    elapsed = time.perf_counter() - t0
    if profiler is not None:
        import pstats

        profiler.disable()
        pstats.Stats(profiler).sort_stats("tottime").print_stats(25)
    done = replica.executed_seq
    stats = replica.stats
    rec = {
        "bench": "replica_unit",
        "mode": mode,
        "n": n,
        "quorum": cfg.quorum,
        "blocks": blocks,
        "batch": batch,
        "wire_messages": len(wire),
        "completed_blocks": done,
        "ok": done == blocks,
        "req_s": round(done * batch / elapsed, 1) if elapsed > 0 else 0.0,
        "ms_per_req": round(1e3 * elapsed / max(1, done * batch), 4),
        "elapsed_s": round(elapsed, 2),
        "presign_s": round(prep_s, 1),
        "verify_items": stats.verify_items,
        "verify_s": round(stats.verify_seconds, 2),
        "verify_share": round(stats.verify_seconds / elapsed, 3)
        if elapsed > 0
        else 0.0,
        "sig_cache_hits": replica.metrics.get("sig_cache_hits", 0),
        "checkpointing": "emit-only (no peers answer)",
        "verifier": getattr(replica.verifier, "name", "?"),
        **simple_pbft_tpu.device_stamp(),
    }
    if svc is not None:
        rec.update(
            svc_device_passes=svc.device_passes,
            svc_cpu_passes=svc.cpu_passes,
            # null until a device pass ran — the EMA's constructor seed
            # (30 ms) must never read as a measured round trip
            svc_rtt_ms_ema=(
                round(svc.rtt_ms, 1) if svc.device_passes else None
            ),
        )
    await replica.stop()
    if svc is not None:
        svc.close()
    return rec


async def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=100)
    ap.add_argument("--blocks", type=int, default=16)
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--modes", default="plain,qc")
    ap.add_argument("--verifier", default="cpu", choices=["cpu", "tpu"])
    ap.add_argument(
        "--out", default=os.path.join("bench_results", "replica_unit_r05.jsonl")
    )
    args = ap.parse_args()
    simple_pbft_tpu.select_platform(
        args.verifier == "tpu", "bench_replica_unit.py --verifier tpu"
    )
    for mode in args.modes.split(","):
        mode = mode.strip()
        assert mode in ("plain", "qc"), mode
        rec = await run_mode(
            mode, args.n, args.blocks, args.batch, verifier=args.verifier
        )
        _emit(rec, args.out)


if __name__ == "__main__":
    asyncio.run(main())
