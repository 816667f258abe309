"""Device-plane observatory (ISSUE 14): per-dispatch ledger schema and
aggregates, the zero-overhead-when-disabled A/B, the BLS and per-device
events sharing the schema, the static cost model's anchor points,
verify_observatory's decomposition/reconciliation/limiter logic, the
pbft_top DEV cell, and the dead-target view-change evidence rule."""

from __future__ import annotations

import importlib.util
import json
import os
import time
from types import SimpleNamespace

import numpy as np
import pytest

from simple_pbft_tpu import clock, devledger
from simple_pbft_tpu.crypto import costmodel
from simple_pbft_tpu.crypto import ed25519_cpu as ref
from simple_pbft_tpu.crypto.coalesce import VerifyService
from simple_pbft_tpu.crypto.tpu_verifier import TpuVerifier
from simple_pbft_tpu.crypto.verifier import BatchItem
from simple_pbft_tpu.devledger import DeviceLedger

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "tools", f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


observatory = _load_tool("verify_observatory")
pbft_top = _load_tool("pbft_top")


@pytest.fixture()
def fresh_ledger():
    devledger.configure("t")
    yield devledger.ledger()
    devledger.configure("")


@pytest.fixture(scope="module")
def signed_items():
    sk = b"\x07" * 32
    pub = ref.public_key(sk)
    return pub, [
        BatchItem(pubkey=pub, msg=b"dl%d" % i, sig=ref.sign(sk, b"dl%d" % i))
        for i in range(8)
    ]


@pytest.fixture(scope="module")
def warm_verifier(signed_items):
    pub, _ = signed_items
    v = TpuVerifier(initial_keys=4)
    v.warm(pubkeys=[pub], buckets=[8])
    v._warm_done = True
    return v


# ---------------------------------------------------------------------------
# the ledger itself
# ---------------------------------------------------------------------------


def test_dispatch_event_schema(fresh_ledger, warm_verifier, signed_items):
    """A real jit dispatch records the full per-dispatch tuple: shape,
    pad waste, host prep, RTT, compile-vs-cache, bytes both ways."""
    _, items = signed_items
    assert warm_verifier.verify_batch(items[:5]) == [True] * 5
    evs = devledger.recent()
    assert len(evs) == 1
    ev = evs[0]
    assert ev["lane"] == "ed25519"
    assert ev["mode"] == "fused" and ev["window"] == 4
    assert ev["bucket"] == 8 and ev["n"] == 5 and ev["pad"] == 3
    assert ev["rtt_s"] > 0 and ev["host_prep_s"] > 0
    assert ev["compile"] is False  # warmed shape: cached
    assert ev["bytes_up"] > 0 and ev["bytes_down"] == 8
    snap = devledger.snapshot()
    assert snap["dispatches"] == 1 and snap["items"] == 5
    assert snap["pad_waste_pct"] == pytest.approx(100 * 3 / 8, abs=0.1)
    # lane-qualified shape key: two lanes sharing a
    # (mode, window, bucket) must never overwrite each other
    assert "ed25519:fused/w4/b8" in snap["shapes"]
    assert 0 < snap["occupancy"] <= 1.0


def test_service_route_records_queue_wait(fresh_ledger, warm_verifier,
                                          signed_items):
    """Through the coalescing service the dispatch events carry the
    admission-queue wait and submission count (the thread-local
    annotation seam), and the service snapshot exposes the aggregate
    ``device`` block."""
    _, items = signed_items
    svc = VerifyService(warm_verifier, cpu_cutoff=0, max_batch=8)
    f1 = svc.submit(items[:3])
    f2 = svc.submit(items[3:6])
    assert f1.result(30) == [True] * 3
    assert f2.result(30) == [True] * 3
    snap = svc.snapshot()
    svc.close()
    dev = snap["device"]
    lane = dev["lanes"]["ed25519"]
    assert lane["items"] == 6
    assert 1 <= lane["dispatches"] <= 2
    assert lane["submissions"] == 2
    assert lane["queue_wait_s"] >= 0.0
    assert lane["busy_s"] > 0
    # the top-level mirror pbft_top / bench_gate floors read
    assert dev["dispatches"] == lane["dispatches"]
    assert dev["verifies_per_s_effective"] > 0


def test_disabled_ledger_is_free_ab(signed_items):
    """The acceptance A/B: a disabled ledger records NOTHING and its
    per-call cost is one attribute read — orders of magnitude under the
    enabled path, and far under any measurable per-dispatch budget."""
    led = DeviceLedger()
    n = 20000
    led.configure("ab", enabled=False)
    t0 = time.perf_counter()
    for _ in range(n):
        led.record("ed25519", "fused", 4, 8, 5, rtt_s=0.001)
    dt_off = time.perf_counter() - t0
    assert led.recorded == 0 and not led._ring  # structurally inert
    assert led.snapshot()["dispatches"] == 0
    led.configure("ab", enabled=True)
    t0 = time.perf_counter()
    for _ in range(n):
        led.record("ed25519", "fused", 4, 8, 5, rtt_s=0.001)
    dt_on = time.perf_counter() - t0
    assert led.recorded == n
    assert dt_off < dt_on  # disabled strictly cheaper than enabled
    assert dt_off / n < 5e-6  # one attribute read, generous CI margin


def test_record_never_raises(fresh_ledger):
    """PBL004 discipline: hostile/malformed fields drop the event (and
    count it dropped), never raise into the verify pipeline."""
    devledger.record("x", "fused", "not-an-int", None, "nope")
    assert devledger.ledger().dropped == 1
    assert devledger.snapshot()["dispatches"] == 0


def test_annotation_is_consumed_once(fresh_ledger):
    devledger.annotate(0.25, 3)
    devledger.record("ed25519", "fused", 4, 8, 8)
    ev = devledger.recent()[-1]
    assert ev["queue_wait_s"] == pytest.approx(0.25)
    devledger.record("ed25519", "fused", 4, 8, 8)
    assert devledger.recent()[-1]["queue_wait_s"] == 0.0  # not sticky
    lane = devledger.snapshot()["lanes"]["ed25519"]
    assert lane["submissions"] == 3 + 1


def test_bls_lane_shares_schema(fresh_ledger):
    """One RLC pairing batch in the QC lane = one ledger event on the
    ``bls`` lane, same schema as the jit dispatches."""
    from simple_pbft_tpu.consensus import qc as qc_mod
    from simple_pbft_tpu.crypto import bls

    keys = [bls.keygen(bytes([i + 31]) * 32) for i in range(4)]
    cfg = SimpleNamespace(
        quorum=3,
        replica_ids=tuple(f"r{i}" for i in range(4)),
        bls={f"r{i}": pk for i, (_, pk) in enumerate(keys)},
    )
    cfg.bls_pubkey = cfg.bls.get
    shares = {
        f"r{i}": qc_mod.sign_share(sk, "prepare", 0, 7, "d" * 64)
        for i, (sk, _) in enumerate(keys[:3])
    }
    cert = qc_mod.build_qc("prepare", 0, 7, "d" * 64, shares, cfg.quorum)
    lane = qc_mod.QcVerifyLane()
    lane._started = True  # drive the worker by hand: deterministic
    fut = lane.submit(cfg, cert)
    with lane._cond:
        take = lane._take_locked()
    lane._run_batch(take)
    assert fut.result(5) is True
    evs = [e for e in devledger.recent() if e["lane"] == "bls"]
    assert len(evs) == 1
    ev = evs[0]
    assert ev["mode"] == "pairing" and ev["bucket"] == 1 and ev["n"] == 1
    assert ev["rtt_s"] > 0 and ev["bytes_up"] > 0
    assert devledger.snapshot()["lanes"]["bls"]["dispatches"] == 1


def test_per_device_events_normalize_occupancy(fresh_ledger):
    """Events that name their device (one per chip of an SPMD pass) keep
    the lane's device count, and occupancy is normalized by it."""
    for d in ("d0", "d1"):
        devledger.record(
            "ed25519", "fused", 4, 4, 3, rtt_s=0.01, compile_fresh=d == "d0",
            bytes_up=400, bytes_down=4, device=d,
        )
    evs = [e for e in devledger.recent() if e["lane"] == "ed25519"]
    assert len(evs) == 2
    assert {e["device"] for e in evs} == {"d0", "d1"}
    assert all(e["bucket"] == 4 and e["pad"] == 1 for e in evs)
    lane = devledger.snapshot()["lanes"]["ed25519"]
    assert lane["devices"] == 2 and lane["dispatches"] == 2
    assert lane["compiles"] == 1
    # occupancy normalizes by device count: one pass != 2x busy window
    assert lane["occupancy"] <= 1.0


# ---------------------------------------------------------------------------
# cost model
# ---------------------------------------------------------------------------


def test_costmodel_r05_anchor_points():
    # the kernel: 64 joint-window gathers x 512 B table lines (the row
    # wanted and its neighbour) = 32,768 B an item
    c4 = costmodel.shape_cost("fused", 4, 8192)
    assert c4["gathers_per_item"] == 64
    assert c4["gather_bytes_per_item"] == 32768
    assert c4["gather_bytes_per_pass"] == 32768 * 8192
    assert c4["madds_per_item"] == 64
    # wire staging ships 101 B/item
    assert c4["wire_bytes_per_item"] == 101
    # other lanes' modes sum as zero instead of raising
    pairing = costmodel.shape_cost("pairing", 0, 4)
    assert pairing["gather_bytes_per_item"] == 0
    assert pairing["madds_per_item"] == pairing["wire_bytes_per_item"] == 0


def test_costmodel_ladder_row():
    """The table-free program's shape key: no key-table gather, the key's
    32 bytes on the wire, ops/ladder.py's own count of field multiplies,
    7-8 times the comb's 64 mixed adds of 7 and its ending."""
    from simple_pbft_tpu.ops import ladder

    assert costmodel.parse_shape_key("ed25519:ladder/w4/b2048") == {
        "lane": "ed25519", "mode": "ladder", "window": 4, "bucket": 2048}
    lad = costmodel.shape_cost("ladder", 4, 2048)
    assert lad["gathers_per_item"] == lad["gather_bytes_per_pass"] == 0
    assert lad["wire_bytes_per_item"] == 128 + 1
    assert lad["madds_per_item"] == 128  # one add a scalar a window
    assert lad["flops_per_item"] == ladder.FIELD_MULS * costmodel.MUL_INT_OPS
    assert ladder.FIELD_MULS == 278 + 129 + 64 * (4 * 8 + 8 + 7) + 5 == 3420
    assert 7 < ladder.FIELD_MULS / (64 * 7 + 5) < 8
    # a run's shapes block with both programs sums the comb's gathers only
    shapes = {"ed25519:fused/w4/b2048": {"dispatches": 3, "items": 3000},
              "ed25519:ladder/w4/b512": {"dispatches": 3, "items": 1290}}
    assert costmodel.gather_bytes_for_shapes(shapes) == 3 * 2048 * 32768


def test_costmodel_shapes_rollup():
    shapes = {
        "ed25519:fused/w4/b8": {"dispatches": 2, "items": 10,
                                "pad_items": 6},
        "bls:pairing/w0/b4": {"dispatches": 1, "items": 4, "pad_items": 0},
        "garbage-key": {"dispatches": 9},
    }
    per_item = costmodel.shape_cost("fused", 4, 8)["gather_bytes_per_item"]
    assert costmodel.gather_bytes_for_shapes(shapes) == per_item * 8 * 2
    # both the lane-qualified and bare spellings parse
    assert costmodel.parse_shape_key("ed25519:fused/w4/b8")["lane"] == \
        "ed25519"
    assert costmodel.parse_shape_key("fused/w4/b8")["mode"] == "fused"
    assert costmodel.parse_shape_key("nonsense") is None


# ---------------------------------------------------------------------------
# observatory analysis
# ---------------------------------------------------------------------------


def _dev_block(busy=1.0, prep=0.01, queue=0.005, occ=0.9, disp=10):
    return {
        "window_s": 2.0,
        "dispatches": disp,
        "items": 100,
        "busy_s": busy,
        "host_prep_s": prep,
        "queue_wait_s": queue,
        "occupancy": occ,
        "shapes": {"ed25519:fused/w4/b32": {"dispatches": disp,
                                            "items": 100,
                                            "pad_items": 20}},
    }


def test_analyze_shares_sum_and_reconciliation():
    dev = _dev_block()
    stages = {"verify.device": {"total_ms": 1005.0, "count": 10},
              "verify.queue": {"total_ms": 5.0, "count": 10}}
    v = observatory.analyze(dev, stages)
    shares = v["decomposition"]["shares"]
    assert abs(sum(shares.values()) - 1.0) < 1e-9
    rec = v["reconciliation"]
    assert rec["ledger_device_ms"] == pytest.approx(1010.0)
    assert rec["ok"] and rec["delta_pct"] <= 15.0
    assert v["limiter"] == "bandwidth"
    assert v["gather"]["per_shape"][0]["shape"] == "ed25519:fused/w4/b32"
    assert v["gather"]["gather_bytes"] > 0


def test_analyze_reconciliation_flags_disagreement():
    dev = _dev_block(busy=1.0)
    stages = {"verify.device": {"total_ms": 2000.0, "count": 10}}
    rec = observatory.analyze(dev, stages)["reconciliation"]
    assert not rec["ok"] and rec["delta_pct"] > 15.0


def test_limiter_decision_tree():
    # device-dominated + saturated = bandwidth (table engines)
    assert observatory.dominant_limiter(
        {"device_busy": 0.9, "host_prep": 0.05, "queue_wait": 0.05,
         "cpu_path": 0.0}, {"dispatches": 5, "occupancy": 0.9}, 1000
    ) == "bandwidth"
    # device-dominated + idle device = the pipeline starves it
    assert observatory.dominant_limiter(
        {"device_busy": 0.9, "host_prep": 0.05, "queue_wait": 0.05,
         "cpu_path": 0.0}, {"dispatches": 5, "occupancy": 0.2}, 1000
    ) == "queue_starvation"
    # gather-free kernels are compute-bound, not bandwidth-bound
    assert observatory.dominant_limiter(
        {"device_busy": 0.9, "host_prep": 0.05, "queue_wait": 0.05,
         "cpu_path": 0.0}, {"dispatches": 5, "occupancy": 0.9}, 0
    ) == "device_compute"
    # queue-dominated + idle device = dispatch gap
    assert observatory.dominant_limiter(
        {"device_busy": 0.2, "host_prep": 0.1, "queue_wait": 0.7,
         "cpu_path": 0.0}, {"dispatches": 5, "occupancy": 0.3}, 1000
    ) == "dispatch_gap"
    assert observatory.dominant_limiter(
        {"device_busy": 0.2, "host_prep": 0.7, "queue_wait": 0.1,
         "cpu_path": 0.0}, {"dispatches": 5, "occupancy": 0.9}, 1000
    ) == "host_prep"
    assert observatory.dominant_limiter(
        {}, {"dispatches": 0}, 0
    ) == "no_device_dispatches"


def test_merge_device_blocks_sums_processes_and_dedups():
    a = {"node": "r0", "window_s": 2.0, "lanes": {"ed25519": {
        "dispatches": 2, "items": 10, "pad_items": 2, "submissions": 3,
        "busy_s": 0.8, "host_prep_s": 0.01, "queue_wait_s": 0.0,
        "bytes_up": 100, "bytes_down": 10, "compiles": 1, "devices": 1,
    }}, "shapes": {"ed25519:fused/w4/b8": {"dispatches": 2, "items": 10,
                                           "pad_items": 2}}}
    b = json.loads(json.dumps(a))  # second PROCESS, same posture
    b["node"] = "r1"
    merged = observatory.merge_device_blocks([a, b])
    assert merged["dispatches"] == 4 and merged["items"] == 20
    assert merged["shapes"]["ed25519:fused/w4/b8"]["dispatches"] == 4
    assert merged["window_s"] == 2.0  # max, not sum
    assert merged["processes"] == 2
    lane = merged["lanes"]["ed25519"]
    assert lane["compiles"] == 2
    # device counts SUM across per-process blocks (distinct hardware):
    # two nodes each 40% busy on their own device merge to 40% fleet
    # occupancy, not a saturated single device
    assert lane["devices"] == 2
    assert lane["occupancy"] == pytest.approx(1.6 / (2.0 * 2), abs=1e-6)
    # the SAME process-wide ledger seen through n per-replica flight
    # files (an in-process committee) dedups to one block — the n-fold
    # over-count would inflate every rate and trip reconciliation
    same = [json.loads(json.dumps(a)) for _ in range(4)]
    m1 = observatory.merge_device_blocks(same)
    assert m1["dispatches"] == 2 and m1["processes"] == 1
    assert m1["lanes"]["ed25519"]["devices"] == 1


# ---------------------------------------------------------------------------
# pbft_top DEV cell
# ---------------------------------------------------------------------------


def test_dev_cell_renders_and_blanks():
    snap = {"verify": {"device": {
        "dispatches": 42, "dispatches_per_s": 8.8, "occupancy": 0.95,
        "verifies_per_s_effective": 4123.0, "pad_waste_pct": 12.4,
    }}}
    cell = pbft_top.dev_cell(snap)
    assert cell == "8.8/s 95% 4.1kv/s 12%"
    # the key bank beside it, where the verifier reports one
    snap["verify"]["device_shapes"] = {"bank_keys": 1064, "bank_capacity": 1152}
    assert pbft_top.dev_cell(snap) == "8.8/s 95% 4.1kv/s 12% k1064/1152"
    # and the share of verified rows that took the table-free ladder
    snap["verify"]["device_shapes"] = {
        "bank_keys": 1814, "bank_capacity": 1814, "ladder_items": 430,
        "native_prep_items": 1000, "fallback_prep_items": 0}
    assert pbft_top.dev_cell(snap) == "8.8/s 95% 4.1kv/s 12% k1814/1814 L43%"
    assert pbft_top.dev_cell({"verify": {"device": {"dispatches": 0}}}) == ""
    assert pbft_top.dev_cell({}) == ""
    # the column is wired into the row renderer
    assert "DEV" in pbft_top.COLUMNS


# ---------------------------------------------------------------------------
# dead-target view-change fast-path (ISSUE 14 satellite; e2e regression
# gate is tests/test_sim.py::test_slow_failover_tail_repro_fast_failover)
# ---------------------------------------------------------------------------


def _stub_viewchanger(view_timeout=1.0):
    from collections import defaultdict

    from simple_pbft_tpu.consensus.viewchange import ViewChanger

    cfg = SimpleNamespace(
        view_timeout=view_timeout, n=4, weak_quorum=2,
        replica_ids=("r0", "r1", "r2", "r3"),
        primary=lambda v: f"r{v % 4}",
    )
    rep = SimpleNamespace(
        id="r0", cfg=cfg, view=0, executed_seq=0, max_committed_seen=0,
        peer_seen={}, _boot_mono=clock.now(), metrics=defaultdict(int),
    )
    return ViewChanger(rep), rep


def test_dead_target_evidence_rule():
    vc, rep = _stub_viewchanger()
    now = clock.now()
    # r1 silent past the window, r2+r3 loud: evidence-dead
    rep.peer_seen = {"r2": now, "r3": now, "r1": now - 100.0}
    assert vc.primary_evidence_dead(1)  # primary(1) = r1
    assert not vc.primary_evidence_dead(2)  # r2 is loud
    assert not vc.primary_evidence_dead(4)  # ourselves: never
    # idle committee: nobody loud -> nobody dead
    rep.peer_seen = {}
    assert not vc.primary_evidence_dead(1)
    # we are the partitioned ones: everyone silent -> no verdicts
    rep.peer_seen = {p: now - 100.0 for p in ("r1", "r2", "r3")}
    assert not vc.primary_evidence_dead(1)


def test_next_live_target_skips_dead_and_is_bounded():
    vc, rep = _stub_viewchanger()
    now = clock.now()
    # r1 and r2 crashed (silent), r3 loud: escalation from view 1 must
    # land on view 3 (primary r3), two skips counted
    rep.peer_seen = {"r3": now, "r1": now - 100.0, "r2": now - 100.0}
    assert vc.next_live_target(1) == 3
    assert rep.metrics["deadview_skipped"] == 2
    # a live-primaried start view is never skipped
    assert vc.next_live_target(3) == 3
    # skip budget is one rotation: even a pathological evidence table
    # cannot stall escalation (monkey-verdict everything dead)
    vc.primary_evidence_dead = lambda view: True
    assert vc.next_live_target(1) == 1 + (rep.cfg.n - 1)
