#!/usr/bin/env python3
"""pbft_top: one table for a whole committee's live telemetry.

Scrapes every node's /metrics.json status endpoint (or tails its
flight-recorder JSONL when the process is unreachable — wedged, SIGKILLed,
or just not serving) and renders committee-wide quorum progress, verify
queue depth, and shed/degraded/quarantine state. The r5 qc256 wedge took
25 minutes of blind waiting to diagnose; with this it is one glance:
every row quarantined, verify queue pinned at cap, exec frontier flat.

Sources (combine freely; endpoint wins over flight file for a node):
  --endpoints 127.0.0.1:9100,127.0.0.1:9101   explicit scrape targets
  --log-dir DIR    discover *.status.json endpoint drops AND
                   *.flight.jsonl timelines written by node.py / bench
  --flight-dir DIR alias of --log-dir for bench --flight-dir output

Usage:
  python tools/pbft_top.py --log-dir dep/log              # live loop
  python tools/pbft_top.py --endpoints 127.0.0.1:9100 --once --json
  python tools/pbft_top.py --flight-dir /tmp/flight --once  # post-mortem

Stdlib only (urllib); schema in docs/OBSERVABILITY.md.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time
import urllib.request
from typing import Dict, List, Optional, Tuple

COLUMNS = (
    "NODE", "SRC", "VIEW", "ROLE", "EXEC", "STABLE", "CAGE", "BACKLOG",
    "VQ", "QCQ", "QCB", "PAIRms", "SHED", "DEG", "QUAR", "REJ", "WDOG",
    "AUD", "SPEC", "LOAD", "CTL", "NET", "NETIO", "DEV", "TRACE", "RTTms",
    "LAGms", "LOOP", "REQ/s",
)


def _fmt_kib(b: float) -> str:
    return f"{b / 1024:.0f}K" if b < 10 * 1024 * 1024 else f"{b / (1024 * 1024):.1f}M"


def loop_cell(snap: dict, prev: Optional[dict]) -> str:
    """LOOP: where the event loop's thread went (ISSUE 26) — the
    loop-held stage with the most self time and the off-CPU time, each
    in percent of the time accounted (the nine ``loop.*`` stages,
    ``loop.unattributed`` and ``loop.offcpu``, which together are the
    wall time): ``ingest38 off12``. Between refreshes in the live loop,
    cumulative post-mortem / on the first frame. Blank for a snapshot
    without the accumulators. One loop runs every in-process node, so
    every row of such a committee reads the same. ``fz412k``, where the
    heartbeat reports it, is the objects the collector's policy froze
    (heap.settle_heap; thousands): ``fz0k`` is a node that never settled."""
    def sums(doc: Optional[dict]) -> Dict[str, float]:
        stages = ((doc or {}).get("spans") or {}).get("stages") or {}
        return {k: v.get("sum", 0.0) for k, v in stages.items()
                if k.startswith("loop.") and k != "loop.lag"}

    now, before = sums(snap), sums(prev)
    took = {k: v - before.get(k, 0.0) for k, v in now.items()}
    if any(v < 0 for v in took.values()):
        took = now  # spans.configure() reset the surface between frames
    total = sum(took.values())
    held = {k: v for k, v in took.items()
            if k not in ("loop.offcpu", "loop.unattributed")}
    if total <= 0 or not held:
        return ""
    top = max(held, key=held.get)
    cell = (f"{top[5:]}{100 * held[top] / total:.0f} "
            f"off{100 * took.get('loop.offcpu', 0.0) / total:.0f}")
    frozen = (snap.get("loop_lag") or {}).get("gc_frozen")
    if frozen is not None:
        cell += f" fz{frozen / 1000:.0f}k"
    return cell


def netio_cell(snap: dict, prev: Optional[dict], dt: float) -> str:
    """NETIO: wire-accounting volume (ISSUE 12) — ``msgs/s KiB/s``
    (sent+recv) between refreshes in the live loop, or cumulative
    ``msgs KiB`` totals post-mortem / on the first frame. Blank when the
    node's transport carries no wire ledger (pre-accounting flight
    files)."""
    wire = (snap.get("transport") or {}).get("wire") or {}
    if not wire:
        return ""
    msgs = wire.get("sent_msgs", 0) + wire.get("recv_msgs", 0)
    byts = wire.get("sent_bytes", 0) + wire.get("recv_bytes", 0)
    pwire = ((prev or {}).get("transport") or {}).get("wire") or {}
    if pwire and dt > 0:
        dm = msgs - (pwire.get("sent_msgs", 0) + pwire.get("recv_msgs", 0))
        db = byts - (pwire.get("sent_bytes", 0) + pwire.get("recv_bytes", 0))
        if dm >= 0 and db >= 0:
            return f"{dm / dt:.0f}/s {_fmt_kib(db / dt)}/s"
    return f"{msgs} {_fmt_kib(byts)}"


def _fmt_rate(v: float) -> str:
    return f"{v / 1000:.1f}k" if v >= 1000 else f"{v:.0f}"


def trace_cell(snap: dict) -> str:
    """TRACE: live quorum-margin view (ISSUE 20) — ``p50ms!straggler``
    from the replica snapshot's quorum block: the p50 gap between the
    (2f+1)-th and slowest vote arrival, and the node currently arriving
    last ("3.2!r7" = 3.2 ms of straggler headroom, r7 trailing). Blank
    until a certificate has finalized with a full arrival order (QC-mode
    backups never see the vote flood — only the primary shows margins)."""
    q = (snap.get("replica") or {}).get("quorum") or {}
    if not q.get("certs"):
        return ""
    p50 = (q.get("margin_ms") or {}).get("p50", 0.0)
    cell = f"{p50:.1f}"
    if q.get("last_straggler"):
        cell += f"!{q['last_straggler']}"
    return cell


def dev_cell(snap: dict) -> str:
    """DEV: device-plane observatory aggregates (ISSUE 14) —
    ``disp/s occ% eff-verifies/s pad%`` from the verify service's
    ``device`` ledger block, then ``k<keys>/<capacity>`` of the key bank
    where ``device_shapes`` reports it (keys at the capacity: the next
    walk-in key has no table), then ``L<share>%`` where rows took the
    table-free ladder: their share of the items finished passes verified
    (``ladder_items`` over the two staging counters). Works identically from a live
    scrape and from a flight-file tail (the block rides every frame), so
    a wedged node's last device posture is still one glance. Blank when
    the node never dispatched to a device (CPU-verifier committees)."""
    verify = snap.get("verify") or {}
    dev = verify.get("device") or {}
    if not dev.get("dispatches"):
        return ""
    cell = (
        f"{dev.get('dispatches_per_s', 0):.1f}/s "
        f"{dev.get('occupancy', 0) * 100:.0f}% "
        f"{_fmt_rate(dev.get('verifies_per_s_effective', 0))}v/s "
        f"{dev.get('pad_waste_pct', 0):.0f}%"
    )
    shapes = verify.get("device_shapes") or {}
    if shapes.get("bank_capacity"):
        cell += f" k{shapes.get('bank_keys', 0)}/{shapes['bank_capacity']}"
    if shapes.get("ladder_items"):
        staged = (shapes.get("native_prep_items", 0)
                  + shapes.get("fallback_prep_items", 0))
        cell += f" L{100 * shapes['ladder_items'] / max(staged, 1):.0f}%"
    return cell


def spec_cell(snap: dict) -> str:
    """SPEC: speculative-execution posture (ISSUE 15) —
    ``speculated/rolled-back p50ms`` where the counts are slots executed
    at PREPARED vs slots walked back on divergence, and the latency is
    the spec-reply p50 from the stats histogram (admission -> the
    speculative answer the client can act on). Blank when the node never
    speculated (speculation disabled, or a pre-ISSUE-15 flight file).
    A climbing rolled-back count under view-change churn is expected;
    rolled-back climbing while VIEW is stable is the triage signal
    (docs/SCENARIOS.md §speculative divergence)."""
    rep = snap.get("replica") or {}
    met = rep.get("metrics") or {}
    ex = met.get("spec_executed", 0)
    rb = met.get("spec_rolled_back", 0)
    if not ex and not rb:
        return ""
    cell = f"{ex}/{rb}"
    p50 = ((rep.get("stats") or {}).get("spec_reply_ms") or {}).get("p50")
    if p50:
        cell += f" {p50:.0f}ms"
    return cell


def load_cell(snap: dict, prev: Optional[dict], dt: float) -> str:
    """LOAD: traffic-observatory posture (ISSUE 17) —
    ``offered>accepted/s shed% p99ms`` where the rates are per-class-
    summed offered vs accepted req/s between refreshes in the live
    loop (falling back to the frame's last-closed-window rates on the
    first frame / a flight tail), shed% is the cumulative shed fraction
    of offered, and p99 is the worst honest class's run p99. Blank when
    the node carries no traffic block (not a workload run). Offered
    climbing while accepted holds flat IS overload working as designed;
    shed% ~0 while accepted collapses is the silent-queuing shape the
    shed-before-collapse oracle rejects (docs/SCENARIOS.md)."""
    tr = snap.get("traffic") or {}
    if not tr:
        return ""
    off, acc = tr.get("offered", 0), tr.get("accepted", 0)
    ptr = (prev or {}).get("traffic") or {}
    if ptr and dt > 0 and off >= ptr.get("offered", 0):
        d_off = (off - ptr.get("offered", 0)) / dt
        d_acc = (acc - ptr.get("accepted", 0)) / dt
    else:
        d_off = tr.get("offered_req_s", 0.0)
        d_acc = tr.get("accepted_req_s", 0.0)
    shed_pct = 100.0 * tr.get("shed", 0) / off if off else 0.0
    return (
        f"{_fmt_rate(d_off)}>{_fmt_rate(d_acc)}/s "
        f"{shed_pct:.0f}% {tr.get('worst_p99_ms', 0.0):.0f}ms"
    )


def ctl_cell(snap: dict) -> str:
    """CTL: self-driving perf-plane posture (ISSUE 19) —
    ``profile last-rule(knob-shorthand) age`` plus ``FRZ:n`` when the
    oscillation guard has knobs frozen and ``osc:n`` once any reversal
    was counted. Works identically from a live scrape and from a
    flight-file tail (the knobs block rides every frame). Blank when
    the node carries no knob registry; a registry without a running
    controller shows just the knob count (``8 knobs``) — knobs are
    live-settable even when nothing is driving them. A big last-action
    age during a storm means the controller is NOT reacting — check
    the decision ledger's guard records before blaming the rules
    (docs/OBSERVABILITY.md §self-driving perf plane)."""
    kb = snap.get("knobs") or {}
    if not kb:
        return ""
    post = kb.get("controller") or {}
    if not post:
        return f"{len(kb.get('knobs') or {})} knobs"
    cell = str(post.get("profile", "?"))
    last = post.get("last") or {}
    if last:
        knob = str(last.get("knob", "?")).split(".")[-1]
        cell += f" {last.get('rule', '?')}({knob}) {post.get('last_age_s', 0):.0f}s"
    frozen = (post.get("guard") or {}).get("frozen") or {}
    if frozen:
        cell += f" FRZ:{len(frozen)}"
    if post.get("oscillations"):
        cell += f" osc:{post['oscillations']}"
    return cell


def net_cell(snap: dict) -> str:
    """NET: per-node partition/shaping state (ISSUE 7). Composed from the
    transport block's ``shaping`` sub-snapshot (faults.ShapedTransport):
    the active WAN profile, open outbound cuts ("!2cut"), and a lost-frame
    signal ("~N" = loss + partition drops). A node syncing state shows
    "sync". Blank = unshaped, healthy links."""
    parts = []
    rep = snap.get("replica") or {}
    shaping = (snap.get("transport") or {}).get("shaping") or {}
    if shaping.get("profile"):
        parts.append(str(shaping["profile"]))
    cuts = shaping.get("cut_to") or []
    if cuts:
        parts.append(f"!{len(cuts)}cut")
    lost = (
        shaping.get("shaped_lost", 0) + shaping.get("partition_dropped", 0)
    )
    if lost:
        parts.append(f"~{lost}")
    if rep.get("statesync_active"):
        parts.append("sync")
    if rep.get("retired"):
        parts.append("retired")
    return "+".join(parts)


def scrape_endpoint(hostport: str, timeout: float = 2.0) -> Optional[dict]:
    try:
        with urllib.request.urlopen(
            f"http://{hostport}/metrics.json", timeout=timeout
        ) as resp:
            return json.loads(resp.read())
    except Exception:
        return None


def tail_flight(path: str, max_tail: int = 256 * 1024) -> Optional[dict]:
    """Last complete snapshot line of a flight-recorder JSONL (the file a
    SIGKILLed node left behind)."""
    try:
        size = os.path.getsize(path)
        with open(path, "rb") as fh:
            fh.seek(max(0, size - max_tail))
            lines = [ln for ln in fh.read().split(b"\n") if ln.strip()]
        for ln in reversed(lines):
            try:
                return json.loads(ln)
            except ValueError:
                continue  # torn final line mid-write: take the previous
    except OSError:
        pass
    return None


def discover(log_dir: str) -> Tuple[List[str], Dict[str, str], Dict[str, str]]:
    """(endpoints, {node: flight_path}, {node: evidence_path}) from a
    node/bench log directory."""
    endpoints = []
    for path in sorted(glob.glob(os.path.join(log_dir, "*.status.json"))):
        try:
            doc = json.load(open(path))
            endpoints.append(f"{doc.get('host', '127.0.0.1')}:{doc['port']}")
        except (OSError, ValueError, KeyError):
            continue
    flights = {
        os.path.basename(p)[: -len(".flight.jsonl")]: p
        for p in sorted(glob.glob(os.path.join(log_dir, "*.flight.jsonl")))
    }
    # sim flight frames (Scenario.flight_dir) use the flight_<node>.jsonl
    # spelling; fold them in under the node name so the post-mortem
    # table reads a sim run's last posture too (ISSUE 17)
    for p in sorted(glob.glob(os.path.join(log_dir, "flight_*.jsonl"))):
        node = os.path.basename(p)[len("flight_"):-len(".jsonl")]
        flights.setdefault(node, p)
    evidence = {
        os.path.basename(p)[: -len(".evidence.jsonl")]: p
        for p in sorted(glob.glob(os.path.join(log_dir, "*.evidence.jsonl")))
    }
    return endpoints, flights, evidence


_EVIDENCE_CACHE: Dict[str, Tuple[tuple, Optional[dict]]] = {}


def evidence_summary(path: str) -> Optional[dict]:
    """Post-mortem AUD fallback: synthesize a minimal ``audit`` block
    from a node's evidence ledger (the auditor only creates the file on
    the first violation, so existence alone is already a signal).
    Cached by (mtime, size) — the live loop re-calls this every refresh
    tick and evidence ledgers can be large. Rotation-aware: the ``.1``
    backup's records count too."""
    try:
        st = os.stat(path)
    except OSError:
        return None
    key = (st.st_mtime_ns, st.st_size)
    cached = _EVIDENCE_CACHE.get(path)
    if cached is not None and cached[0] == key:
        return cached[1]
    count = 0
    last_kind = None
    last_accused = None
    for p in (path + ".1", path):  # rotated backup first (older records)
        try:
            with open(p, "r") as fh:
                for ln in fh:
                    ln = ln.strip()
                    if not ln:
                        continue
                    try:
                        rec = json.loads(ln)
                    except ValueError:
                        continue  # torn final line
                    if rec.get("evt") == "violation":
                        count += 1
                        last_kind = rec.get("kind")
                        last_accused = (
                            ",".join(rec.get("accused") or []) or None
                        )
        except OSError:
            continue
    summ = (
        {"violations": count, "last_kind": last_kind,
         "last_accused": last_accused}
        if count else None
    )
    _EVIDENCE_CACHE[path] = (key, summ)
    return summ


def row_from_snapshot(snap: dict, src: str, prev: Optional[dict],
                      dt: float) -> List[str]:
    rep = snap.get("replica") or {}
    ver = snap.get("verify") or {}
    lane = snap.get("qc_lane") or {}  # QC verify lane (qc-mode runs only)
    lag = snap.get("loop_lag") or {}  # event-loop scheduling delay
    aud = snap.get("audit") or {}  # safety auditor (evidence counters)
    met = rep.get("metrics") or {}
    # AUD: evidence count + last accused replica — "2:r0" means two
    # violations, most recently accusing r0; "0" is an attached auditor
    # with a clean ledger; blank means no auditor
    aud_cell = ""
    if aud:
        aud_cell = str(aud.get("violations", 0))
        if aud.get("violations") and aud.get("last_accused"):
            aud_cell += f":{aud['last_accused']}"
    # commit age: seconds since this node last applied a block — the
    # wedge gauge (a live view with CAGE climbing IS the qc256 shape)
    cage = rep.get("last_commit_age_s")
    committed = met.get("committed_requests", 0)
    rate = ""
    if prev is not None and dt > 0:
        prev_committed = (
            (prev.get("replica") or {}).get("metrics", {})
            .get("committed_requests", 0)
        )
        rate = f"{(committed - prev_committed) / dt:.1f}"
    backlog = rep.get("pending_requests", 0) + rep.get("relay_buffer", 0)
    return [
        str(snap.get("node", "?")),
        src,
        str(rep.get("view", "?")),
        ("PRIM" if rep.get("is_primary")
         else "vc" if rep.get("in_view_change") else "bkup"),
        str(rep.get("executed_seq", "?")),
        str(rep.get("stable_seq", "?")),
        (f"{cage:.1f}" if isinstance(cage, (int, float)) else ""),
        str(backlog),
        str(ver.get("pending_items", "")),
        str(lane.get("pending", "")),
        str(lane.get("batch_mean", "")),
        (f"{lane['pairing_ms_ema']:.0f}" if "pairing_ms_ema" in lane else ""),
        str(met.get("messages_shed", 0)),
        "*" if (met.get("degraded_mode") or ver.get("degraded")) else "",
        "*" if ver.get("quarantined") else "",
        str(ver.get("overload_rejections", "")),
        str(ver.get("watchdog_failovers", "")),
        aud_cell,
        spec_cell(snap),
        load_cell(snap, prev, dt),
        ctl_cell(snap),
        net_cell(snap),
        netio_cell(snap, prev, dt),
        dev_cell(snap),
        trace_cell(snap),
        (f"{ver['rtt_ms_ema']:.0f}" if "rtt_ms_ema" in ver else ""),
        (f"{lag['ema_ms']:.1f}" if "ema_ms" in lag else ""),
        loop_cell(snap, prev),
        rate,
    ]


def render(rows: List[List[str]]) -> str:
    table = [list(COLUMNS)] + rows
    widths = [max(len(r[i]) for r in table) for i in range(len(COLUMNS))]
    lines = [
        "  ".join(cell.ljust(w) for cell, w in zip(r, widths)).rstrip()
        for r in table
    ]
    i_exec = COLUMNS.index("EXEC")
    i_deg, i_quar = COLUMNS.index("DEG"), COLUMNS.index("QUAR")
    execs = [int(r[i_exec]) for r in rows if r[i_exec].isdigit()]
    if execs:
        lines.append(
            f"-- committee: {len(rows)} nodes, exec frontier "
            f"min={min(execs)} max={max(execs)} (spread {max(execs) - min(execs)}), "
            f"degraded={sum(1 for r in rows if r[i_deg])}, "
            f"quarantined={sum(1 for r in rows if r[i_quar])}"
        )
    return "\n".join(lines)


def gather(endpoints: List[str], flights: Dict[str, str]) -> Dict[str, Tuple[str, dict]]:
    """node -> (source, snapshot). Endpoint scrape wins; flight tail
    covers nodes that stopped serving (the post-mortem path)."""
    snaps: Dict[str, Tuple[str, dict]] = {}
    for hp in endpoints:
        snap = scrape_endpoint(hp)
        if snap is not None:
            snaps[str(snap.get("node", hp))] = ("http", snap)
    for node, path in flights.items():
        if node in snaps:
            continue
        snap = tail_flight(path)
        if snap is not None:
            snaps[node] = ("jsonl", snap)
    return snaps


def main() -> None:
    ap = argparse.ArgumentParser(
        description="committee-wide live telemetry table"
    )
    ap.add_argument("--endpoints", default="",
                    help="comma-separated host:port /metrics.json targets")
    ap.add_argument("--log-dir", default=None,
                    help="discover *.status.json + *.flight.jsonl here")
    ap.add_argument("--flight-dir", default=None,
                    help="alias of --log-dir (bench --flight-dir output)")
    ap.add_argument("--interval", type=float, default=2.0)
    ap.add_argument("--once", action="store_true",
                    help="print one table and exit (no screen clearing)")
    ap.add_argument("--json", action="store_true",
                    help="emit raw snapshots as JSONL instead of the table")
    args = ap.parse_args()

    endpoints = [e.strip() for e in args.endpoints.split(",") if e.strip()]
    prev: Dict[str, dict] = {}
    prev_t = time.monotonic()
    while True:
        flights: Dict[str, str] = {}
        evidence: Dict[str, str] = {}
        found: List[str] = []
        for d in (args.log_dir, args.flight_dir):
            if d:
                eps, fls, evs = discover(d)
                found.extend(eps)
                flights.update(fls)
                evidence.update(evs)
        snaps = gather(endpoints + found, flights)
        for node, (_, snap) in snaps.items():
            if "audit" not in snap and node in evidence:
                # post-mortem fallback: a flight frame predating the
                # audit plane (or a node whose snapshot lacks the block)
                # still surfaces its on-disk evidence ledger
                summ = evidence_summary(evidence[node])
                if summ is not None:
                    snap["audit"] = summ
        now = time.monotonic()
        if not snaps:
            print("pbft_top: no nodes found (check --endpoints/--log-dir)",
                  file=sys.stderr)
            if args.once:
                sys.exit(1)
        elif args.json:
            for _, (_, snap) in sorted(snaps.items()):
                print(json.dumps(snap, sort_keys=True))
        else:
            rows = [
                row_from_snapshot(snap, src, prev.get(node), now - prev_t)
                for node, (src, snap) in sorted(snaps.items())
            ]
            if not args.once:
                print("\x1b[2J\x1b[H", end="")  # clear screen, home cursor
                print(time.strftime("%H:%M:%S"), "pbft_top")
            print(render(rows))
        prev = {node: snap for node, (_, snap) in snaps.items()}
        prev_t = now
        if args.once:
            return
        time.sleep(args.interval)


if __name__ == "__main__":
    main()
