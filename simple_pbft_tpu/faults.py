"""Deterministic fault injection: seeded schedules + an async injector.

The r5 evidence gap this closes (VERDICT Missing #1/#4): the chaos-on-TPU
cell never ran because there was no way to inject a device stall, and the
storm A/B was not crash-count-matched because crashes fired on ad-hoc
wall-clock grids. Here every fault a run experiences is a pure function
of a seed: ``FaultSchedule.generate(seed=42, ...)`` yields the identical
event list on every host, every run — so a wedge reproduces, an A/B pair
really differs only in the axis under test, and a regression test can
assert behavior under the EXACT schedule that once wedged.

The fault kinds are defined in ``KIND_REGISTRY`` below — the SINGLE
source of truth the docstrings, the ``--fault-schedule`` parse errors,
and ``KINDS`` are all generated from (a kind added to the registry can
never again drift undocumented). Call ``kind_table()`` for the current
table; it is appended to this module's and FaultSchedule's docstrings
at import.

The injector drives a LocalCommittee (transport/local.py); the wrappers
slot into any verifier seam. Real-process deployments get the same
schedule shape through bench_consensus.py's --fault-schedule flag, and
WAN link shaping additionally through node.py's --wan-profile flag
(docs/SCENARIOS.md).
"""

from __future__ import annotations

import asyncio
import logging
import random
import threading
import time
import zlib
from dataclasses import dataclass, field
from typing import ClassVar, Dict, List, Optional, Sequence, Set, Tuple

from . import clock
from .crypto.signer import Signer
from .messages import Checkpoint, Message, PrePrepare, QuorumCert, sha256_hex
from .transport import base as base_transport
from .workload import (
    WORKLOAD_KINDS,
    WorkloadEvent,
    workload_event_from_dict,
    workload_kind_table,
)

# The authoritative fault-kind registry: kind -> one-line description.
# EVERYTHING that names the kind set (module/class docstrings, parse
# error messages, KINDS) derives from this dict — add new kinds HERE.
KIND_REGISTRY: Dict[str, str] = {
    "crash": (
        "crash-stop a replica (the named one, or whoever is primary of "
        "the highest live view at fire time)"
    ),
    "drop_window": (
        "raise the network's iid drop rate to `magnitude` for "
        "`duration` seconds, then restore"
    ),
    "delay_window": (
        "uniform per-message delay up to `magnitude` seconds for "
        "`duration` seconds, then restore"
    ),
    "slow_verifier": (
        "arm a SlowVerifier wrapper: every batch pays `magnitude` extra "
        "seconds for `duration`"
    ),
    "stall_device": (
        "arm a StallableDevice wrapper: device finishers block for "
        "`duration` seconds (the VerifyService dispatch-deadline "
        "watchdog's target — see crypto/coalesce)"
    ),
    "equivocate": (
        "wrap the target in EquivocatingPrimary: pre-prepares FORK to "
        "disjoint committee halves, validly signed (docs/AUDIT.md)"
    ),
    "fork_checkpoint": (
        "wrap the target in ForkingCheckpointer: outbound checkpoints "
        "carry a wrong, validly re-signed state digest"
    ),
    "partition": (
        "cut links per `spec` 'SRCS>DSTS' (asymmetric) or 'SRCS<>DSTS' "
        "(symmetric), groups |-separated, '*' = all replicas; heals "
        "after `duration` seconds when duration > 0 (ShapedTransport)"
    ),
    "heal": "heal every open partition on every shaped transport",
    "shape": (
        "apply the named WAN profile in `spec` (see WAN_PROFILES: "
        "wan3dc, lossy) to every replica's links for `duration` "
        "seconds (0 = rest of the run)"
    ),
    "stale_epoch": (
        "arm a StaleEpochVoter on the target: a replica removed by a "
        "reconfiguration that keeps voting in the old committee "
        "(honest nodes must role-gate it out, docs/SCENARIOS.md)"
    ),
    "forge_statesync": (
        "arm a ForgedSnapshotServer on the target: state-transfer "
        "chunks it serves are corrupted — a joiner must detect the "
        "digest mismatch and re-fetch from another peer"
    ),
    "spec_divergence": (
        "arm a SpecDivergencePrimary on the target (QC-mode primary): "
        "every k-th slot's prepare QC is revealed to a SINGLE victim "
        "and the commit QC withheld — the victim speculates a block "
        "the rest of the committee never prepared, and the fork is "
        "only revealed when a view change may no-op the slot "
        "(speculative rollback, consensus/speculation.py)"
    ),
}

KINDS = tuple(KIND_REGISTRY)

log = logging.getLogger("pbft.faults")


def kind_table() -> str:
    """The fault-kind table, regenerated from KIND_REGISTRY."""
    width = max(len(k) for k in KIND_REGISTRY)
    return "\n".join(
        f"- {k.ljust(width)} : {desc}" for k, desc in KIND_REGISTRY.items()
    )


@dataclass(frozen=True)
class FaultEvent:
    """One scheduled fault. ``t`` is seconds from injector start."""

    t: float
    kind: str
    target: str = ""  # replica id; "" = current primary at fire time
    duration: float = 0.0
    magnitude: float = 0.0
    # kind-specific payload: partition group spec ("r0|r1>r2|r3"),
    # WAN profile name for `shape` ("wan3dc") — empty for other kinds
    spec: str = ""

    def to_dict(self) -> dict:
        d = {
            "t": round(self.t, 3),
            "kind": self.kind,
            "target": self.target,
            "duration": round(self.duration, 3),
            "magnitude": round(self.magnitude, 4),
        }
        if self.spec:
            d["spec"] = self.spec
        return d


@dataclass(frozen=True)
class FaultSchedule:
    """An immutable, seed-deterministic list of FaultEvents, plus (since
    schema v3 / ISSUE 17) the run's WorkloadEvents: one schedule object
    IS the complete replay tuple — faults AND load shape — so sim repro
    artifacts, bench ledger lines and ddmin minimization treat both
    planes uniformly."""

    seed: int
    horizon: float
    events: Tuple[FaultEvent, ...]
    workload: Tuple[WorkloadEvent, ...] = ()

    @classmethod
    def generate(
        cls,
        seed: int,
        horizon: float,
        crashes: int = 0,
        drop_windows: int = 0,
        delay_windows: int = 0,
        slow_verifier_windows: int = 0,
        device_stalls: int = 0,
        equivocators: int = 0,
        checkpoint_forkers: int = 0,
        partition_windows: int = 0,
        wan: str = "",
        stale_epoch_voters: int = 0,
        statesync_forgers: int = 0,
        spec_divergers: int = 0,
        replica_ids: Sequence[str] = (),
        drop_rate: float = 0.02,
        delay_s: float = 0.03,
        slow_s: float = 0.05,
        stall_s: float = 5.0,
        extra_events: Sequence["FaultEvent"] = (),
        bursts: int = 0,
        retry_storms: int = 0,
        byz_floods: int = 0,
        remixes: int = 0,
        class_names: Sequence[str] = (),
        workload_events: Sequence[WorkloadEvent] = (),
    ) -> "FaultSchedule":
        """Deterministic schedule over ``horizon`` seconds. Same
        arguments -> byte-identical schedule, on any host (the RNG is a
        private random.Random(seed); nothing reads the wall clock).
        Events avoid the first and last 10% of the horizon so setup and
        drain windows stay clean, mirroring the storm bench's crash grid
        (first crash at horizon/6)."""
        rng = random.Random(seed)
        lo, hi = 0.1 * horizon, 0.9 * horizon
        events: List[FaultEvent] = []

        def times(k: int) -> List[float]:
            return sorted(rng.uniform(lo, hi) for _ in range(k))

        for t in times(crashes):
            # "" targets the live primary at fire time — matching the
            # storm bench's behavior so a crash-count-matched A/B only
            # differs in WHEN, deterministically, not in WHO
            target = ""
            if replica_ids and rng.random() < 0.25:
                target = rng.choice(list(replica_ids))
            events.append(FaultEvent(t=t, kind="crash", target=target))
        for t in times(drop_windows):
            events.append(FaultEvent(
                t=t, kind="drop_window",
                duration=rng.uniform(0.5, 0.15 * horizon),
                magnitude=drop_rate * rng.uniform(0.5, 2.0),
            ))
        for t in times(delay_windows):
            events.append(FaultEvent(
                t=t, kind="delay_window",
                duration=rng.uniform(0.5, 0.15 * horizon),
                magnitude=delay_s * rng.uniform(0.5, 2.0),
            ))
        for t in times(slow_verifier_windows):
            events.append(FaultEvent(
                t=t, kind="slow_verifier",
                duration=rng.uniform(0.5, 0.15 * horizon),
                magnitude=slow_s * rng.uniform(0.5, 2.0),
            ))
        for t in times(device_stalls):
            events.append(FaultEvent(
                t=t, kind="stall_device", duration=stall_s,
            ))
        for t in times(equivocators):
            # "" = whoever is primary at fire time: equivocation is a
            # PRIMARY behavior (pre-prepare forks), so the live primary
            # is the only target that exercises the detection path
            events.append(FaultEvent(t=t, kind="equivocate"))
        for t in times(checkpoint_forkers):
            # any replica can fork its checkpoints; pick one
            # deterministically when the committee roster is known
            target = (
                rng.choice(list(replica_ids)) if replica_ids else ""
            )
            events.append(FaultEvent(t=t, kind="fork_checkpoint",
                                     target=target))
        for t in times(partition_windows):
            # deterministic random split: a minority group loses its
            # links TO the majority (asymmetric — it still hears them)
            # half the time, both directions otherwise; always heals
            # before the drain window (duration bounded by the window
            # rule the other kinds use)
            ids = list(replica_ids)
            if len(ids) < 2:
                continue
            rng.shuffle(ids)
            cut = max(1, len(ids) // 3)
            a, b = ids[:cut], ids[cut:]
            arrow = ">" if rng.random() < 0.5 else "<>"
            events.append(FaultEvent(
                t=t, kind="partition",
                # clamp the floor: on short horizons uniform(0.5, 0.15h)
                # would INVERT its bounds and deal durations past the cap
                # (and potentially past the horizon into the drain)
                duration=rng.uniform(
                    min(0.5, 0.15 * horizon), 0.15 * horizon
                ),
                spec=f"{'|'.join(a)}{arrow}{'|'.join(b)}",
            ))
        if wan:
            if wan not in WAN_PROFILES:
                raise ValueError(
                    f"unknown WAN profile {wan!r} "
                    f"(known: {sorted(WAN_PROFILES)})"
                )
            # profile applies from t=0 for the whole run: WAN shaping is
            # an environment, not a transient fault
            events.append(FaultEvent(t=0.0, kind="shape", spec=wan))
        for t in times(stale_epoch_voters):
            target = rng.choice(list(replica_ids)) if replica_ids else ""
            events.append(FaultEvent(t=t, kind="stale_epoch",
                                     target=target))
        for t in times(statesync_forgers):
            target = rng.choice(list(replica_ids)) if replica_ids else ""
            events.append(FaultEvent(t=t, kind="forge_statesync",
                                     target=target))
        for t in times(spec_divergers):
            # "" = the live primary at fire time: withholding quorum
            # aggregates is a PRIMARY power (QC mode), like equivocation
            events.append(FaultEvent(t=t, kind="spec_divergence"))
        events.extend(extra_events)
        events.sort(key=lambda e: (e.t, e.kind, e.target, e.spec))
        # workload-event draws come AFTER every fault draw so zero
        # workload counts leave the fault RNG stream — and therefore
        # every pre-v3 schedule — byte-identical
        wl: List[WorkloadEvent] = []
        honest = [c for c in class_names if c != "byzantine"]
        for t in times(bursts):
            target = ""
            if honest and rng.random() < 0.5:
                target = rng.choice(honest)
            wl.append(WorkloadEvent(
                t=t, kind="burst", target=target,
                duration=rng.uniform(min(0.5, 0.15 * horizon),
                                     0.25 * horizon),
                magnitude=rng.uniform(2.0, 8.0),
            ))
        for t in times(retry_storms):
            wl.append(WorkloadEvent(
                t=t, kind="retry_storm",
                duration=rng.uniform(min(0.5, 0.15 * horizon),
                                     0.25 * horizon),
                magnitude=rng.uniform(2.0, 4.0),
            ))
        for t in times(byz_floods):
            wl.append(WorkloadEvent(
                t=t, kind="byz_flood",
                duration=rng.uniform(min(0.5, 0.15 * horizon),
                                     0.25 * horizon),
                magnitude=rng.uniform(1.0, 4.0),
            ))
        for t in times(remixes):
            if len(honest) < 2:
                continue
            src = rng.choice(honest)
            dst = rng.choice([c for c in honest if c != src])
            wl.append(WorkloadEvent(
                t=t, kind="remix", spec=f"{src}>{dst}",
                duration=rng.uniform(min(0.5, 0.15 * horizon),
                                     0.25 * horizon),
                magnitude=rng.uniform(0.3, 0.9),
            ))
        wl.extend(workload_events)
        wl.sort(key=lambda e: (e.t, e.kind, e.target, e.spec))
        return cls(seed=seed, horizon=horizon, events=tuple(events),
                   workload=tuple(wl))

    # --fault-schedule spec keys (regenerated into parse errors so new
    # keys can't drift undocumented): scalar keys take one value (last
    # wins), event keys may REPEAT (each occurrence adds an event) and
    # may also hold several ';'-separated entries in one value.
    SCALAR_PARSE_KEYS: ClassVar[Dict[str, str]] = {
        "seed": "RNG seed (default 42)",
        "crashes": "count of crash events",
        "drops": "count of drop_window events",
        "delays": "count of delay_window events",
        "slow": "count of slow_verifier windows",
        "stalls": "count of stall_device events",
        "equiv": "count of equivocate events",
        "forkckpt": "count of fork_checkpoint events",
        "partitions": "count of GENERATED random partition windows",
        "stale": "count of stale_epoch events",
        "forgesync": "count of forge_statesync events",
        "specdiv": (
            "count of spec_divergence events (QC-mode speculative "
            "plane, ISSUE 15)"
        ),
        "wan": "WAN profile name applied at t=0 (wan3dc, lossy, ...)",
        "stall_s": "stall_device duration seconds",
        "drop_rate": "drop_window base rate",
        "delay_s": "delay_window base delay seconds",
        "slow_s": "slow_verifier base delay seconds",
        "bursts": "count of burst workload events (flash crowds)",
        "storms": "count of retry_storm workload events",
        "floods": "count of byz_flood workload events",
        "remixes": "count of remix workload events (class remix)",
    }
    EVENT_PARSE_KEYS: ClassVar[Dict[str, str]] = {
        "partition": (
            "T:SRCS>DSTS[:DUR] or T:SRCS<>DSTS[:DUR] — explicit "
            "partition at T seconds, groups |-separated, '*'=all; "
            "DUR>0 auto-heals"
        ),
        "heal": "T — heal every open partition at T seconds",
        "shape": "NAME or T:NAME[:DUR] — apply a WAN profile",
    }

    @classmethod
    def parse(cls, spec: str, horizon: float,
              replica_ids: Sequence[str] = ()) -> "FaultSchedule":
        """Build from a CLI spec like
        ``seed=42,crashes=3,drops=1,stalls=1,equiv=1,forkckpt=1,
        partition=2.0:r0|r1<>r2|r3:1.5,heal=5.0,shape=wan3dc`` — the
        bench_consensus --fault-schedule format. Raises ValueError on
        unknown keys (a typo must not silently mean 'no faults'); the
        error names every known key and the kind table, both generated
        from the registries."""
        scalars: Dict[str, str] = {}
        extra: List[FaultEvent] = []
        for kv in spec.split(","):
            if not kv:
                continue
            if "=" not in kv:
                raise ValueError(
                    f"malformed fault-schedule entry {kv!r} (want key=value)"
                )
            key, val = kv.split("=", 1)
            if key in cls.SCALAR_PARSE_KEYS:
                scalars[key] = val
            elif key in cls.EVENT_PARSE_KEYS:
                for one in val.split(";"):
                    if one:
                        extra.append(cls._parse_event(key, one, replica_ids))
            else:
                known = sorted(cls.SCALAR_PARSE_KEYS) + sorted(
                    cls.EVENT_PARSE_KEYS
                )
                raise ValueError(
                    f"unknown fault-schedule key {key!r}; known keys: "
                    f"{known}\nfault kinds:\n{kind_table()}"
                )
        return cls.generate(
            seed=int(scalars.get("seed", 42)),
            horizon=horizon,
            crashes=int(scalars.get("crashes", 0)),
            drop_windows=int(scalars.get("drops", 0)),
            delay_windows=int(scalars.get("delays", 0)),
            slow_verifier_windows=int(scalars.get("slow", 0)),
            device_stalls=int(scalars.get("stalls", 0)),
            equivocators=int(scalars.get("equiv", 0)),
            checkpoint_forkers=int(scalars.get("forkckpt", 0)),
            partition_windows=int(scalars.get("partitions", 0)),
            wan=scalars.get("wan", ""),
            stale_epoch_voters=int(scalars.get("stale", 0)),
            statesync_forgers=int(scalars.get("forgesync", 0)),
            spec_divergers=int(scalars.get("specdiv", 0)),
            replica_ids=replica_ids,
            drop_rate=float(scalars.get("drop_rate", 0.02)),
            delay_s=float(scalars.get("delay_s", 0.03)),
            slow_s=float(scalars.get("slow_s", 0.05)),
            stall_s=float(scalars.get("stall_s", 5.0)),
            extra_events=extra,
            bursts=int(scalars.get("bursts", 0)),
            retry_storms=int(scalars.get("storms", 0)),
            byz_floods=int(scalars.get("floods", 0)),
            remixes=int(scalars.get("remixes", 0)),
        )

    @classmethod
    def _parse_event(cls, key: str, val: str,
                     replica_ids: Sequence[str]) -> FaultEvent:
        """One explicit event entry (see EVENT_PARSE_KEYS grammar)."""
        if key == "heal":
            try:
                return FaultEvent(t=float(val), kind="heal")
            except ValueError:
                raise ValueError(f"heal= wants a time, got {val!r}") from None
        if key == "shape":
            parts = val.split(":")
            if len(parts) == 1:
                t, name, dur = 0.0, parts[0], 0.0
            else:
                # multi-part MUST be T:NAME[:DUR] — a non-numeric first
                # field (e.g. 'shape=lossy:5') is a malformed spec, and a
                # typo must not silently mean different faults
                try:
                    t = float(parts[0])
                    dur = float(parts[2]) if len(parts) > 2 else 0.0
                except ValueError:
                    raise ValueError(
                        f"shape= wants NAME or T:NAME[:DUR], got {val!r}"
                    ) from None
                name = parts[1]
            if name not in WAN_PROFILES:
                raise ValueError(
                    f"shape= wants a WAN profile "
                    f"(known: {sorted(WAN_PROFILES)}), got {val!r}"
                )
            return FaultEvent(t=t, kind="shape", spec=name, duration=dur)
        # partition: T:SRCS>DSTS[:DUR]
        parts = val.split(":")
        if len(parts) < 2:
            raise ValueError(
                f"partition= wants T:SRCS>DSTS[:DUR], got {val!r}"
            )
        try:
            t = float(parts[0])
            dur = float(parts[2]) if len(parts) > 2 else 0.0
        except ValueError:
            raise ValueError(
                f"partition= wants numeric T/DUR, got {val!r}"
            ) from None
        parse_partition_spec(parts[1], replica_ids)  # validate now
        return FaultEvent(t=t, kind="partition", spec=parts[1],
                          duration=dur)

    #: summary()/from_summary() wire format version (ISSUE 13 satellite:
    #: any failing run's exact schedule must reconstruct from its ledger
    #: line alone)
    SUMMARY_SCHEMA: ClassVar[str] = "fault-schedule-v3"

    def summary(self) -> dict:
        """Ledger/bench-record form: the complete replay tuple. Carries
        (seed, horizon, the full event list, and a kind-table
        fingerprint), so :meth:`from_summary` rebuilds the EXACT
        schedule from a ledger line with no access to the original CLI
        spec or generate() arguments — and a replay attempted against a
        drifted kind registry fails loudly instead of silently meaning
        different faults."""
        kinds: Dict[str, int] = {}
        for e in self.events:
            kinds[e.kind] = kinds.get(e.kind, 0) + 1
        doc = {
            "schema": self.SUMMARY_SCHEMA,
            "seed": self.seed,
            "horizon_s": round(self.horizon, 1),
            # crc over the ordered FAULT kind table only — unchanged
            # across v2->v3, so pre-workload ledger lines replay without
            # a spurious registry-drift warning
            "kinds_crc": zlib.crc32(",".join(KINDS).encode()) & 0xFFFFFFFF,
            "counts": kinds,
            "events": [e.to_dict() for e in self.events],
        }
        if self.workload:
            wkinds: Dict[str, int] = {}
            for e in self.workload:
                wkinds[e.kind] = wkinds.get(e.kind, 0) + 1
            doc["workload"] = [e.to_dict() for e in self.workload]
            doc["workload_counts"] = wkinds
            doc["workload_kinds_crc"] = (
                zlib.crc32(",".join(WORKLOAD_KINDS).encode()) & 0xFFFFFFFF
            )
        return doc

    @classmethod
    def from_summary(cls, doc: dict) -> "FaultSchedule":
        """Rebuild the exact schedule from a :meth:`summary` dict (a
        bench record's ``faults`` block, a campaign ledger line, a sim
        repro artifact). Unknown event kinds are an error — the ledger
        predates/postdates this registry and a replay would lie."""
        crc = doc.get("kinds_crc")
        here = zlib.crc32(",".join(KINDS).encode()) & 0xFFFFFFFF
        if crc is not None and int(crc) != here:
            # the registry changed since this schedule was recorded.
            # Per-event name lookup below still hard-fails on renames/
            # removals; a mismatch with all names resolving means the
            # registry GREW (or semantics drifted) — replay proceeds,
            # loudly, so a semantics drift is never silent
            log.warning(
                "replaying a schedule recorded under a different fault-"
                "kind registry (crc %s, current %s): additions are fine, "
                "semantic drift is not — review KIND_REGISTRY history",
                crc, here,
            )
        events = []
        for e in doc.get("events", ()):
            kind = e.get("kind", "")
            if kind not in KIND_REGISTRY:
                raise ValueError(
                    f"cannot replay: unknown fault kind {kind!r} "
                    f"(known: {sorted(KIND_REGISTRY)}); the schedule "
                    "was recorded under a different kind registry"
                )
            events.append(FaultEvent(
                t=float(e["t"]),
                kind=kind,
                target=str(e.get("target", "")),
                duration=float(e.get("duration", 0.0)),
                magnitude=float(e.get("magnitude", 0.0)),
                spec=str(e.get("spec", "")),
            ))
        # v2 docs carry no "workload" key: () — old ledgers still parse
        wcrc = doc.get("workload_kinds_crc")
        where = zlib.crc32(",".join(WORKLOAD_KINDS).encode()) & 0xFFFFFFFF
        if wcrc is not None and int(wcrc) != where:
            log.warning(
                "replaying a schedule recorded under a different workload-"
                "kind registry (crc %s, current %s): additions are fine, "
                "semantic drift is not — review WORKLOAD_KIND_REGISTRY "
                "history", wcrc, where,
            )
        workload = tuple(
            workload_event_from_dict(e) for e in doc.get("workload", ())
        )
        return cls(
            seed=int(doc.get("seed", 0)),
            horizon=float(doc.get("horizon_s", 0.0)),
            events=tuple(events),
            workload=workload,
        )


# ---------------------------------------------------------------------------
# WAN link shaping (ISSUE 7 tentpole): a transport wrapper that imposes
# per-link latency/jitter/bandwidth/loss and asymmetric partitions. It
# composes over ANY Transport (local endpoint, tcp, grpc) because it
# shapes at the SEND seam — each node shapes its own outbound links, so
# an asymmetric partition A->B is simply A's wrapper cutting dest B
# while B keeps sending to A.
# ---------------------------------------------------------------------------


@dataclass
class LinkShape:
    """One directed link's character. delay/jitter are seconds added per
    frame; ``loss`` is an iid drop probability; ``bw_bytes_per_s`` > 0
    serializes frames through a token-bucket link (a 1 MB NEW-VIEW on a
    1 MB/s link takes a second — the failover shape WAN runs expose)."""

    delay_s: float = 0.0
    jitter_s: float = 0.0
    loss: float = 0.0
    bw_bytes_per_s: float = 0.0  # 0 = unlimited


def _node_seed(node_id: str) -> int:
    """Stable per-node RNG salt. NOT ``hash(str)`` — that is salted per
    process (PYTHONHASHSEED), which would break the module's core
    contract: the same seed must replay the identical jitter/loss stream
    on any host, any run."""
    return zlib.crc32(node_id.encode()) & 0xFFFF


#: Named WAN profiles. A profile is a function (ids, seed) -> per-src
#: per-dst LinkShape maps; registered here so schedules/CLI flags can
#: name them (`shape=wan3dc`, node.py --wan-profile lossy).
WAN_PROFILES: Dict[str, object] = {}


def _profile(name):
    def reg(fn):
        WAN_PROFILES[name] = fn
        return fn

    return reg


@_profile("wan3dc")
def _wan3dc(ids: Sequence[str], seed: int = 0) -> Dict[str, Dict[str, LinkShape]]:
    """Three datacenters, nodes assigned round-robin: intra-DC links are
    fast LAN (~0.3 ms), inter-DC links pay ~12 ms +/- jitter with a
    trickle of loss — the classic geo-replicated committee."""
    dc = {rid: i % 3 for i, rid in enumerate(ids)}
    lan = LinkShape(delay_s=0.0003, jitter_s=0.0001)
    wan = LinkShape(delay_s=0.012, jitter_s=0.003, loss=0.002)
    return {
        src: {
            dst: (lan if dc[src] == dc[dst] else wan)
            for dst in ids if dst != src
        }
        for src in ids
    }


@_profile("wan_thin")
def _wan_thin(ids: Sequence[str], seed: int = 0) -> Dict[str, Dict[str, LinkShape]]:
    """wan3dc's topology with BANDWIDTH-LIMITED inter-DC links: 256
    KB/s per directed link. Block bytes now serialize in virtual time,
    so committee throughput is finite and over-admission queues for
    real — the load shape the knob campaign (ISSUE 19) swings shed
    watermarks against. Jitter-free and lossless on purpose: the
    campaign compares tunings, and retransmission noise would blur the
    queueing signal it measures."""
    dc = {rid: i % 3 for i, rid in enumerate(ids)}
    lan = LinkShape(delay_s=0.0003, jitter_s=0.0001)
    wan = LinkShape(delay_s=0.012, bw_bytes_per_s=256_000.0)
    return {
        src: {
            dst: (lan if dc[src] == dc[dst] else wan)
            for dst in ids if dst != src
        }
        for src in ids
    }


@_profile("lossy")
def _lossy(ids: Sequence[str], seed: int = 0) -> Dict[str, Dict[str, LinkShape]]:
    """Every link pays a few ms and drops 5% of frames iid — the
    retransmission-path workout (PBFT must commit through it)."""
    link = LinkShape(delay_s=0.002, jitter_s=0.002, loss=0.05)
    return {src: {dst: link for dst in ids if dst != src} for src in ids}


def parse_partition_spec(
    spec: str, ids: Sequence[str] = ()
) -> Tuple[Set[str], Set[str], bool]:
    """``SRCS>DSTS`` (asymmetric: srcs stop reaching dsts) or
    ``SRCS<>DSTS`` (symmetric). Groups are ``|``-separated ids; ``*``
    means every known replica. Returns (srcs, dsts, symmetric)."""
    sym = "<>" in spec
    sep = "<>" if sym else ">"
    if sep not in spec:
        raise ValueError(
            f"partition spec {spec!r} wants 'SRCS>DSTS' or 'SRCS<>DSTS'"
        )
    left, right = spec.split(sep, 1)

    def group(s: str) -> Set[str]:
        if s == "*":
            return set(ids)
        members = {m for m in s.split("|") if m}
        if not members:
            raise ValueError(f"empty group in partition spec {spec!r}")
        return members

    return group(left), group(right), sym


class ShapedTransport:
    """Wraps any Transport; outbound frames pay the configured link
    shape (latency + jitter + bandwidth serialization) and may be
    dropped (loss, partitions). Inbound is passthrough — shaping both
    directions of a pair means wrapping both endpoints, which is what
    the injector and committee helpers do.

    Deterministic per node: the jitter/loss RNG is seeded, so a seeded
    schedule over a seeded committee replays the identical delivery
    pattern. Per-link FIFO order is preserved (frames queue behind the
    link's bandwidth serialization point, like a real socket)."""

    def __init__(
        self,
        inner,
        shapes: Optional[Dict[str, LinkShape]] = None,
        default: Optional[LinkShape] = None,
        seed: int = 0,
        profile: str = "",
    ) -> None:
        self._inner = inner
        self.node_id = inner.node_id
        self.shapes: Dict[str, LinkShape] = dict(shapes or {})
        self.default = default or LinkShape()
        self.profile = profile
        self.cut_to: Set[str] = set()  # outbound-blocked destinations
        self.rng = random.Random(seed)
        # the inner transport's wire ledger (transport.base.wire_of):
        # shaped losses are accounted THERE, under named buckets, so a
        # shaped node reports one conservation-complete accounting —
        # lost bytes never vanish (ISSUE 12). Resolved lazily: a bare
        # wrapper over a transport without accounting stays a no-op.
        self._wire_acct = base_transport.wire_of(inner)
        self._link_free: Dict[str, float] = {}  # bw serialization point
        self._link_last: Dict[str, float] = {}  # FIFO clamp: last delivery
        self._bg: Set[asyncio.Task] = set()
        self.shaping_metrics: Dict[str, int] = {
            "shaped_sent": 0,
            "shaped_delayed": 0,
            "shaped_lost": 0,
            "partition_dropped": 0,
        }

    # -- shaping controls --------------------------------------------------

    @classmethod
    def wrap_profile(
        cls, inner, profile: str, ids: Sequence[str], seed: int = 0
    ) -> "ShapedTransport":
        """Wrap ``inner`` with the named WAN profile's outbound links
        for this node (node.py --wan-profile path)."""
        maps = WAN_PROFILES[profile](ids, seed)
        return cls(
            inner,
            shapes=maps.get(inner.node_id, {}),
            seed=seed ^ _node_seed(inner.node_id),
            profile=profile,
        )

    def apply_profile(self, profile: str, ids: Sequence[str],
                      seed: int = 0) -> None:
        maps = WAN_PROFILES[profile](ids, seed)
        self.shapes = dict(maps.get(self.node_id, {}))
        self.profile = profile

    def clear_shaping(self) -> None:
        self.shapes = {}
        self.default = LinkShape()
        self.profile = ""

    def partition(self, dests) -> None:
        self.cut_to |= {d for d in dests if d != self.node_id}

    def heal(self, dests=None) -> None:
        if dests is None:
            self.cut_to.clear()
        else:
            self.cut_to -= set(dests)

    # -- telemetry ---------------------------------------------------------

    @property
    def metrics(self) -> Dict[str, int]:
        # one merged counter surface so NodeTelemetry's transport block
        # shows wire AND shaping counters for a shaped node
        merged = dict(getattr(self._inner, "metrics", {}) or {})
        merged.update(self.shaping_metrics)
        return merged

    def shaping_snapshot(self) -> Dict[str, object]:
        """The NET state pbft_top renders: active profile, open cuts,
        shaped-link count, loss/partition drop counters."""
        return {
            "profile": self.profile,
            "cut_to": sorted(self.cut_to),
            "shaped_links": len(self.shapes),
            **self.shaping_metrics,
        }

    # -- Transport interface ----------------------------------------------

    def _shape_for(self, dest: str) -> LinkShape:
        return self.shapes.get(dest, self.default)

    async def send(self, dest: str, raw: bytes) -> None:
        if dest in self.cut_to:
            self.shaping_metrics["partition_dropped"] += 1
            if self._wire_acct is not None:
                self._wire_acct.account_lost("partition_dropped", raw)
            return
        sh = self._shape_for(dest)
        if sh.loss and self.rng.random() < sh.loss:
            self.shaping_metrics["shaped_lost"] += 1
            if self._wire_acct is not None:
                self._wire_acct.account_lost("shaped_lost", raw)
            return
        delay = sh.delay_s
        if sh.jitter_s:
            delay += sh.jitter_s * self.rng.random()
        loop = asyncio.get_running_loop()
        # pbftlint: disable=PBL007 -- feeds call_at on the SAME loop: this IS the virtualized timebase, not a seam bypass
        now = loop.time()  # the clock call_at schedules against
        if sh.bw_bytes_per_s > 0:
            # serialize through the link: frames queue behind the byte
            # clock, preserving per-link FIFO under bandwidth pressure
            start = max(now, self._link_free.get(dest, 0.0))
            tx = len(raw) / sh.bw_bytes_per_s
            self._link_free[dest] = start + tx
            delay += (start - now) + tx
        target = now + delay
        last = self._link_last.get(dest, 0.0)
        if target <= last:
            # jitter must not reorder the link: a TCP byte stream never
            # delivers frame B before an earlier frame A. STRICTLY after
            # the link's previous delivery — equal timer deadlines pop
            # in heap order, not send order
            target = last + 1e-6
        self._link_last[dest] = target
        self.shaping_metrics["shaped_sent"] += 1
        if target - now <= 0:
            await self._inner.send(dest, raw)
            return
        self.shaping_metrics["shaped_delayed"] += 1
        loop.call_at(target, self._deliver_later, dest, raw)

    def _deliver_later(self, dest: str, raw: bytes) -> None:
        task = asyncio.get_running_loop().create_task(
            self._inner.send(dest, raw)
        )
        self._bg.add(task)

        def _done(t: asyncio.Task) -> None:
            self._bg.discard(t)
            if not t.cancelled():
                t.exception()  # consume: a late send into a closed
                # transport must not log 'exception never retrieved'

        task.add_done_callback(_done)

    async def broadcast(self, raw: bytes, dests) -> None:
        # per-dest send so each link's shape applies independently
        for dest in dests:
            if dest != self.node_id:
                await self.send(dest, raw)

    async def recv(self) -> bytes:
        return await self._inner.recv()

    def recv_nowait(self):
        return self._inner.recv_nowait()

    def __getattr__(self, name):
        return getattr(self._inner, name)


def find_shaped(transport) -> Optional[ShapedTransport]:
    """Walk a wrapper chain (byzantine wrappers may stack over shaping)
    to the ShapedTransport, if any."""
    seen = 0
    t = transport
    while t is not None and seen < 8:
        if isinstance(t, ShapedTransport):
            return t
        t = getattr(t, "_inner", None)
        seen += 1
    return None


# ---------------------------------------------------------------------------
# verifier-seam wrappers (armed/disarmed by the injector)
# ---------------------------------------------------------------------------


class SlowVerifier:
    """Wraps any Verifier; while armed, every batch pays an extra delay
    (models a host CPU contended away from the verify thread). The delay
    runs in whatever thread the inner verify runs in, so the event loop
    is never held. Attribute access (including .name) passes through."""

    def __init__(self, inner) -> None:
        self._inner = inner
        self._delay = 0.0

    def arm(self, delay: float) -> None:
        self._delay = max(0.0, delay)

    def disarm(self) -> None:
        self._delay = 0.0

    def verify_batch(self, items):
        if self._delay:
            time.sleep(self._delay)
        return self._inner.verify_batch(items)

    def __getattr__(self, name):
        return getattr(self._inner, name)


class StallableDevice:
    """Wraps a device verifier (the dispatch_batch protocol VerifyService
    consumes); while stalled, every finisher blocks until the stall
    expires or release() is called. Dispatch itself stays fast — the
    stall models a device that accepted work and went silent, the r5
    qc256 wedge shape the VerifyService watchdog must catch."""

    def __init__(self, inner) -> None:
        self._inner = inner
        self._resume = threading.Event()
        self._resume.set()
        self.stalls_injected = 0
        self.finishers_stalled = 0

    # -- fault controls ---------------------------------------------------

    def stall(self, duration: Optional[float] = None) -> None:
        """Stall finishers; auto-release after ``duration`` seconds
        (None = until release()). The timer is a daemon: a stall must
        never keep the process alive past its last real work."""
        self._resume.clear()
        self.stalls_injected += 1
        if duration is not None:
            t = threading.Timer(duration, self._resume.set)
            t.daemon = True
            t.start()

    def release(self) -> None:
        self._resume.set()

    @property
    def stalled(self) -> bool:
        return not self._resume.is_set()

    # -- Verifier/device protocol -----------------------------------------

    def dispatch_batch(self, items):
        inner_finish = self._inner.dispatch_batch(items)

        def finish():
            if not self._resume.is_set():
                self.finishers_stalled += 1
                self._resume.wait()
            return inner_finish()

        return finish

    def verify_batch(self, items):
        return self.dispatch_batch(items)()

    # counters must pass through BOTH ways: VerifyService's properties
    # read and WRITE device_calls/items/seconds on its device (bench
    # resets them at the timed-window start), and a plain __getattr__
    # would let the write shadow the inner counter forever
    @property
    def device_calls(self):
        return self._inner.device_calls

    @device_calls.setter
    def device_calls(self, v):
        self._inner.device_calls = v

    @property
    def device_items(self):
        return self._inner.device_items

    @device_items.setter
    def device_items(self, v):
        self._inner.device_items = v

    @property
    def device_seconds(self):
        return self._inner.device_seconds

    @device_seconds.setter
    def device_seconds(self, v):
        self._inner.device_seconds = v

    def __getattr__(self, name):
        return getattr(self._inner, name)


# ---------------------------------------------------------------------------
# byzantine transport wrappers (ISSUE 5: detection targets for the audit
# plane — valid signatures, lying content)
# ---------------------------------------------------------------------------


class ByzantineTransport:
    """Passthrough transport base for byzantine wrappers: subclasses
    override ``_mutate`` (per-frame rewrite) and/or ``broadcast``.
    ``injections`` counts frames actually forged, so a bench record can
    state how much byzantine traffic a run really carried."""

    def __init__(self, inner, signer: Signer) -> None:
        self._inner = inner
        self.signer = signer
        self.node_id = inner.node_id
        self.injections = 0

    def _mutate(self, raw: bytes) -> bytes:
        return raw

    async def send(self, dest, raw):
        await self._inner.send(dest, self._mutate(raw))

    async def broadcast(self, raw, dests):
        await self._inner.broadcast(self._mutate(raw), dests)

    async def recv(self):
        return await self._inner.recv()

    def recv_nowait(self):
        return self._inner.recv_nowait()

    def __getattr__(self, name):
        return getattr(self._inner, name)


class EquivocatingPrimary(ByzantineTransport):
    """Deterministic equivocator: every pre-prepare with a block is
    FORKED — the real block to one half of the committee, a
    validly-signed variant (reversed-and-truncated: the strongest fork
    admissible without forging CLIENT signatures) with a different
    digest to the other half. Disjoint recipient halves by construction,
    so no single honest node receives both messages — the case only the
    cross-node ledger join (tools/ledger_audit.py) or a later repair
    round trip can expose."""

    def _fork(self, pp: PrePrepare) -> bytes:
        block = list(reversed(pp.block))[: max(1, len(pp.block) - 1)]
        if block == pp.block:
            block = []  # single-request block: fork to the no-op block
        forked = PrePrepare(
            view=pp.view, seq=pp.seq,
            digest=PrePrepare.block_digest(block), block=block,
        )
        self.signer.sign_msg(forked)
        return forked.to_wire()

    async def broadcast(self, raw, dests):
        try:
            msg = Message.from_wire(raw)
        except ValueError:
            msg = None
        if isinstance(msg, PrePrepare) and msg.block:
            forked_raw = self._fork(msg)
            self.injections += 1
            others = [d for d in dests if d != self.node_id]
            for i, dest in enumerate(others):
                await self._inner.send(
                    dest, raw if i % 2 == 0 else forked_raw
                )
            return
        await self._inner.broadcast(raw, dests)


class ForkingCheckpointer(ByzantineTransport):
    """Deterministic checkpoint forker: every OUTBOUND own checkpoint's
    state digest is replaced (derived from the real one, so it is
    deterministic and stable across resends) and validly re-signed. The
    replica's local state stays honest — only the wire lies, which is
    exactly the shape the checkpoint-divergence invariant (audit I2)
    must catch: peers see a signed digest that disagrees with their
    own deterministic fold."""

    def _mutate(self, raw: bytes) -> bytes:
        try:
            msg = Message.from_wire(raw)
        except ValueError:
            return raw
        if isinstance(msg, Checkpoint) and msg.sender == self.node_id:
            msg.state_digest = sha256_hex(
                (msg.state_digest + ":forked").encode()
            )
            # the BLS share signed the HONEST digest; shipping it would
            # just poison aggregates — blank it (shape-invalid, so QC
            # checkpoint aggregation skips this vote cleanly)
            msg.bls_share = ""
            self.signer.sign_msg(msg)
            self.injections += 1
            return msg.to_wire()
        return raw


class StaleEpochVoter(ByzantineTransport):
    """A replica removed by a committed reconfiguration that refuses to
    leave: it keeps emitting consensus votes (prepare/commit/checkpoint)
    into the NEW epoch's committee. The frames are validly signed with
    its still-published key — the defense is the role gate (honest
    replicas admit consensus traffic only from the CURRENT epoch's
    replica set, replica._batch_items), and the detection surface is
    `dropped_precheck` climbing on every honest node while the ledgers
    stay clean. ``mark_stale()`` is called at the epoch boundary; until
    then the wrapper is a pure passthrough."""

    VOTE_KINDS = (b'"kind":"prepare"', b'"kind":"commit"',
                  b'"kind":"checkpoint"', b'"kind":"preprepare"')

    def __init__(self, inner, signer: Signer) -> None:
        super().__init__(inner, signer)
        self.stale = False
        self._arm_when = None  # optional predicate: stale once it's True

    def mark_stale(self) -> None:
        self.stale = True

    def arm_when(self, predicate) -> None:
        """Defer staleness to a condition — FaultInjector arms schedule-
        driven voters on the replica's removal from the committed
        membership, so votes sent while still a LEGITIMATE member are
        never counted as injections (they are ordinary honest traffic,
        not byzantine behavior)."""
        self._arm_when = predicate

    def _count(self, raw: bytes) -> None:
        if not self.stale and self._arm_when is not None and self._arm_when():
            self.stale = True
        if self.stale and any(k in raw for k in self.VOTE_KINDS):
            self.injections += 1

    async def send(self, dest, raw):
        self._count(raw)
        await self._inner.send(dest, raw)

    async def broadcast(self, raw, dests):
        self._count(raw)
        await self._inner.broadcast(raw, dests)


class SpecDivergencePrimary(ByzantineTransport):
    """Divergence-forcing byzantine primary for the speculative plane
    (ISSUE 15). In QC mode votes flow only to the primary and the
    primary distributes the aggregates — total control over who learns
    a slot prepared. For every PERIOD-th slot this wrapper:

    - delivers the slot's PREPARE QC to a single victim (the highest-id
      backup) instead of broadcasting it — only the victim reaches
      PREPARED, speculates the block, and answers clients with the
      speculative mark (never enough marks for a 2f+1 spec quorum, so
      no client can accept the answer);
    - withholds the slot's COMMIT QC entirely, so the slot never
      commits in this view.

    The fork is revealed only at the view change the stalled slot
    forces: the victim's VIEW-CHANGE carries the prepared proof, and
    whether the NEW-VIEW's 2f+1-certificate happens to include it
    decides the slot's fate — included, the speculation confirms;
    excluded, the O-set no-op-fills the seq and the victim must roll
    its speculated suffix back to the committed anchor. Both outcomes
    are correct; the rollback interleaving is what the sim search
    steers toward (tests/sim_repros/spec_rollback_viewchange.json).
    Everything is validly signed — detection surfaces are the victim's
    ``spec_rolled_back`` metric and a clean audit bill (speculation is
    local; no safety invariant may trip). Non-QC frames pass through
    untouched, so the wrapper is inert on broadcast-vote committees."""

    PERIOD = 3  # every 3rd seq is a victim slot

    def __init__(self, inner, signer: Signer) -> None:
        super().__init__(inner, signer)
        self._victim_of: Dict[int, str] = {}  # seq -> chosen victim

    def _victim_qc(self, raw: bytes) -> Optional[QuorumCert]:
        if b'"kind":"qc"' not in raw and b'"kind": "qc"' not in raw:
            return None
        try:
            msg = Message.from_wire(raw)
        except ValueError:
            return None
        if (
            isinstance(msg, QuorumCert)
            and msg.seq % self.PERIOD == 0
            and msg.phase in ("prepare", "commit")
        ):
            return msg
        return None

    def _strip_vc(self, raw: bytes) -> bytes:
        """Lie by omission in our own VIEW-CHANGE: drop the prepared
        proofs for victim slots and re-sign. Without this the wrapper's
        fork self-reveals — the byzantine primary's honest certificate
        would carry the victim slot's prepare QC into the O-set and the
        speculation would simply confirm. Omission is admissible
        byzantine behavior (a VC is a CLAIM about what its sender
        prepared), and it is exactly what makes the fork surface only
        at the view change: with the victim's own VIEW-CHANGE also
        absent (cut, or outside the 2f+1 certificate), the O-set
        no-op-fills the slot and the victim must roll back."""
        if b'"kind":"viewchange"' not in raw and (
            b'"kind": "viewchange"' not in raw
        ):
            return raw
        try:
            msg = Message.from_wire(raw)
        except ValueError:
            return raw
        if type(msg).KIND != "viewchange" or msg.sender != self.node_id:
            return raw
        kept = []
        for proof in msg.prepared_proofs:
            pp = (proof or {}).get("pre_prepare") or {}
            seq = pp.get("seq")
            if isinstance(seq, int) and seq % self.PERIOD == 0:
                continue
            kept.append(proof)
        if len(kept) == len(msg.prepared_proofs):
            return raw
        msg.prepared_proofs = kept
        self.signer.sign_msg(msg)
        self.injections += 1
        return msg.to_wire()

    async def send(self, dest, raw):
        # the repair plane (SlotFetch answers) re-serves stored QCs via
        # point-to-point sends: a consistent withholder must filter both
        # paths or one probe round trip un-forks the slot
        msg = self._victim_qc(raw)
        if msg is not None:
            if msg.phase == "commit" or dest != self._victim_of.get(msg.seq):
                self.injections += 1
                return
        await self._inner.send(dest, self._strip_vc(raw))

    async def broadcast(self, raw, dests):
        msg = self._victim_qc(raw)
        if msg is not None:
            self.injections += 1
            if msg.phase == "commit":
                return  # withheld: the slot cannot commit in-view
            victims = sorted(d for d in dests if d != self.node_id)
            if victims:
                self._victim_of[msg.seq] = victims[-1]
                await self._inner.send(victims[-1], raw)
            return
        await self._inner.broadcast(self._strip_vc(raw), dests)


class ForgedSnapshotServer(ByzantineTransport):
    """Feeds a joiner a forged checkpoint: every outbound state-transfer
    payload (chunked StateChunkReply and legacy StateResponse) has its
    snapshot bytes corrupted deterministically. The signature over the
    LIE is valid — the joiner's only defense is the certified checkpoint
    digest, which the assembled snapshot must hash to
    (consensus/statesync.py); a mismatch discards the transfer and
    re-fetches from another peer."""

    def _mutate(self, raw: bytes) -> bytes:
        if (b'"kind":"statechunkreply"' not in raw
                and b'"kind":"stateresponse"' not in raw):
            return raw
        try:
            msg = Message.from_wire(raw)
        except ValueError:
            return raw
        kind = getattr(type(msg), "KIND", "")
        if kind == "statechunkreply" and msg.sender == self.node_id:
            msg.data = msg.data[::-1] if msg.data else "00"
        elif kind == "stateresponse" and msg.sender == self.node_id:
            msg.snapshot = msg.snapshot[::-1] if msg.snapshot else "{}"
        else:
            return raw
        self.signer.sign_msg(msg)
        self.injections += 1
        return msg.to_wire()


# ---------------------------------------------------------------------------
# the injector
# ---------------------------------------------------------------------------


@dataclass
class FaultInjector:
    """Applies a FaultSchedule to a LocalCommittee while it runs.

    ``service`` (a VerifyService over a StallableDevice) enables
    stall_device events; ``slow`` (a SlowVerifier the replicas share)
    enables slow_verifier events. Events whose seam is absent are counted
    as skipped, not errors — a CPU-only run simply has no device to
    stall. Windows restore their previous network knobs on expiry and at
    stop(), so a schedule can never leak degraded settings into the
    drain/teardown phase."""

    committee: object
    schedule: FaultSchedule
    service: object = None  # VerifyService whose .device is stallable
    slow: Optional[SlowVerifier] = None
    applied: List[dict] = field(default_factory=list)
    skipped: int = 0
    crashes_applied: int = 0
    # byzantine wrappers armed by equivocate/fork_checkpoint events (a
    # byzantine replica does not heal: wraps persist to run end); their
    # per-wrapper ``injections`` counters feed the bench record
    byzantine: List = field(default_factory=list)
    _restores: List = field(default_factory=list)
    # per-knob active-window refcounts + the pre-schedule baselines:
    # overlapping windows must restore the BASELINE when the last one
    # closes, not each other's mid-schedule snapshots (a stale snapshot
    # would leak degraded settings into the drain phase)
    _window_depth: Dict[str, int] = field(default_factory=dict)
    _baselines: Dict[str, object] = field(default_factory=dict)

    @property
    def applied_count(self) -> int:
        """Events that actually took effect (skipped ones excluded)."""
        return sum(1 for rec in self.applied if rec.get("applied"))

    @property
    def byzantine_injections(self) -> int:
        """Frames the armed byzantine wrappers actually forged."""
        return sum(w.injections for w in self.byzantine)

    async def run(self, stop_at: float) -> None:
        """Fire events at their offsets until done or ``stop_at`` (a
        ``clock.now()`` deadline — virtual under simulation, so a
        schedule replays at identical VIRTUAL offsets regardless of how
        fast the host runs). Call alongside the load pumps."""
        t0 = clock.now()
        for ev in self.schedule.events:
            fire = t0 + ev.t
            while True:
                now = clock.now()
                if now >= fire or now >= stop_at:
                    break
                await clock.sleep(min(0.05, fire - now))
            if clock.now() >= stop_at:
                break
            self._apply(ev)
        # hold the task open until every window has restored (restores
        # are call_later-style sleeps tracked in _restores)
        for task in list(self._restores):
            try:
                await task
            except asyncio.CancelledError:
                pass

    def stop(self) -> None:
        for task in self._restores:
            task.cancel()

    # -- event application -------------------------------------------------

    def _apply(self, ev: FaultEvent) -> None:
        rec = ev.to_dict()
        ok = True
        if ev.kind == "crash":
            ok = self._crash(ev)
        elif ev.kind in ("drop_window", "delay_window"):
            ok = self._net_window(ev)
        elif ev.kind == "slow_verifier":
            ok = self._slow_window(ev)
        elif ev.kind == "stall_device":
            ok = self._stall(ev)
        elif ev.kind in ("equivocate", "fork_checkpoint", "stale_epoch",
                         "forge_statesync", "spec_divergence"):
            ok = self._byzantine(ev)
        elif ev.kind == "partition":
            ok = self._partition(ev)
        elif ev.kind == "heal":
            ok = self._heal_all()
        elif ev.kind == "shape":
            ok = self._shape(ev)
        else:
            ok = False
        rec["applied"] = ok
        self.applied.append(rec)
        if not ok:
            self.skipped += 1

    def _live_primary(self):
        live = [r for r in self.committee.replicas if r._running]
        if not live:
            return None
        view = max(r.view for r in live)
        target = self.committee.cfg.primary(view)
        r = next((x for x in live if x.id == target), None)
        return r

    def _crash(self, ev: FaultEvent) -> bool:
        if ev.target:
            r = next(
                (x for x in self.committee.replicas
                 if x.id == ev.target and x._running),
                None,
            )
        else:
            r = self._live_primary()
        if r is None:
            return False
        # safety floor: never crash below quorum — a schedule is a
        # resilience test, not a liveness-impossibility proof
        live = sum(1 for x in self.committee.replicas if x._running)
        if live - 1 < self.committee.cfg.quorum:
            return False
        r.kill()
        self.crashes_applied += 1
        return True

    def _byzantine(self, ev: FaultEvent) -> bool:
        """Arm a byzantine transport wrapper on the target replica (the
        named one, or the live primary — the equivocation case only
        bites at a primary anyway). Needs the committee's key store to
        produce VALID signatures over the lying content; idempotent per
        (replica, wrapper kind)."""
        if ev.target:
            r = next(
                (x for x in self.committee.replicas
                 if x.id == ev.target and x._running),
                None,
            )
        else:
            r = self._live_primary()
        if r is None:
            return False
        keys = getattr(self.committee, "keys", None)
        kp = keys.get(r.id) if keys else None
        if kp is None:
            return False  # no key material: cannot sign the forks
        cls = {
            "equivocate": EquivocatingPrimary,
            "fork_checkpoint": ForkingCheckpointer,
            "stale_epoch": StaleEpochVoter,
            "forge_statesync": ForgedSnapshotServer,
            "spec_divergence": SpecDivergencePrimary,
        }[ev.kind]
        if isinstance(r.transport, cls):
            return False  # already byzantine this way
        wrapper = cls(r.transport, Signer(r.id, kp.seed))
        if ev.kind == "stale_epoch":
            # The honest retiree self-gags at _send_vote, so a voter
            # armed on `retired` alone never sees a vote frame (vacuous:
            # injections stays 0 and the role gate goes unexercised).
            # The byzantine replica REFUSES its retirement — it keeps
            # voting — and staleness is judged against the ground truth
            # of the committed membership, not the (now unset) gag flag.
            # Until the removal actually commits its votes are ordinary
            # member traffic and must not count as injections.
            r.refuse_retirement = True
            if r.id not in r.cfg.replica_ids:
                r.retired = False  # already removed: un-gag now
                wrapper.mark_stale()
            else:
                wrapper.arm_when(
                    lambda rep=r: rep.id not in rep.cfg.replica_ids
                )
        r.transport = wrapper
        self.byzantine.append(wrapper)
        return True

    # -- WAN shaping / partitions (ShapedTransport seam) -------------------

    def _shaped(self, replica) -> ShapedTransport:
        """The replica's ShapedTransport, wrapping its current transport
        chain on first use (shaping composes OUTSIDE byzantine wrappers,
        so forged frames ride the same degraded links)."""
        shaped = find_shaped(replica.transport)
        if shaped is None:
            shaped = ShapedTransport(
                replica.transport,
                seed=self.schedule.seed ^ _node_seed(replica.id),
            )
            replica.transport = shaped
        return shaped

    def _replica_by_id(self, rid: str):
        return next(
            (x for x in self.committee.replicas if x.id == rid), None
        )

    def _partition(self, ev: FaultEvent) -> bool:
        ids = list(self.committee.cfg.replica_ids)
        try:
            srcs, dsts, sym = parse_partition_spec(ev.spec, ids)
        except ValueError:
            return False
        cuts: List[Tuple[ShapedTransport, Set[str]]] = []

        def cut(from_ids: Set[str], to_ids: Set[str]) -> None:
            for rid in from_ids:
                r = self._replica_by_id(rid)
                if r is None:
                    continue
                shaped = self._shaped(r)
                added = (to_ids - {rid}) - shaped.cut_to
                shaped.partition(to_ids)
                if added:
                    cuts.append((shaped, added))

        cut(srcs, dsts)
        if sym:
            cut(dsts, srcs)
        if not cuts:
            return False
        if ev.duration > 0:
            def restore():
                # remove exactly the pairs THIS window opened; an
                # overlapping window that cut the same pair re-cuts on
                # its own fire, so the earliest close wins (documented
                # in docs/SCENARIOS.md — prefer explicit heal= when
                # composing overlapping partitions)
                for shaped, added in cuts:
                    shaped.heal(added)

            self._after(ev.duration, restore)
        return True

    def _heal_all(self) -> bool:
        for r in self.committee.replicas:
            shaped = find_shaped(r.transport)
            if shaped is not None:
                shaped.heal()
        net = getattr(self.committee, "net", None)
        faults = getattr(net, "faults", None)
        if faults is not None and hasattr(faults, "heal"):
            faults.heal()  # FaultPlan-based cuts heal too
        return True

    def _shape(self, ev: FaultEvent) -> bool:
        if ev.spec not in WAN_PROFILES:
            return False
        ids = list(self.committee.cfg.replica_ids)
        shaped_all: List[ShapedTransport] = []
        for r in self.committee.replicas:
            shaped = self._shaped(r)
            shaped.apply_profile(ev.spec, ids, seed=self.schedule.seed)
            shaped_all.append(shaped)
        if ev.duration > 0:
            def restore():
                for shaped in shaped_all:
                    shaped.clear_shaping()

            self._after(ev.duration, restore)
        return True

    def _net_window(self, ev: FaultEvent) -> bool:
        faults = self.committee.net.faults
        kind = ev.kind
        if self._window_depth.get(kind, 0) == 0:
            # first window of this kind: capture the PRE-SCHEDULE value
            self._baselines[kind] = (
                faults.drop_rate if kind == "drop_window"
                else faults.delay_range
            )
        self._window_depth[kind] = self._window_depth.get(kind, 0) + 1
        if kind == "drop_window":
            faults.drop_rate = ev.magnitude
        else:
            faults.delay_range = (0.0, ev.magnitude)

        def restore():
            # refcounted: with overlapping windows, only the LAST close
            # restores — and always to the baseline, never to another
            # window's mid-schedule snapshot
            self._window_depth[kind] -= 1
            if self._window_depth[kind] == 0:
                if kind == "drop_window":
                    faults.drop_rate = self._baselines[kind]
                else:
                    faults.delay_range = self._baselines[kind]

        self._after(ev.duration, restore)
        return True

    def _slow_window(self, ev: FaultEvent) -> bool:
        if self.slow is None:
            return False
        kind = ev.kind
        self._window_depth[kind] = self._window_depth.get(kind, 0) + 1
        self.slow.arm(ev.magnitude)

        def restore():
            self._window_depth[kind] -= 1
            if self._window_depth[kind] == 0:
                self.slow.disarm()

        self._after(ev.duration, restore)
        return True

    def _stall(self, ev: FaultEvent) -> bool:
        dev = getattr(self.service, "device", None)
        if dev is None or not hasattr(dev, "stall"):
            return False
        # duration managed as a refcounted injector window (not the
        # device's own timer): overlapping stalls release only when the
        # LAST closes, run() awaits the release, and stop() releases
        # EARLY — a stall landing late in the schedule must not leak
        # into the drain/teardown phase
        kind = ev.kind
        if self._window_depth.get(kind, 0) == 0:
            dev.stall(duration=None)
        self._window_depth[kind] = self._window_depth.get(kind, 0) + 1

        def restore():
            self._window_depth[kind] -= 1
            if self._window_depth[kind] == 0:
                dev.release()

        self._after(ev.duration, restore)
        return True

    def _after(self, delay: float, fn) -> None:
        async def later():
            await clock.sleep(delay)

        task = asyncio.get_running_loop().create_task(later())
        # done-callback, NOT a finally inside the coroutine: a task
        # cancelled by stop() before its first event-loop step never
        # enters its own try/finally (CancelledError lands at function
        # entry), but done callbacks fire on completion AND cancellation
        # unconditionally — the restore can never be skipped
        task.add_done_callback(lambda _t: fn())
        self._restores.append(task)


# Regenerate the kind documentation from the registry (ISSUE 7
# satellite: the docstring and parse errors once named only the
# pre-PR-5 kinds — now they cannot drift, tests assert the sync).
_TABLE = "\n\nFault kinds (generated from KIND_REGISTRY):\n\n" + kind_table() + "\n"
__doc__ = (__doc__ or "") + _TABLE
FaultSchedule.__doc__ = (FaultSchedule.__doc__ or "") + _TABLE
