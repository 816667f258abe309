"""WAN survival plane (ISSUE 7): the three pillars and their injectors.

(a) an n=7 TCP committee under `wan3dc` link shaping commits through an
    asymmetric partition that opens and HEALS MID-VIEW-CHANGE;
(b) a killed replica rejoins via chunked checkpoint state-transfer with
    the transferred volume bounded (asserted) by snapshot size + one
    watermark window of log suffix, and commits after rejoin;
(c) a replica is added then removed through the committed config slot,
    with the audit plane clean across both epoch boundaries and the
    verify seam's jit shapes untouched by the key registration.

Plus the new byzantine surfaces (ForgedSnapshotServer, StaleEpochVoter),
the tcp frames_dropped/requeue accounting, the client's stale-address-
book re-resolution, the faults kind-registry doc sync, and pbft_top's
NET column.
"""

import asyncio
import json
import os
import sys

import pytest

from simple_pbft_tpu.app import KVStore
from simple_pbft_tpu import clock as pbft_clock
from simple_pbft_tpu.committee import LocalCommittee
from simple_pbft_tpu.client import Client
from simple_pbft_tpu.config import KeyPair, make_test_committee
from simple_pbft_tpu.consensus.replica import Replica
from simple_pbft_tpu.crypto.signer import Signer
from simple_pbft_tpu.faults import (
    KIND_REGISTRY,
    FaultEvent,
    FaultInjector,
    FaultSchedule,
    ForgedSnapshotServer,
    LinkShape,
    ShapedTransport,
    StaleEpochVoter,
    find_shaped,
    kind_table,
)
from simple_pbft_tpu.transport.tcp import TcpTransport

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
import ledger_audit  # noqa: E402  (tools/ is not a package)
import pbft_top  # noqa: E402


def run(coro, timeout=180):
    return asyncio.run(asyncio.wait_for(coro, timeout))


def _joiner_keys(rid: str) -> KeyPair:
    # same derivation as make_test_committee: keys are a function of the id
    return KeyPair.generate((rid.encode() * 32)[:32])


async def _drain_stop(replicas, clients, transports=()):
    await asyncio.gather(
        *(r.stop() for r in replicas), return_exceptions=True
    )
    await asyncio.gather(
        *(c.stop() for c in clients), return_exceptions=True
    )
    for t in transports:
        try:
            await t.stop()
        except Exception:
            pass


# ---------------------------------------------------------------------------
# pillar (a): wan3dc-shaped TCP committee, partition heals mid-view-change
# ---------------------------------------------------------------------------


class TestWanPartitionHeal:
    def test_n7_tcp_wan3dc_partition_opens_and_heals_mid_view_change(self):
        view_timeout = 0.8

        async def scenario():
            n = 7
            cfg, keys = make_test_committee(
                n=n, clients=1, view_timeout=view_timeout, checkpoint_interval=8
            )
            inner = {}
            for nid in list(cfg.replica_ids) + ["c0"]:
                t = TcpTransport(nid, ("127.0.0.1", 0), peers={})
                await t.start()
                inner[nid] = t
            addrs = {
                nid: ("127.0.0.1", t.bound_port) for nid, t in inner.items()
            }
            for nid, t in inner.items():
                t.peers.update(
                    {k: v for k, v in addrs.items() if k != nid}
                )
            replicas = []
            for rid in cfg.replica_ids:
                shaped = ShapedTransport.wrap_profile(
                    inner[rid], "wan3dc", list(cfg.replica_ids)
                )
                replicas.append(
                    Replica(
                        node_id=rid, cfg=cfg, seed=keys[rid].seed,
                        transport=shaped, app=KVStore(),
                    )
                )
            client = Client(
                "c0", cfg, keys["c0"].seed, inner["c0"], request_timeout=1.5
            )
            try:
                for r in replicas:
                    r.start()
                client.start()
                for i in range(8):
                    assert await client.submit(f"put a{i} {i}", retries=8) == "ok"

                # open an ASYMMETRIC partition around the live primary:
                # its outbound links die (proposals vanish), inbound stays
                # — the shape only a per-link direction cut can produce
                view0 = max(r.view for r in replicas)
                primary = cfg.primary(view0)
                prim = next(r for r in replicas if r.id == primary)
                find_shaped(prim.transport).partition(
                    [r for r in cfg.replica_ids if r != primary]
                )

                # load pump in the background keeps failover timers armed
                pump_ok = 0

                async def pump():
                    nonlocal pump_ok
                    for i in range(24):
                        try:
                            res = await client.submit(
                                f"put b{i} {i}", retries=12
                            )
                            if res == "ok":
                                pump_ok += 1
                        except Exception:
                            pass

                pump_task = asyncio.create_task(pump())

                # heal EXACTLY mid-view-change: wait for any survivor to
                # enter the view change the dead primary caused, then
                # reopen the links while the change is still in flight
                healed_mid_vc = False
                for _ in range(400):
                    if any(
                        r.vc.in_view_change
                        for r in replicas if r.id != primary
                    ):
                        find_shaped(prim.transport).heal()
                        healed_mid_vc = True
                        break
                    await asyncio.sleep(0.05)
                assert healed_mid_vc, "no view change within the window"

                await pump_task
                # the committee moved views AND kept committing through it
                assert pump_ok == 24, f"only {pump_ok}/24 committed"
                assert max(r.view for r in replicas) > view0
                # post-heal quiesce: every replica converges (the healed
                # ex-primary catches up too, via probes or state transfer)
                for _ in range(200):
                    execs = {r.executed_seq for r in replicas}
                    if len(execs) == 1:
                        break
                    await asyncio.sleep(0.05)
                shaped0 = find_shaped(replicas[0].transport)
                snap = shaped0.shaping_snapshot()
                assert snap["profile"] == "wan3dc"
                assert snap["shaped_links"] == n - 1
            finally:
                await _drain_stop(replicas, [client], inner.values())

        # A deadline of this test's own. Where the heal lands after every
        # survivor has entered the view change, the replicas skip to
        # different target views, views 1-4 never gather 2f+1 and the
        # committee regroups only at view 5, some 93 s in at view_timeout
        # 0.8 (one run in five alone, every run beside three others:
        # ROADMAP S8); the pump then pays a client timeout a
        # request, 24 x 1.5 s. What is asserted is THAT the committee
        # regroups and commits all 24, not how soon, so the deadline is
        # the failover ladder walked to its 60 s cap (doubling, +20%
        # jitter: 194 s at 0.8) plus the pump and the two settle loops.
        ladder = 1.2 * sum(min(view_timeout * 2**k, 60.0) for k in range(8))
        run(scenario(), timeout=ladder + 24 * 2 * 1.5 + 30)


# ---------------------------------------------------------------------------
# pillar (b): rejoin via chunked state transfer, bounded volume
# ---------------------------------------------------------------------------


class TestStatesyncRejoin:
    def test_killed_replica_rejoins_chunked_with_bounded_volume(self):
        async def scenario():
            com = LocalCommittee.build(
                n=4, clients=1, checkpoint_interval=4, view_timeout=1.0
            )
            com.start()
            c = com.clients[0]
            victim = com.replica("r3")
            try:
                for i in range(6):
                    await c.submit(f"put k{i} {i}", retries=5)
                victim.kill()
                # the committee moves on past several checkpoints; the
                # victim's unexecuted suffix is GC'd under the watermark
                for i in range(14):
                    await c.submit(f"put m{i} {i}", retries=5)
                frontier = max(r.executed_seq for r in com.replicas)

                fresh = Replica(
                    node_id="r3", cfg=com.cfg, seed=com.keys["r3"].seed,
                    transport=com.net.endpoint("r3"), app=KVStore(),
                )
                com.replicas[com.replicas.index(victim)] = fresh
                fresh.start()
                # background traffic produces the checkpoint broadcasts
                # the cold-started replica learns the gap from
                for i in range(10):
                    await c.submit(f"put s{i} {i}", retries=5)
                for _ in range(300):
                    if fresh.executed_seq >= frontier:
                        break
                    await asyncio.sleep(0.05)
                assert fresh.executed_seq >= frontier, (
                    fresh.executed_seq, frontier, dict(fresh.metrics),
                )

                # it caught up by TRANSFER, not replay
                m = fresh.metrics
                assert m["state_syncs"] >= 1
                assert m["statesync_chunks"] >= 1
                sync_seq = m["stable_checkpoint"]
                assert sync_seq > 0

                # volume bound (asserted, not eyeballed): chunk payload
                # received == the installed snapshots' bytes (no forgery
                # -> no re-fetch), and the replayed log suffix above the
                # snapshot is within one watermark window by construction
                snap_bytes = sum(
                    len(s) for s in fresh.snapshots.values()
                )
                assert 0 < m["statesync_bytes"] <= max(
                    snap_bytes,
                    m["statesync_transfers"] * snap_bytes,
                ), (m["statesync_bytes"], snap_bytes)
                assert (
                    fresh.executed_seq - sync_seq
                    <= com.cfg.watermark_window
                )

                # commits WITHIN one checkpoint interval of rejoin: the
                # first post-install execution lands at sync_seq + 1 and
                # the replica participates in the next interval's blocks
                r = await c.submit("put after-rejoin 1", retries=5)
                assert r == "ok"
                assert fresh.app.data.get("k0") == "0"  # transferred state
                assert fresh.app.data.get("s0") == "0"  # suffix state
            finally:
                await com.stop()

        run(scenario())

    def test_forged_snapshot_server_detected_and_survived(self):
        async def scenario():
            com = LocalCommittee.build(
                n=4, clients=1, checkpoint_interval=4, view_timeout=1.0
            )
            com.start()
            c = com.clients[0]
            victim = com.replica("r3")
            try:
                for i in range(6):
                    await c.submit(f"put k{i} {i}", retries=5)
                victim.kill()
                for i in range(10):
                    await c.submit(f"put m{i} {i}", retries=5)

                # EVERY serving peer forges its chunks: the joiner's only
                # defense is the certified digest
                wrapped = []
                for rid in ("r0", "r1", "r2"):
                    r = com.replica(rid)
                    w = ForgedSnapshotServer(
                        r.transport, Signer(rid, com.keys[rid].seed)
                    )
                    r.transport = w
                    wrapped.append((r, w))

                fresh = Replica(
                    node_id="r3", cfg=com.cfg, seed=com.keys["r3"].seed,
                    transport=com.net.endpoint("r3"), app=KVStore(),
                )
                com.replicas[com.replicas.index(victim)] = fresh
                fresh.start()
                for i in range(6):
                    await c.submit(f"put s{i} {i}", retries=5)
                # the forged transfer MUST be detected (digest mismatch)
                for _ in range(200):
                    if fresh.metrics["statesync_forged"] >= 1:
                        break
                    await asyncio.sleep(0.05)
                assert fresh.metrics["statesync_forged"] >= 1
                assert fresh.metrics["statesync_restarts"] >= 1
                assert sum(w.injections for _, w in wrapped) >= 1

                # heal the liars; the joiner re-fetches and installs the
                # REAL state (the restart path, not a wedge)
                for r, w in wrapped:
                    r.transport = w._inner
                for i in range(8):
                    await c.submit(f"put t{i} {i}", retries=5)
                frontier = max(
                    r.executed_seq for r in com.replicas if r is not fresh
                )
                for _ in range(300):
                    if fresh.executed_seq >= frontier:
                        break
                    await asyncio.sleep(0.05)
                assert fresh.executed_seq >= frontier, dict(fresh.metrics)
                assert fresh.app.data.get("k0") == "0"
            finally:
                await com.stop()

        run(scenario())


# ---------------------------------------------------------------------------
# pillar (c): live membership reconfiguration through the committed slot
# ---------------------------------------------------------------------------


class TestReconfiguration:
    def test_add_then_remove_epoch_cycle_with_clean_audit(self, tmp_path):
        async def scenario():
            com = LocalCommittee.build(
                n=4, clients=1, checkpoint_interval=4, view_timeout=1.0
            )
            auditors = com.attach_auditors(log_dir=str(tmp_path))
            com.start()
            c = com.clients[0]
            joiner = None
            try:
                for i in range(6):
                    await c.submit(f"put k{i} {i}", retries=5)

                # ADD r4 through the committed config slot
                kp = _joiner_keys("r4")
                res = await c.submit(
                    "__reconfig__ "
                    + json.dumps({"add": {"r4": {"pub": kp.pub.hex()}}}),
                    retries=5,
                )
                assert res.startswith("reconfig-staged:epoch=1"), res
                # activation at the next checkpoint boundary
                for i in range(8):
                    await c.submit(f"put m{i} {i}", retries=5)
                assert all(r.cfg.epoch == 1 for r in com.replicas)
                assert all(
                    "r4" in r.cfg.replica_ids for r in com.replicas
                )
                # the client re-resolved the committee from reply epochs
                for _ in range(100):
                    if c.epoch == 1:
                        break
                    await asyncio.sleep(0.05)
                assert c.epoch == 1
                assert c.metrics["config_refreshes"] >= 1
                assert "r4" in c.cfg.replica_ids

                # the joiner cold-starts with the new config and
                # bootstraps via chunked state transfer
                from simple_pbft_tpu.audit import SafetyAuditor

                new_cfg = com.replicas[0].cfg
                joiner = Replica(
                    node_id="r4", cfg=new_cfg, seed=kp.seed,
                    transport=com.net.endpoint("r4"), app=KVStore(),
                )
                joiner.auditor = SafetyAuditor(
                    "r4", new_cfg, log_dir=str(tmp_path)
                )
                auditors["r4"] = joiner.auditor
                com.replicas.append(joiner)
                joiner.start()
                for i in range(12):
                    await c.submit(f"put j{i} {i}", retries=5)
                frontier = max(
                    r.executed_seq for r in com.replicas if r is not joiner
                )
                for _ in range(300):
                    if joiner.executed_seq >= frontier:
                        break
                    await asyncio.sleep(0.05)
                assert joiner.executed_seq >= frontier
                assert joiner.metrics["state_syncs"] >= 1

                # REMOVE r4 again; it retires, the committee shrinks
                res = await c.submit(
                    "__reconfig__ " + json.dumps({"remove": ["r4"]}),
                    retries=5,
                )
                assert res.startswith("reconfig-staged:epoch=2"), res
                for i in range(10):
                    await c.submit(f"put z{i} {i}", retries=5)
                assert all(r.cfg.epoch == 2 for r in com.replicas)
                assert joiner.retired
                assert all(
                    "r4" not in r.cfg.replica_ids
                    for r in com.replicas if r is not joiner
                )

                # non-admin reconfig is DENIED deterministically
                evil_cfg = com.replicas[0].cfg
                assert "c9" not in evil_cfg.admin_ids
            finally:
                await com.stop()
                for a in auditors.values():
                    a.close()

            # the audit plane held I1-I4 across BOTH epoch boundaries:
            # zero violations, cross-node ledgers agree, clean bill
            assert all(a.violations == 0 for a in auditors.values())
            report, code = ledger_audit.run_audit(
                [str(tmp_path)], cfg=com.replicas[0].cfg
            )
            assert code == 0, report
            assert not report["accused"]

        run(scenario())

    def test_reconfig_denied_for_non_admin_and_bad_spec(self):
        async def scenario():
            com = LocalCommittee.build(
                n=4, clients=2, checkpoint_interval=4,
                admin_ids=("c0",),  # c1 is NOT an admin
            )
            com.start()
            c0, c1 = com.clients
            try:
                res = await c1.submit(
                    "__reconfig__ " + json.dumps({"remove": ["r3"]}),
                    retries=5,
                )
                assert res == "reconfig-denied:not-admin"
                # structurally bad change from a real admin: denied, not
                # staged (removing below n=4 would make f = 0)
                res = await c0.submit(
                    "__reconfig__ " + json.dumps({"remove": ["r3"]}),
                    retries=5,
                )
                assert res.startswith("reconfig-denied:"), res
                assert all(r.cfg.epoch == 0 for r in com.replicas)
            finally:
                await com.stop()

        run(scenario())

    def test_stale_epoch_voter_is_role_gated_not_believed(self, tmp_path):
        async def scenario():
            com = LocalCommittee.build(
                n=5, clients=1, checkpoint_interval=4, view_timeout=1.0
            )
            auditors = com.attach_auditors(log_dir=str(tmp_path))
            com.start()
            c = com.clients[0]
            try:
                for i in range(6):
                    await c.submit(f"put k{i} {i}", retries=5)
                res = await c.submit(
                    "__reconfig__ " + json.dumps({"remove": ["r4"]}),
                    retries=5,
                )
                assert res.startswith("reconfig-staged:"), res
                for i in range(6):
                    await c.submit(f"put m{i} {i}", retries=5)
                removed = com.replica("r4")
                assert removed.retired

                # r4 turns byzantine: refuses retirement, keeps voting
                # into the new epoch with its still-published key
                w = StaleEpochVoter(
                    removed.transport, Signer("r4", com.keys["r4"].seed)
                )
                w.mark_stale()
                removed.transport = w
                removed.retired = False  # the byzantine un-retire
                before = {
                    r.id: r.metrics["dropped_precheck"]
                    for r in com.replicas if r.id != "r4"
                }
                # the refusenik actively votes into the new epoch:
                # validly signed prepares/commits for live slots, sent
                # straight at the new committee's members
                from simple_pbft_tpu.messages import Commit, Prepare

                signer = Signer("r4", com.keys["r4"].seed)
                live_view = max(r.view for r in com.replicas if r.id != "r4")
                frontier = max(
                    r.executed_seq for r in com.replicas if r.id != "r4"
                )
                for cls in (Prepare, Commit):
                    vote = cls(
                        view=live_view, seq=frontier + 1, digest="ab" * 32
                    )
                    signer.sign_msg(vote)
                    for r in com.replicas:
                        if r.id != "r4":
                            await w.send(r.id, vote.to_wire())
                for i in range(10):
                    await c.submit(f"put z{i} {i}", retries=5)
                await asyncio.sleep(0.3)
                # the committee kept committing; honest replicas dropped
                # the stale votes at the role gate (no signature spent,
                # no quorum influence) and nobody got accused
                assert w.injections >= 1
                gated = sum(
                    r.metrics["dropped_precheck"] - before[r.id]
                    for r in com.replicas if r.id != "r4"
                )
                assert gated >= 1, "stale votes never hit the role gate"
                assert all(a.violations == 0 for a in auditors.values())
            finally:
                await com.stop()
                for a in auditors.values():
                    a.close()

        run(scenario())

    def test_epoch_key_registration_keeps_jit_shapes_closed(self):
        """PR 3's warm_for_population contract across an epoch boundary:
        registering a NEW member's key fills a reserved bank row — the
        jit signature (mode, window, batch, table cap) is unchanged, so
        post_warm_compiles stays 0 and the new key's signatures verify
        on the warmed device path."""
        from simple_pbft_tpu.crypto import ed25519_cpu
        from simple_pbft_tpu.crypto.tpu_verifier import TpuVerifier
        from simple_pbft_tpu.crypto.verifier import BatchItem

        cfg, keys = make_test_committee(n=4, clients=1)
        pop = list(cfg.pubkeys.values())
        v = TpuVerifier(initial_keys=len(pop) + 32)
        v.warm_for_population(pop, max_sweep=8)
        assert v.shape_snapshot()["post_warm_compiles"] == 0

        # the epoch boundary registers the joiner's key, shapes closed
        kp = _joiner_keys("r4")
        v.warm(pubkeys=[kp.pub], buckets=[])
        payload = b"post-epoch message"
        sig = ed25519_cpu.sign(kp.seed, payload)
        out = v.verify_batch(
            [BatchItem(pubkey=kp.pub, msg=payload, sig=sig)] * 8
        )
        assert all(out)
        snap = v.shape_snapshot()
        assert snap["post_warm_compiles"] == 0, snap


# ---------------------------------------------------------------------------
# satellites: tcp frame accounting, kind-registry sync, NET column
# ---------------------------------------------------------------------------


class TestTcpFrameAccounting:
    def test_mid_write_failure_counted_and_quorum_frames_requeued(self):
        async def scenario():
            # a peer that accepts every connection and slams it shut:
            # every frame that reaches the writer dies mid-write
            conns = 0

            async def slam(reader, writer):
                nonlocal conns
                conns += 1
                writer.close()

            server = await asyncio.start_server(slam, "127.0.0.1", 0)
            port = server.sockets[0].getsockname()[1]
            a = TcpTransport(
                "a", ("127.0.0.1", 0), peers={"b": ("127.0.0.1", port)}
            )
            await a.start()
            try:
                critical = b'{"kind":"commit","seq":1}'
                deferrable = b'{"kind":"request","op":"x"}'
                # phase 1: only quorum-critical frames — a mid-write
                # failure must requeue, never silently drop
                for _ in range(400):
                    await a.send("b", critical)
                    if a.metrics["frames_requeued"] >= 1:
                        break
                    await asyncio.sleep(0.02)
                # phase 2: only deferrable frames — a mid-write failure
                # is a COUNTED drop (the sender retries on its own timer)
                for _ in range(400):
                    await a.send("b", deferrable)
                    if a.metrics["frames_dropped"] >= 1:
                        break
                    await asyncio.sleep(0.02)
                # quorum-critical frames got their one requeue; the
                # second failure (and every deferrable failure) is a
                # counted drop — never a silent loss
                assert a.metrics["frames_requeued"] >= 1, dict(a.metrics)
                assert a.metrics["frames_dropped"] >= 1, dict(a.metrics)
            finally:
                await a.stop()
                server.close()
                await server.wait_closed()

        run(scenario(), timeout=60)


class TestKindRegistrySync:
    def test_docstrings_and_parse_errors_name_every_kind(self):
        import simple_pbft_tpu.faults as faults_mod

        table = kind_table()
        for kind in KIND_REGISTRY:
            assert kind in table
            # regenerated into both docstrings at import: no drift
            assert kind in (faults_mod.__doc__ or "")
            assert kind in (FaultSchedule.__doc__ or "")
        with pytest.raises(ValueError) as ei:
            FaultSchedule.parse("bogus_key=1", horizon=10.0)
        msg = str(ei.value)
        for kind in KIND_REGISTRY:
            assert kind in msg, f"parse error does not name {kind!r}"

    def test_new_kind_parse_and_determinism(self):
        spec = (
            "seed=7,partition=1.0:r0|r1<>r2|r3:0.5;3.0:*>r0,"
            "heal=4.0,shape=wan3dc,stale=1,forgesync=1"
        )
        ids = ["r0", "r1", "r2", "r3"]
        s1 = FaultSchedule.parse(spec, horizon=10.0, replica_ids=ids)
        s2 = FaultSchedule.parse(spec, horizon=10.0, replica_ids=ids)
        assert s1.events == s2.events
        kinds = {e.kind for e in s1.events}
        assert {
            "partition", "heal", "shape", "stale_epoch", "forge_statesync"
        } <= kinds
        with pytest.raises(ValueError):
            FaultSchedule.parse("shape=nosuchprofile", horizon=10.0)
        with pytest.raises(ValueError):
            FaultSchedule.parse("partition=oops", horizon=10.0)
        # 'shape=lossy:5' is malformed (T:NAME[:DUR]), not 'lossy forever'
        with pytest.raises(ValueError):
            FaultSchedule.parse("shape=lossy:5", horizon=10.0)

    def test_reconfig_key_rotation_keeps_the_member(self):
        # remove+add of the SAME id in one op is key rotation: the member
        # must survive with the new key, not be silently dropped
        from simple_pbft_tpu.config import apply_reconfig, make_test_committee

        cfg, _ = make_test_committee(n=5, clients=1)
        kp = _joiner_keys("r2x")
        new_cfg = apply_reconfig(
            cfg, {"r2": {"pub": kp.pub.hex()}}, ["r2"]
        )
        assert "r2" in new_cfg.replica_ids
        assert new_cfg.n == 5
        assert new_cfg.pubkeys["r2"] == kp.pub
        # rotation re-enters at the END of the order (it is a re-add)
        assert new_cfg.replica_ids[-1] == "r2"


class TestNetColumn:
    def test_net_cell_renders_shaping_partition_and_sync_state(self):
        snap = {
            "replica": {"statesync_active": True, "retired": False},
            "transport": {
                "shaping": {
                    "profile": "wan3dc",
                    "cut_to": ["r1", "r2"],
                    "shaped_links": 6,
                    "shaped_lost": 3,
                    "partition_dropped": 4,
                },
            },
        }
        cell = pbft_top.net_cell(snap)
        assert "wan3dc" in cell and "!2cut" in cell
        assert "~7" in cell and "sync" in cell
        assert pbft_top.net_cell({"replica": {}, "transport": {}}) == ""
        row = pbft_top.row_from_snapshot(snap, "http", None, 1.0)
        assert cell in row
        assert len(row) == len(pbft_top.COLUMNS)


# ---------------------------------------------------------------------------
# statesync SOLO mode: forgery attribution without honest-peer collateral
# ---------------------------------------------------------------------------


class _StubReplica:
    """Minimal replica surface for driving StateSync deterministically."""

    def __init__(self):
        from collections import defaultdict
        from types import SimpleNamespace

        self.id = "rx"
        self.cfg = SimpleNamespace(
            replica_ids=["rx", "r0", "r1", "r2"]
        )
        self.metrics = defaultdict(int)
        self.signer = SimpleNamespace(sign_msg=lambda m: None)
        self.sent = []
        self.transport = SimpleNamespace(send=self._send)
        self.installed = []
        self.snapshots = {}

    async def _send(self, dest, raw):
        self.sent.append((dest, raw))

    async def install_snapshot(self, seq, digest, snap):
        self.installed.append((seq, digest, snap))
        return True

    async def send_slot_probe(self):
        pass


def _chunk_reply(sender, seq, index, total, data):
    from simple_pbft_tpu.messages import StateChunkReply

    msg = StateChunkReply(seq=seq, index=index, total=total, data=data)
    msg.sender = sender
    return msg


class TestStatesyncSoloMode:
    def test_forgery_attribution_convicts_only_the_liar(self):
        """The full recovery ladder: a multi-source mismatch convicts
        NOBODY (attribution impossible) and drops to SOLO mode; a solo
        mismatch convicts its sole source definitively; the next honest
        solo peer completes the install. Before this, a mismatch
        excluded EVERY serving peer — one persistent forger livelocked
        the transfer (honest peers excluded, nobody left to serve)."""
        from simple_pbft_tpu.app import snapshot_digest
        from simple_pbft_tpu.consensus.statesync import StateSync

        async def scenario():
            r = _StubReplica()
            ss = StateSync(r)
            snap = "A" * 40 + "B" * 40
            digest = snapshot_digest(snap)
            await ss.begin(8, digest, certifiers=["r0", "r1", "r2"])
            a = ss.active

            # round 1: striped assembly, r0's chunk forged — mismatch
            # with two sources convicts nobody, enters solo mode
            await ss.on_chunk_reply(_chunk_reply("r0", 8, 0, 2, "X" * 40))
            await ss.on_chunk_reply(_chunk_reply("r1", 8, 1, 2, snap[40:]))
            assert ss.active is a  # still transferring
            assert r.metrics["statesync_forged"] == 1
            assert a["bad_peers"] == set()
            assert a["solo"] is not None
            assert not a["chunks"] and a["total"] is None

            # round 2: the solo peer serves the WHOLE (forged) snapshot
            # — every byte came from it, so conviction is definitive
            liar = a["solo"]
            await ss.on_chunk_reply(_chunk_reply(liar, 8, 0, 1, "Z" * 80))
            assert r.metrics["statesync_forged"] == 2
            assert a["bad_peers"] == {liar}
            assert a["solo"] is not None and a["solo"] != liar

            # replies from the convicted liar (and stale multi-source
            # peers) are ignored in solo mode
            await ss.on_chunk_reply(_chunk_reply(liar, 8, 0, 1, snap))
            others = [
                p for p in ("r0", "r1", "r2")
                if p != a["solo"] and p != liar
            ]
            await ss.on_chunk_reply(_chunk_reply(others[0], 8, 0, 1, snap))
            assert not a["chunks"]

            # round 3: the honest solo peer completes the transfer
            await ss.on_chunk_reply(_chunk_reply(a["solo"], 8, 0, 1, snap))
            assert ss.active is None
            assert r.installed == [(8, digest, snap)]
            assert r.metrics["statesync_restarts"] == 2

        run(scenario(), timeout=30)

    def test_conflicting_totals_convict_only_on_clean_attribution(self):
        from simple_pbft_tpu.app import snapshot_digest
        from simple_pbft_tpu.consensus.statesync import StateSync

        async def scenario():
            r = _StubReplica()
            ss = StateSync(r)
            snap = "C" * 64
            await ss.begin(4, snapshot_digest(snap), certifiers=["r0", "r1"])
            a = ss.active
            # two DISTINCT claimants disagree on the count: either could
            # be lying — nobody convicted, transfer isolates to solo
            await ss.on_chunk_reply(_chunk_reply("r0", 4, 0, 2, "C" * 32))
            await ss.on_chunk_reply(_chunk_reply("r1", 4, 0, 3, "C" * 16))
            assert a["bad_peers"] == set()
            assert a["solo"] is not None
            assert a["total"] is None

            # the SAME peer contradicting its own earlier claim is
            # definitive: convict it
            solo = a["solo"]
            await ss.on_chunk_reply(_chunk_reply(solo, 4, 0, 2, "C" * 32))
            await ss.on_chunk_reply(_chunk_reply(solo, 4, 1, 5, "C" * 16))
            assert solo in a["bad_peers"]
            assert a["solo"] != solo

        run(scenario(), timeout=30)

    def test_serve_bucket_admits_pipelined_burst_then_throttles(self):
        """The requester's WINDOW round-robin lands back-to-back requests
        on the same peer; a fixed per-request cooldown dropped them
        (capping transfers at ~1 chunk/peer/tick) — the token bucket
        serves the whole burst and still bounds a hostile spammer."""
        from simple_pbft_tpu.consensus.statesync import (
            SERVE_BURST, StateSync,
        )
        from simple_pbft_tpu.messages import StateChunkRequest

        async def scenario():
            r = _StubReplica()
            r.snapshots[4] = "D" * 64
            ss = StateSync(r)
            req = StateChunkRequest(seq=4, index=0)
            req.sender = "joiner"
            for _ in range(SERVE_BURST):
                await ss.on_chunk_request(req)
            assert r.metrics["statesync_chunks_served"] == SERVE_BURST
            assert r.metrics["statesync_throttled"] == 0
            await ss.on_chunk_request(req)  # burst exhausted
            assert r.metrics["statesync_throttled"] == 1
            assert len(r.sent) == SERVE_BURST

        run(scenario(), timeout=30)

    def test_persistent_forgers_cannot_livelock_rejoin(self):
        """Integration regression for the livelock: TWO of three serving
        peers forge every chunk and NEVER heal; the snapshot spans
        multiple chunks so the striped first assembly must touch a
        forger. Solo mode convicts the forgers individually and the
        honest peer completes the transfer — previously the first
        mismatch excluded all three peers and the joiner never caught
        up while a forger stayed active."""

        async def scenario():
            from simple_pbft_tpu.consensus.statesync import CHUNK_BYTES

            com = LocalCommittee.build(
                n=4, clients=1, checkpoint_interval=4, view_timeout=1.0
            )
            com.start()
            c = com.clients[0]
            victim = com.replica("r3")
            big = "x" * 20000
            try:
                for i in range(6):
                    await c.submit(f"put k{i} {big}", retries=5)
                victim.kill()
                for i in range(10):
                    await c.submit(f"put m{i} {big}", retries=5)
                # the live snapshot now spans >= 2 chunks. Settle: the
                # speculative fast path (ISSUE 15) answers submits
                # before the commit wave executes, so the checkpoint
                # that cuts the big snapshot may still be in flight
                donor = com.replica("r0")
                for _ in range(200):
                    if any(
                        len(s) > CHUNK_BYTES
                        for s in donor.snapshots.values()
                    ):
                        break
                    await asyncio.sleep(0.05)
                assert any(
                    len(s) > CHUNK_BYTES for s in donor.snapshots.values()
                )
                wrapped = []
                for rid in ("r0", "r1"):
                    rep = com.replica(rid)
                    w = ForgedSnapshotServer(
                        rep.transport, Signer(rid, com.keys[rid].seed)
                    )
                    rep.transport = w
                    wrapped.append(w)

                fresh = Replica(
                    node_id="r3", cfg=com.cfg, seed=com.keys["r3"].seed,
                    transport=com.net.endpoint("r3"), app=KVStore(),
                )
                com.replicas[com.replicas.index(victim)] = fresh
                fresh.start()
                for i in range(6):
                    await c.submit(f"put s{i} {i}", retries=5)
                frontier = max(
                    r.executed_seq for r in com.replicas if r is not fresh
                )
                # catch-up WHILE the forgers stay active — no heal
                for _ in range(500):
                    if fresh.executed_seq >= frontier:
                        break
                    await asyncio.sleep(0.05)
                assert fresh.executed_seq >= frontier, (
                    fresh.executed_seq, frontier, dict(fresh.metrics),
                )
                assert sum(w.injections for w in wrapped) >= 1
                assert fresh.metrics["statesync_forged"] >= 1
                assert fresh.app.data.get("k0") == big
            finally:
                await com.stop()

        run(scenario())

    def test_oversized_chunk_convicts_before_storing(self):
        """An honest server never exceeds CHUNK_BYTES per chunk, so an
        oversized reply is an individually attributable lie — it must be
        convicted BEFORE a byte is stored, or a forged stream of
        transport-cap-sized chunks balloons the joiner's memory long
        before the assembly digest check could notice."""
        from simple_pbft_tpu.app import snapshot_digest
        from simple_pbft_tpu.consensus.statesync import CHUNK_BYTES, StateSync

        async def scenario():
            r = _StubReplica()
            ss = StateSync(r)
            snap = "E" * 64
            await ss.begin(4, snapshot_digest(snap), certifiers=["r0", "r1"])
            a = ss.active
            await ss.on_chunk_reply(
                _chunk_reply("r0", 4, 0, 2, "F" * (CHUNK_BYTES + 1))
            )
            assert "r0" in a["bad_peers"]
            assert not a["chunks"]
            assert r.metrics["statesync_bytes"] == 0
            assert r.metrics["statesync_forged"] == 1
            # the honest peer still completes the transfer in solo mode
            while a["solo"] == "r0":
                ss._rotate_solo(a)
            await ss.on_chunk_reply(_chunk_reply(a["solo"], 4, 0, 1, snap))
            assert r.installed == [(4, snapshot_digest(snap), snap)]

        run(scenario(), timeout=30)


# ---------------------------------------------------------------------------
# review hardening: link FIFO, schedule-driven stale voter, address plane
# ---------------------------------------------------------------------------


class _RecordingInner:
    def __init__(self, node_id="rA"):
        self.node_id = node_id
        self.delivered = []

    async def send(self, dest, raw):
        self.delivered.append(raw)

    async def broadcast(self, raw, dests):
        for d in dests:
            if d != self.node_id:
                await self.send(d, raw)


class TestShapedLinkFifo:
    def test_jitter_never_reorders_a_link(self):
        """A TCP byte stream cannot deliver frame B before an earlier
        frame A. Independent per-frame jitter draws used to violate that
        on every shaped link (both shipped profiles set jitter but no
        bandwidth, so nothing serialized deliveries) — shaped-over-TCP
        rehearsals were strictly MORE adversarial than the WAN they
        claim to model. Deliveries are now clamped behind the link's
        previous one."""

        async def scenario():
            inner = _RecordingInner()
            shaped = ShapedTransport(
                inner,
                shapes={"rB": LinkShape(delay_s=0.0005, jitter_s=0.02)},
                seed=3,
            )
            frames = [f"frame-{i}".encode() for i in range(30)]
            for f in frames:
                await shaped.send("rB", f)
            deadline = asyncio.get_event_loop().time() + 5.0
            while (
                len(inner.delivered) < len(frames)
                and asyncio.get_event_loop().time() < deadline
            ):
                await asyncio.sleep(0.01)
            assert inner.delivered == frames

        run(scenario(), timeout=30)


class TestScheduleDrivenStaleVoter:
    def test_armed_voter_actually_votes_after_removal(self):
        """The honest retiree self-gags at _send_vote, so a StaleEpochVoter
        armed purely on `retired` never saw a vote frame: injections
        stayed 0 and the schedule recorded a byzantine fault that never
        happened. The injector now makes the target REFUSE retirement —
        its stale-epoch votes actually leave the process and die at the
        honest peers' role gate."""
        import time as time_mod

        async def scenario():
            com = LocalCommittee.build(
                n=5, clients=1, checkpoint_interval=4, view_timeout=2.0
            )
            com.start()
            c = com.clients[0]
            schedule = FaultSchedule(
                seed=0, horizon=0.2,
                events=(FaultEvent(t=0.0, kind="stale_epoch", target="r4"),),
            )
            injector = FaultInjector(committee=com, schedule=schedule)
            try:
                await injector.run(pbft_clock.now() + 0.5)
                removed = com.replica("r4")
                assert removed.refuse_retirement
                assert isinstance(removed.transport, StaleEpochVoter)
                for i in range(4):
                    await c.submit(f"put k{i} {i}", retries=5)
                res = await c.submit(
                    "__reconfig__ " + json.dumps({"remove": ["r4"]}),
                    retries=5,
                )
                assert res.startswith("reconfig-staged:"), res
                before = {
                    r.id: r.metrics["dropped_precheck"]
                    for r in com.replicas if r.id != "r4"
                }
                for i in range(10):
                    await c.submit(f"put m{i} {i}", retries=5)
                await asyncio.sleep(0.3)
                # the refusenik crossed the boundary WITHOUT gagging
                assert removed.cfg.epoch >= 1
                assert "r4" not in removed.cfg.replica_ids
                assert not removed.retired
                # its stale votes really left the process this time...
                assert injector.byzantine_injections >= 1
                # ...and died at the honest role gate, not in a quorum
                gated = sum(
                    r.metrics["dropped_precheck"] - before[r.id]
                    for r in com.replicas if r.id != "r4"
                )
                assert gated >= 1
                assert all(
                    r.executed_seq >= 14
                    for r in com.replicas if r.id != "r4"
                )
            finally:
                await com.stop()

        run(scenario())


class TestAddressPlane:
    def test_reconfig_addr_rides_config_and_updates_peer_books(self):
        """Socket transports route by peer book: a reconfiguration-added
        member used to be named by the committed config but unreachable
        (tcp/grpc send drops unknown dests silently). The add spec now
        carries `addr`, the book rides config_doc (so snapshots and
        ConfigReply ship it), and epoch activation / client adoption
        push it into every peer map in the transport wrapper chain."""
        import dataclasses

        from simple_pbft_tpu.config import (
            apply_reconfig, config_doc, config_from_doc,
        )
        from simple_pbft_tpu.transport.base import update_peer_book

        cfg, _ = make_test_committee(n=4, clients=1)
        cfg = dataclasses.replace(
            cfg,
            addrs={f"r{i}": ("127.0.0.1", 7000 + i) for i in range(4)},
        )
        kp = _joiner_keys("r9")
        new_cfg = apply_reconfig(
            cfg,
            {"r9": {"pub": kp.pub.hex(), "addr": "10.0.0.9:7009"}},
            [],
        )
        assert new_cfg.addrs["r9"] == ("10.0.0.9", 7009)
        # survivors keep their entries; the doc round-trip (checkpoint
        # snapshot / ConfigReply) preserves the whole book
        assert new_cfg.addrs["r0"] == ("127.0.0.1", 7000)
        rt = config_from_doc(cfg, config_doc(new_cfg))
        assert rt.addrs == new_cfg.addrs
        # a malformed addr denies the whole reconfig deterministically
        with pytest.raises(ValueError):
            apply_reconfig(
                cfg, {"r9": {"pub": kp.pub.hex(), "addr": "nocolon"}}, []
            )

        class _Sock:
            node_id = "r0"

            def __init__(self):
                self.peers = {"r1": ("127.0.0.1", 7001)}

        sock = _Sock()
        shaped = ShapedTransport(sock)
        assert update_peer_book(shaped, new_cfg.addrs) >= 1
        assert sock.peers["r9"] == ("10.0.0.9", 7009)
        assert sock.peers["r3"] == ("127.0.0.1", 7003)
        assert "r0" not in sock.peers  # a book never routes to itself

    def test_deployment_boot_config_carries_the_book(self, tmp_path):
        from simple_pbft_tpu import deploy

        dep = deploy.generate(str(tmp_path), n=4, clients=1)
        assert dep.cfg.addrs == dep.addresses
        loaded = deploy.load(str(tmp_path / "committee.json"))
        assert loaded.cfg.addrs == dep.addresses


class TestEpochBoundarySafety:
    """A slot past a staged membership boundary belongs to the NEXT
    epoch: the old committee's (smaller) quorum must never decide it.
    Stop-sequence gates hold such slots while the change is staged, and
    activation refits any straddler that slipped through the
    staging-knowledge race (proposals pipelined ahead of the execution
    frontier)."""

    def _staged_replica(self):
        com = LocalCommittee.build(
            n=4, clients=1, checkpoint_interval=4, view_timeout=5.0
        )
        r0 = com.replica("r0")
        kp = _joiner_keys("r4")
        from simple_pbft_tpu.config import apply_reconfig

        grown = apply_reconfig(
            r0.cfg,
            {"r4": {"pub": kp.pub.hex()},
             "r5": {"pub": _joiner_keys("r5").pub.hex()},
             "r6": {"pub": _joiner_keys("r6").pub.hex()}},
            [],
        )
        assert grown.quorum > r0.cfg.quorum  # 3 -> 5: the unsafe delta
        r0.pending_reconfig = (8, grown)
        return com, r0, grown

    def test_stop_sequence_gates_proposals_and_admission(self):
        from simple_pbft_tpu.messages import PrePrepare, Request

        async def scenario():
            com, r0, grown = self._staged_replica()
            # primary side: next_seq past the staged boundary stalls
            r0.next_seq = 9
            req = Request(client_id="c0", timestamp=1, operation="put a 1")
            r0.pending_requests = [req]
            await r0._propose_if_ready()
            assert r0.metrics["reconfig_boundary_stall"] == 1
            assert r0.metrics["proposed_blocks"] == 0
            assert (0, 9) not in r0.instances
            # backup side: a proposal past the boundary is refused
            pp = PrePrepare(
                view=0, seq=9, digest=PrePrepare.block_digest([]), block=[]
            )
            pp.sender = "r0"
            await r0._on_phase(pp)
            assert r0.metrics["preprepare_beyond_boundary"] == 1
            assert (0, 9) not in r0.instances
            # at/below the boundary is untouched by the gate
            pp8 = PrePrepare(
                view=0, seq=8, digest=PrePrepare.block_digest([]), block=[]
            )
            pp8.sender = "r0"
            await r0._on_phase(pp8)
            assert r0.metrics["preprepare_beyond_boundary"] == 1

        run(scenario(), timeout=30)

    def test_activation_refits_straddler_instances(self):
        from simple_pbft_tpu.consensus.state import (
            ExecuteBlock, Stage,
        )
        from simple_pbft_tpu.messages import Commit, Prepare

        async def scenario():
            com, r0, grown = self._staged_replica()
            old_quorum = r0.cfg.quorum
            # a straddler: slot 9 fully committed under the OLD quorum
            # (its pre-prepare outran r0's execution of the staging op),
            # with one vote from a sender the new epoch removes
            inst = r0._instance(0, 9)
            assert inst.quorum == old_quorum
            inst.digest = "ab" * 32
            inst.block = []
            from simple_pbft_tpu.messages import PrePrepare

            ppin = PrePrepare(view=0, seq=9, digest=inst.digest, block=[])
            ppin.sender = r0.cfg.primary(0)
            inst.pre_prepare = ppin
            for sender in ("r0", "r1", "r2"):
                p = Prepare(view=0, seq=9, digest=inst.digest)
                p.sender = sender
                inst.on_prepare(p)
                c = Commit(view=0, seq=9, digest=inst.digest)
                c.sender = sender
                inst.on_commit(c)
            inst.stage = Stage.COMMITTED
            inst.executed = True
            r0.ready[9] = ExecuteBlock(0, 9, inst.digest, [])
            # also an UNPINNED buffer instance: primary must repoint
            buf = r0._instance(1, 10)

            low = r0._instance(0, 8)
            r0.executed_seq = 8
            r0._activate_epoch(grown)
            assert inst.quorum == grown.quorum
            # 3 surviving old-epoch votes < new quorum 5: the commit is
            # walked back (digest stays pinned — no re-vote two ways)
            assert inst.stage == Stage.PRE_PREPARED
            assert inst.digest == "ab" * 32
            assert not inst.executed
            assert 9 not in r0.ready
            assert r0.metrics["epoch_slots_downgraded"] >= 1
            assert buf.quorum == grown.quorum
            assert buf.primary == grown.primary(1)
            # slots at/below the boundary keep their old-epoch threshold
            assert low.quorum == old_quorum

        run(scenario(), timeout=30)

    def test_generated_partition_durations_respect_short_horizons(self):
        # uniform(0.5, 0.15*h) inverts its bounds below h~3.3s and dealt
        # durations past the cap (into the bench drain window)
        s = FaultSchedule.generate(
            seed=3, horizon=2.0, partition_windows=8,
            replica_ids=["r0", "r1", "r2", "r3"],
        )
        durs = [e.duration for e in s.events if e.kind == "partition"]
        assert durs and all(d <= 0.15 * 2.0 + 1e-9 for d in durs)


class TestConfigVoteSpam:
    def test_hostile_replica_cannot_starve_honest_config_adoption(self):
        """Per-sender claim slots: a hostile KNOWN replica signing any
        number of distinct configs only overwrites its own slot, so the
        honest f+1 still accumulates and the client adopts. (The old
        bounded-table eviction could be pre-filled and then starved the
        honest entry on the fewest-votes tie-break.)"""
        from simple_pbft_tpu.config import (
            apply_reconfig, config_doc, make_test_committee,
        )
        from simple_pbft_tpu.messages import ConfigReply

        async def scenario():
            cfg, keys = make_test_committee(
                n=4, clients=1, verify_signatures=False
            )
            client = Client(
                "c0", cfg, keys["c0"].seed, _RecordingInner("c0")
            )
            grown = apply_reconfig(
                cfg, {"r4": {"pub": _joiner_keys("r4").pub.hex()}}, []
            )
            good = json.dumps(config_doc(grown))
            # r3 floods distinct forged configs for epochs far ahead
            for i in range(200):
                spam = ConfigReply(
                    epoch=1 + i, config=json.dumps({"junk": i})
                )
                spam.sender = "r3"
                client._on_config_reply(spam)
            assert len(client._config_votes) == 1  # only r3's own slot
            # two honest members (f+1) report the real epoch-1 config
            for sender in ("r0", "r1"):
                msg = ConfigReply(epoch=1, config=good)
                msg.sender = sender
                client._on_config_reply(msg)
            assert client.epoch == 1
            assert "r4" in client.cfg.replica_ids

        run(scenario(), timeout=30)
