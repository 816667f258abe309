"""Wire accounting (ISSUE 12 tentpole): kind classification without a
parse, per-link per-kind conservation under shaped loss and asymmetric
partitions, schema alignment across transports, and the derived
per-commit costs every bench record now carries."""

from __future__ import annotations

import asyncio
import json

import pytest

from simple_pbft_tpu import messages
from simple_pbft_tpu.committee import LocalCommittee
from simple_pbft_tpu.faults import LinkShape, ShapedTransport, find_shaped
from simple_pbft_tpu.telemetry import (
    WIRE_PHASE_OF_KIND,
    transport_snapshot,
    wire_aggregate,
    wire_delta,
    wire_per_commit,
)
from simple_pbft_tpu.transport.base import (
    COUNTER_SCHEMA,
    UNKNOWN_KIND,
    WireAccounting,
    base_metrics,
    wire_kind,
    wire_of,
)


def _run(coro, timeout=120):
    return asyncio.run(asyncio.wait_for(coro, timeout))


class TestWireKind:
    def test_every_registered_kind_classifies_from_default_instance(self):
        for kind, cls in messages._REGISTRY.items():
            assert wire_kind(cls().to_wire()) == kind

    def test_embedded_request_kind_does_not_fool_the_classifier(self):
        # a pre-prepare's block field sorts BEFORE its top-level kind in
        # canonical JSON, and the block embeds full requests — the exact
        # shape a first-substring scan would misclassify as "request"
        req = messages.Request(
            client_id="c0", timestamp=7, operation="put a b",
            sender="c0", sig="ab" * 32,
        )
        pp = messages.PrePrepare(
            view=0, seq=3, digest="d" * 64, sender="r0", sig="ab" * 32,
            block={"reqs": [req.to_dict()], "kind_decoy": '"kind":"qc"'},
        )
        assert wire_kind(pp.to_wire()) == "preprepare"

    def test_escaped_quotes_and_braces_in_payload(self):
        req = messages.Request(
            client_id="c0", timestamp=1, sender="c0", sig="cd" * 32,
            operation='put k {"quoted\\" }{[ brace bomb, \\"kind\\":\\"qc\\"',
        )
        assert wire_kind(req.to_wire()) == "request"

    def test_malformed_frames_return_unknown_and_never_raise(self):
        cases = [
            b"", b"[1,2]", b"garbage", b'{"a":}', b'{"zeta":1}',
            b'{"kind":12}', b'{"block"', b'{"a":"unterminated',
            b'{"kind":"x"',  # classifiable prefix, torn tail is fine
        ]
        for raw in cases[:-1]:
            assert wire_kind(raw) == UNKNOWN_KIND
        # truncation fuzz over a real message: any cut must classify or
        # return unknown, never raise
        raw = messages.Prepare(
            view=1, seq=2, digest="e" * 64, sender="r1", sig="ef" * 32
        ).to_wire()
        for cut in range(0, len(raw), 7):
            out = wire_kind(raw[:cut])
            assert isinstance(out, str)

    def test_phase_table_covers_every_registered_kind(self):
        # a new message kind must get a phase assignment (or this drifts
        # silently into "other" and per-phase rollups undercount)
        assert set(WIRE_PHASE_OF_KIND) == set(messages.ALL_KINDS)


class TestSchemaAlignment:
    def test_local_endpoint_metrics_carry_the_full_shared_schema(self):
        async def go():
            from simple_pbft_tpu.transport.local import LocalNetwork

            net = LocalNetwork()
            ep = net.endpoint("r0")
            assert set(ep.metrics) == set(COUNTER_SCHEMA)
            assert all(v == 0 for v in ep.metrics.values())
            assert isinstance(ep.wire, WireAccounting)
            # a re-handle for the same id shares the accounting ledger
            assert net.endpoint("r0").wire is ep.wire

        _run(go())

    def test_base_metrics_is_fresh_per_call(self):
        a, b = base_metrics(), base_metrics()
        a["sent"] = 9
        assert b["sent"] == 0

    def test_tcp_and_grpc_metrics_share_the_schema(self):
        from simple_pbft_tpu.transport.grpc import GrpcTransport
        from simple_pbft_tpu.transport.tcp import TcpTransport

        t = TcpTransport("r0", ("127.0.0.1", 0), peers={})
        g = GrpcTransport("r0", ("127.0.0.1", 0), peers={})
        assert set(t.metrics) == set(COUNTER_SCHEMA)
        assert set(g.metrics) == set(COUNTER_SCHEMA)
        assert isinstance(t.wire, WireAccounting)
        assert isinstance(g.wire, WireAccounting)


def _sum_sent(wires):
    out = {}
    for w in wires:
        for kinds in w.sent.values():
            for k, (m, b) in kinds.items():
                cell = out.setdefault(k, [0, 0])
                cell[0] += m
                cell[1] += b
    return out


def _sum_recv(wires):
    out = {}
    for w in wires:
        for k, (m, b) in w.recv.items():
            cell = out.setdefault(k, [0, 0])
            cell[0] += m
            cell[1] += b
    return out


def _sum_lost(wires, bucket):
    out = {}
    for w in wires:
        for k, (m, b) in w.lost.get(bucket, {}).items():
            cell = out.setdefault(k, [0, 0])
            cell[0] += m
            cell[1] += b
    return out


class TestConservation:
    def test_bytes_conserve_under_shaped_loss_and_asymmetric_partition(self):
        """The acceptance invariant: per-kind bytes summed over senders'
        links equal receivers' observed totals; shaped/partition losses
        land in named buckets, never vanish."""

        async def go():
            com = LocalCommittee.build(n=4, clients=1, view_timeout=60.0)
            ids = list(com.cfg.replica_ids)
            for r in com.replicas:
                # lossy links replica->replica; client links unshaped
                r.transport = ShapedTransport(
                    r.transport,
                    shapes={d: LinkShape(loss=0.05) for d in ids if d != r.id},
                    seed=7,
                )
            com.clients[0].request_timeout = 5.0
            com.start()
            try:
                for i in range(4):
                    assert await com.clients[0].submit(
                        f"put a{i} {i}", retries=8) == "ok"
                # asymmetric partition: r0 stops reaching r3 (r3 still
                # talks to r0) — quorum 3/4 keeps committing
                find_shaped(com.replica("r0").transport).partition(["r3"])
                for i in range(4):
                    assert await com.clients[0].submit(
                        f"put b{i} {i}", retries=8) == "ok"
                find_shaped(com.replica("r0").transport).heal()
                for i in range(2):
                    assert await com.clients[0].submit(
                        f"put c{i} {i}", retries=8) == "ok"
            finally:
                await com.stop()

            wires = [wire_of(r.transport) for r in com.replicas] + [
                wire_of(c.transport) for c in com.clients
            ]
            assert all(w is not None for w in wires)
            sent, recv = _sum_sent(wires), _sum_recv(wires)
            assert sent == recv, (sent, recv)
            assert sent, "nothing was accounted"
            assert UNKNOWN_KIND not in sent
            shaped = _sum_lost(wires, "shaped_lost")
            cut = _sum_lost(wires, "partition_dropped")
            assert sum(b for _, b in shaped.values()) > 0, \
                "5% loss over a whole run lost nothing?"
            assert sum(b for _, b in cut.values()) > 0, \
                "an open partition dropped nothing?"
            # the shaped wrapper reports through the SAME ledger the
            # telemetry plane reads: counters reconcile exactly
            w0 = wire_of(com.replica("r0").transport)
            snap = w0.snapshot()
            assert snap["lost"].get("partition_dropped", [0, 0])[0] == sum(
                m for m, _ in w0.lost.get("partition_dropped", {}).values()
            )

        _run(go())

    def test_local_faultplan_drops_land_in_net_dropped(self):
        async def go():
            from simple_pbft_tpu.transport.local import (
                FaultPlan,
                LocalNetwork,
            )

            net = LocalNetwork(FaultPlan(drop_rate=1.0, seed=1))
            a, b = net.endpoint("a"), net.endpoint("b")
            raw = messages.Prepare(
                view=0, seq=1, digest="d" * 64, sender="a", sig="ab" * 32
            ).to_wire()
            await a.send("b", raw)
            assert a.wire.sent == {}
            assert a.wire.lost["net_dropped"]["prepare"] == [1, len(raw)]
            assert b.wire.recv == {}
            # unknown destination: accounted, not silent
            await a.send("nobody", raw)
            assert a.wire.lost["no_route"]["prepare"][0] == 1

        _run(go())

    def test_tcp_self_send_and_overflow_buckets(self):
        async def go():
            from simple_pbft_tpu.transport.tcp import TcpTransport

            t = TcpTransport("r0", ("127.0.0.1", 0), peers={})
            raw = messages.Commit(
                view=0, seq=1, digest="d" * 64, sender="r0", sig="ab" * 32
            ).to_wire()
            await t.send("r0", raw)
            assert t.wire.sent["r0"]["commit"] == [1, len(raw)]
            assert t.wire.recv["commit"] == [1, len(raw)]
            await t.send("ghost", raw)
            assert t.wire.lost["no_route"]["commit"][0] == 1

        _run(go())


class TestDerived:
    def test_per_commit_costs_and_phase_amplification(self):
        per_kind = {
            "prepare": {"sent_msgs": 24, "sent_bytes": 4800,
                        "recv_msgs": 24, "recv_bytes": 4800,
                        "lost_msgs": 0, "lost_bytes": 0},
            "commit": {"sent_msgs": 24, "sent_bytes": 4800,
                       "recv_msgs": 24, "recv_bytes": 4800,
                       "lost_msgs": 2, "lost_bytes": 400},
            "preprepare": {"sent_msgs": 6, "sent_bytes": 6000,
                           "recv_msgs": 6, "recv_bytes": 6000,
                           "lost_msgs": 0, "lost_bytes": 0},
        }
        pc = wire_per_commit(per_kind, slots=2, requests=8)
        assert pc["per_kind"]["prepare"] == {
            "phase": "prepare", "msgs_per_slot": 12.0,
            "bytes_per_slot": 2400.0, "msgs_per_req": 3.0,
            "bytes_per_req": 600.0,
        }
        # a phase's msgs_per_slot IS its broadcast amplification: the
        # all-to-all vote phase reads n(n-1) here
        assert pc["per_phase"]["prepare"]["msgs_per_slot"] == 12.0
        assert pc["per_phase"]["commit"]["lost_bytes"] == 400
        assert pc["per_phase"]["preprepare"]["bytes_per_slot"] == 3000.0
        assert pc["total_msgs_per_slot"] == 27.0
        assert pc["total_msgs_per_req"] == pytest.approx(54 / 8)

    def test_every_message_kind_has_a_phase_and_batches_are_replies(self):
        """A kind added to messages.py without a phase would report under
        "other"; a ``replybatch`` frame is reply-phase traffic, a kind of
        its own on the wire."""
        from simple_pbft_tpu.telemetry import WIRE_PHASE_OF_KIND
        from simple_pbft_tpu.transport.base import wire_kind

        assert set(messages.ALL_KINDS) - {"message"} <= set(WIRE_PHASE_OF_KIND)
        raw = messages.ReplyBatch(
            sender="r0", client_id="c0", timestamps=[1, 2],
            results=['{"kind":"reply"}', "ok"], mac="ab" * 32,
        ).to_wire()
        assert wire_kind(raw) == "replybatch"
        w = WireAccounting("r0")
        w.account_send("c0", raw)
        single = messages.Reply(sender="r0", client_id="c0", timestamp=3,
                                result="ok", mac="ab" * 32).to_wire()
        w.account_send("c0", single)
        per_kind = w.per_kind()
        assert per_kind["replybatch"]["sent_msgs"] == 1
        assert per_kind["reply"]["sent_msgs"] == 1
        pc = wire_per_commit(per_kind, slots=1, requests=3)
        assert pc["per_kind"]["replybatch"]["phase"] == "reply"
        assert pc["per_phase"]["reply"]["msgs_per_slot"] == 2.0
        assert pc["per_phase"]["reply"]["bytes_per_slot"] == float(
            len(raw) + len(single))

    def test_aggregate_and_delta(self):
        a = {"prepare": {"sent_msgs": 2, "sent_bytes": 100}}
        b = {"prepare": {"sent_msgs": 5, "sent_bytes": 300},
             "commit": {"sent_msgs": 1, "sent_bytes": 50}}
        agg = wire_aggregate([a, b])
        assert agg["prepare"]["sent_msgs"] == 7
        d = wire_delta(a, b)
        assert d["prepare"]["sent_msgs"] == 3
        assert d["commit"]["sent_msgs"] == 1
        # a restarted node's counter going backwards clamps, no nonsense
        assert wire_delta(b, a) == {}

    def test_snapshot_shape_and_telemetry_block(self):
        w = WireAccounting("r0")
        raw = messages.Reply(sender="r0", sig="ab" * 32).to_wire()
        w.account_send("c0", raw)
        w.account_recv(raw)
        w.account_lost("shaped_lost", raw)
        snap = w.snapshot()
        assert snap["sent_msgs"] == 1 and snap["recv_msgs"] == 1
        assert snap["links"]["c0"] == [1, len(raw)]
        assert snap["lost"]["shaped_lost"] == [1, len(raw)]
        assert snap["per_kind"]["reply"]["lost_bytes"] == len(raw)

        class FakeT:
            node_id = "r0"
            metrics = {"sent": 1}
            wire = w

        blk = transport_snapshot(FakeT())
        assert blk["wire"]["sent_bytes"] == len(raw)

    def test_accounting_never_raises_on_hostile_input(self):
        w = WireAccounting("r0")
        w.account_send("d", b"")
        w.account_recv(b"\xff\xfe")
        w.account_lost("b", None)  # type: ignore[arg-type]
        assert w.snapshot()["sent_msgs"] == 1


class TestNetioCell:
    def test_rate_and_totals_rendering(self):
        import importlib.util
        import os

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        spec = importlib.util.spec_from_file_location(
            "pbft_top", os.path.join(root, "tools", "pbft_top.py")
        )
        top = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(top)
        snap = {"transport": {"wire": {
            "sent_msgs": 300, "recv_msgs": 100,
            "sent_bytes": 200 * 1024, "recv_bytes": 56 * 1024,
        }}}
        prev = {"transport": {"wire": {
            "sent_msgs": 100, "recv_msgs": 100,
            "sent_bytes": 100 * 1024, "recv_bytes": 28 * 1024,
        }}}
        live = top.netio_cell(snap, prev, dt=2.0)
        assert live == "100/s 64K/s", live
        post = top.netio_cell(snap, None, dt=0.0)
        assert post == "400 256K", post
        assert top.netio_cell({"transport": {}}, None, 0.0) == ""
        assert "NETIO" in top.COLUMNS
