"""Deployment plane: TCP framing, deploy documents, process launcher.

These are the 582 LoC that landed untested in round 1 (VERDICT weak #4):
hostile/oversized frames, reconnect, outbox overflow, deploy round-trip,
and one real multi-process launch over localhost TCP.
"""

import asyncio
import json
import os
import subprocess
import sys

import pytest

from simple_pbft_tpu import deploy
from simple_pbft_tpu.transport.tcp import (
    MAX_FRAME,
    OUTBOX_DEPTH,
    TcpTransport,
    encode_frame,
)


def run(coro, timeout=60):
    return asyncio.run(asyncio.wait_for(coro, timeout))


async def _pair():
    """Two connected endpoints on ephemeral localhost ports."""
    a = TcpTransport("a", ("127.0.0.1", 0), peers={})
    b = TcpTransport("b", ("127.0.0.1", 0), peers={})
    await a.start()
    await b.start()
    a.peers["b"] = ("127.0.0.1", b.bound_port)
    b.peers["a"] = ("127.0.0.1", a.bound_port)
    return a, b


async def _stop_all(*ts):
    for t in ts:
        await t.stop()


class TestTcpFraming:
    def test_roundtrip_and_self_send(self):
        async def scenario():
            a, b = await _pair()
            try:
                payloads = [b"x", b"y" * 1000, b"z" * 100_000]
                for p in payloads:
                    await a.send("b", p)
                got = [await asyncio.wait_for(b.recv(), 10) for _ in payloads]
                assert got == payloads
                # self-send loops back without touching the network
                await a.send("a", b"self")
                assert await a.recv() == b"self"
                # unknown destination: fire-and-forget no-op
                await a.send("nobody", b"lost")
            finally:
                await _stop_all(a, b)

        run(scenario())

    def test_hostile_frames_close_connection_but_not_server(self):
        async def scenario():
            a, b = await _pair()
            try:
                for hostile in [
                    (0).to_bytes(4, "big"),  # zero-length frame
                    (MAX_FRAME + 1).to_bytes(4, "big") + b"x",  # oversized
                    b"\xff\xff",  # truncated header then close
                ]:
                    r, w = await asyncio.open_connection(
                        "127.0.0.1", b.bound_port
                    )
                    w.write(hostile)
                    await w.drain()
                    w.close()
                    await w.wait_closed()
                # the server must still accept well-formed traffic
                await a.send("b", b"still alive")
                assert await asyncio.wait_for(b.recv(), 10) == b"still alive"
            finally:
                await _stop_all(a, b)

        run(scenario())

    def test_raw_frame_bytes_layout(self):
        f = encode_frame(b"abc")
        assert f == b"\x00\x00\x00\x03abc"

    def test_reconnect_after_peer_restart(self):
        async def scenario():
            a, b = await _pair()
            b_port = b.bound_port
            try:
                await a.send("b", b"one")
                assert await asyncio.wait_for(b.recv(), 10) == b"one"
                # peer goes down; frames sent meanwhile are fire-and-forget
                await b.stop()
                await a.send("b", b"into the void")
                await asyncio.sleep(0.2)
                # peer comes back on the SAME port
                b2 = TcpTransport("b", ("127.0.0.1", b_port), peers={})
                await b2.start()
                for attempt in range(50):
                    await a.send("b", b"hello again %d" % attempt)
                    got = b2.recv_nowait()
                    if got is not None:
                        break
                    await asyncio.sleep(0.1)
                else:
                    raise AssertionError(
                        f"no frame after restart (reconnects="
                        f"{a.metrics['reconnects']})"
                    )
                await b2.stop()
            finally:
                await a.stop()

        run(scenario())

    def test_outbox_overflow_drops_not_blocks(self):
        async def scenario():
            # peer address that never answers: connect() fails fast on a
            # closed port, sender loop backs off, outbox fills
            a = TcpTransport("a", ("127.0.0.1", 0), peers={"ghost": ("127.0.0.1", 1)})
            await a.start()
            try:
                for i in range(OUTBOX_DEPTH + 100):
                    await a.send("ghost", b"frame %d" % i)
                assert a.metrics["dropped_outbox"] >= 100
            finally:
                await a.stop()

        run(scenario())

    def test_recv_queue_bound_drops(self):
        async def scenario():
            b = TcpTransport("b", ("127.0.0.1", 0), peers={}, recv_depth=2)
            await b.start()
            try:
                r, w = await asyncio.open_connection("127.0.0.1", b.bound_port)
                for i in range(10):
                    w.write(encode_frame(b"m%d" % i))
                await w.drain()
                await asyncio.sleep(0.3)
                assert b.metrics["recv"] == 10
                assert b.metrics["dropped_recv"] >= 8
                w.close()
            finally:
                await b.stop()

        run(scenario())


class TestDeployDocs:
    def test_generate_load_roundtrip(self, tmp_path):
        dep = deploy.generate(
            str(tmp_path), n=4, clients=2, base_port=7400,
            checkpoint_interval=16, view_timeout=5.0,
        )
        loaded = deploy.load(str(tmp_path / "committee.json"))
        assert loaded.cfg.replica_ids == dep.cfg.replica_ids == (
            "r0", "r1", "r2", "r3",
        )
        assert loaded.cfg.checkpoint_interval == 16
        assert loaded.cfg.view_timeout == 5.0
        assert loaded.addresses == dep.addresses
        assert loaded.cfg.pubkeys == dep.cfg.pubkeys
        assert loaded.peers_for("r0") == {
            k: v for k, v in loaded.addresses.items() if k != "r0"
        }
        for node in ["r0", "r1", "r2", "r3", "c0", "c1"]:
            seed = deploy.read_seed(str(tmp_path), node)
            assert len(seed) == 32

    def test_node_tpu_verifier_sized_and_warmed_from_deploy(self, tmp_path):
        """node.py's tpu backend must size the key bank to the deploy
        doc's key population and pre-register those keys (the jit table
        shape must never move under live traffic — round-4
        consensus-on-chip fix)."""
        from unittest import mock

        from simple_pbft_tpu.crypto.tpu_verifier import TpuVerifier
        from simple_pbft_tpu.node import make_verifier

        deploy.generate(str(tmp_path), n=4, clients=2, base_port=7410)
        dep = deploy.load(str(tmp_path / "committee.json"))
        # warm only the smallest bucket here: the full (8..512) boot
        # warm compiles 4 kernels (~minutes cold), covered by the chip
        # path; this test pins the sizing/registration contract
        real_warm = TpuVerifier.warm
        with mock.patch.object(
            TpuVerifier,
            "warm",
            lambda self, pubkeys=(), buckets=(8,): real_warm(
                self, pubkeys, (8,)
            ),
        ):
            svc = make_verifier("tpu", dep.cfg.pubkeys.values())
        # node.py wraps the device verifier in the coalescing service;
        # the sizing/registration contract lives on the device verifier
        v = svc.device
        n_keys = len(dep.cfg.pubkeys)
        assert len(v._bank._index) == n_keys  # all published keys cached
        cap = v._bank._cap
        assert cap >= n_keys + 32  # headroom for walk-in client keys
        # live traffic — including a WALK-IN key the deploy doc never
        # published — must not grow the table (growth = a fresh kernel
        # compile under the device lock mid-consensus)
        from simple_pbft_tpu.crypto import ed25519_cpu as ref
        from simple_pbft_tpu.crypto.verifier import BatchItem

        seed = b"\x77" * 32
        walkin = BatchItem(
            ref.public_key(seed), b"walk-in", ref.sign(seed, b"walk-in")
        )
        assert v.verify_batch([walkin]) == [True]
        assert len(v._bank._index) == n_keys + 1  # registered in place
        assert v._bank._cap == cap  # capacity (jit shape) unmoved

    def test_seed_files_hold_no_shared_secrets(self, tmp_path):
        deploy.generate(str(tmp_path), n=4, clients=1)
        doc = json.load(open(tmp_path / "committee.json"))
        blob = json.dumps(doc)
        for node in ["r0", "r1", "r2", "r3", "c0"]:
            seed = deploy.read_seed(str(tmp_path), node)
            assert seed.hex() not in blob  # document carries only pubkeys

    @pytest.mark.parametrize(
        "doc",
        [
            [],  # not an object
            {},  # no replicas
            {"replicas": {}},  # empty replicas
            {"replicas": {"r0": "nope"}},  # entry not an object
            {"replicas": {"r0": {"host": "x", "port": "NaN", "pubkey": ""}}},
            {"replicas": {"r0": {"host": "x", "port": 1, "pubkey": "zz"}}},
            {"replicas": {"r0": {"host": "x", "port": 1}}},  # missing pubkey
        ],
    )
    def test_malformed_documents_raise(self, tmp_path, doc):
        path = tmp_path / "committee.json"
        path.write_text(json.dumps(doc))
        with pytest.raises(ValueError):
            deploy.load(str(path))

    def test_short_seed_rejected(self, tmp_path):
        (tmp_path / "r0.seed").write_bytes(b"short")
        with pytest.raises(ValueError):
            deploy.read_seed(str(tmp_path), "r0")


class TestLaunchIntegration:
    def test_four_node_launch_commits_load(self, tmp_path):
        """The run.bat analog, for real: 4 replica processes + 1 client
        process over localhost TCP, 8 requests, f+1 reply matching."""
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"  # children must never touch the chip
        base_port = 7900 + (os.getpid() % 500)  # dodge stale-orphan ports
        out = subprocess.run(
            [
                sys.executable, "-m", "simple_pbft_tpu.launch",
                "-n", "4", "--load", "8",
                "--base-port", str(base_port),
                "--deploy-dir", str(tmp_path),
                "--keep",
            ],
            capture_output=True,
            text=True,
            timeout=240,
            env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        assert out.returncode == 0, (out.stdout[-800:], out.stderr[-800:])
        assert '"ops": 8' in out.stdout, out.stdout[-800:]
