"""Bring-up contracts (ISSUE 21): the served path starts on the chip, in one
process, and nothing quietly runs a ``tpu`` path on the CPU.

- chip_smoke.py: the CPU dry run passes; doctored, it fails; without the
  flag on a host with no chip it exits nonzero and prints no result.
- bench_consensus.py --verifier tpu and launch.py -n 4 --verifier tpu
  refuse, nonzero, before doing anything.
- the compile cache is placed from outside or at one fixed path.
- the meshed fused verifier traces with the Pallas accumulator.
- the native loader decides freshness from the source's content.
"""

import ctypes
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402  (repo root; imports no jax at module level)
import simple_pbft_tpu  # noqa: E402


def _run(*argv):
    """A repo entry point in a child on the CPU-only platform the test
    environment exports (conftest sets JAX_PLATFORMS=cpu)."""
    return subprocess.run(
        [sys.executable, *argv], cwd=REPO, capture_output=True, text=True,
        timeout=120,
    )


# ---------------------------------------------------------------------------
# chip_smoke.py
# ---------------------------------------------------------------------------


def test_chip_smoke_cpu_dry_run_passes(capsys):
    import jax

    chip_smoke.main(["--cpu-dry-run"])
    lines = capsys.readouterr().out.strip().splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True, "dry_run": True,
        "device": {"platform": "cpu", "kind": "cpu",
                   "count": len(jax.devices())},
    }
    out = "\n".join(lines)
    assert "platform=cpu" in out and "dry_run=true" in out
    assert "agrees_with_oracle=true" in out
    assert "read_backs_equal=true replicas_agree=4" in out
    assert "post_warm_compiles=0" in out


def test_chip_smoke_fails_on_a_flipped_planted_verdict(monkeypatch, capsys):
    """The kernel stage compares with the oracle item by item: a device
    that lets ONE planted failure through fails the smoke."""
    from simple_pbft_tpu.crypto.tpu_verifier import TpuVerifier

    real = TpuVerifier.verify_batch

    def lenient(self, items):
        got = real(self, items)
        if len(items) == chip_smoke.DRY["kernel_batch"]:
            got[got.index(False)] = True
        return got

    monkeypatch.setattr(TpuVerifier, "verify_batch", lenient)
    with pytest.raises(chip_smoke.SmokeFailure, match="oracle says False"):
        chip_smoke.main(["--cpu-dry-run"])
    assert '"ok"' not in capsys.readouterr().out


def test_chip_smoke_fails_when_the_device_verified_nothing(monkeypatch, capsys):
    """A green served path whose verifying all happened on a CPU route is
    the failure the chip stage exists for."""
    from simple_pbft_tpu.crypto.coalesce import VerifyService

    real = VerifyService.snapshot

    def idle_device(self):
        return {**real(self), "device_pass_items": 0}

    monkeypatch.setattr(VerifyService, "snapshot", idle_device)
    with pytest.raises(chip_smoke.SmokeFailure, match="device_pass_items == 0"):
        chip_smoke.main(["--cpu-dry-run"])
    assert '"ok"' not in capsys.readouterr().out


def test_chip_smoke_without_the_flag_refuses_a_cpu_host():
    r = _run("chip_smoke.py")
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr
    assert '"ok"' not in r.stdout


# ---------------------------------------------------------------------------
# no tpu path on a CPU platform; one process per chip
# ---------------------------------------------------------------------------


def test_bench_consensus_verifier_tpu_refuses_a_cpu_host():
    r = _run("bench_consensus.py", "--verifier", "tpu", "--seconds", "1")
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr
    assert r.stdout == ""


def test_launch_refuses_several_nodes_on_one_chip(tmp_path):
    r = _run("-m", "simple_pbft_tpu.launch", "-n", "4", "--verifier", "tpu",
             "--deploy-dir", str(tmp_path / "dep"))
    assert r.returncode != 0
    assert "ONE process" in r.stderr
    assert not (tmp_path / "dep").exists()  # refused before deploying a node


# ---------------------------------------------------------------------------
# compile cache placement
# ---------------------------------------------------------------------------


def test_jit_cache_is_placed_from_outside_or_at_one_fixed_path(monkeypatch):
    import jax

    before = jax.config.jax_compilation_cache_dir
    try:
        # set from outside: JAX reads the variable itself, code sets nothing
        jax.config.update("jax_compilation_cache_dir", "/sentinel/untouched")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        assert simple_pbft_tpu.enable_jit_cache() == "/somewhere/else"
        assert jax.config.jax_compilation_cache_dir == "/sentinel/untouched"
        # unset: one fixed path inside the checkout, the same on every call
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = os.path.join(REPO, ".jax_cache")
        assert simple_pbft_tpu.enable_jit_cache() == want
        assert simple_pbft_tpu.enable_jit_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


# ---------------------------------------------------------------------------
# meshed fused verifier + Pallas accumulator (the trace path a TPU mesh takes)
# ---------------------------------------------------------------------------


def test_meshed_fused_verifier_traces_with_the_pallas_accumulator():
    """On a TPU mesh `auto` resolves to Pallas, which jax 0.9.0 refused to
    trace inside this shard_map; tier-1's CPU mesh resolved to the XLA
    loop and never saw it. Interpret mode takes the same trace path."""
    import jax
    from jax.sharding import Mesh

    from simple_pbft_tpu.crypto import ed25519_cpu as ref
    from simple_pbft_tpu.crypto.tpu_verifier import TpuVerifier
    from simple_pbft_tpu.crypto.verifier import BatchItem
    from simple_pbft_tpu.ops import comb

    items = []
    for i in range(12):
        seed = bytes([i % 4 + 1]) * 32
        msg = b"meshed pallas %d" % i
        items.append(BatchItem(ref.public_key(seed), msg, ref.sign(seed, msg)))
    items.append(BatchItem(items[0].pubkey, b"not the msg", items[0].sig))
    oracle = [ref.verify(i.pubkey, i.msg, i.sig) for i in items]
    mesh = Mesh(np.asarray(jax.devices()[:8]), ("dp",))
    try:
        comb.use_accum_impl("pallas_interpret")
        got = TpuVerifier(mesh=mesh).verify_batch(items)
        # "pallas" proper is Mosaic, and Mosaic cannot run here: an error,
        # never a silent drop to the interpreter
        comb.use_accum_impl("pallas")
        with pytest.raises(RuntimeError, match="needs a TPU"):
            TpuVerifier(mesh=mesh).verify_batch(items)
    finally:
        comb.use_accum_impl("auto")
    assert got == oracle == [True] * 12 + [False]


# ---------------------------------------------------------------------------
# native loader: freshness is the source's content
# ---------------------------------------------------------------------------


def test_native_loader_rebuilds_on_content_not_mtime(tmp_path):
    from simple_pbft_tpu import native

    src = tmp_path / "answer.cpp"
    src.write_text('extern "C" int answer() { return 1; }\n')
    stamp = (1_700_000_000, 1_700_000_000)
    os.utime(src, stamp)
    first = native._ensure_built(str(src))
    assert ctypes.CDLL(first).answer() == 1
    assert native._ensure_built(str(src)) == first  # same content: reused

    src.write_text('extern "C" int answer() { return 2; }\n')
    os.utime(src, stamp)  # the mtime says nothing changed
    second = native._ensure_built(str(src))
    assert second != first
    assert ctypes.CDLL(second).answer() == 2
    assert not os.path.exists(first)  # builds of older content are removed

    src.unlink()  # no source: nothing is loaded, whatever lies around
    assert native._ensure_built(str(src)) is None
