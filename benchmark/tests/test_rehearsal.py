"""The harness end to end on the CPU rehearsal files (n=4), clean and
doctored; and a real cell refusing a host without a TPU."""

import json
import os
import subprocess
import sys

import pytest

import run

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
REHEARSAL = ["--workload", "rehearsal", "--seed", "3000000019", "--trace", "0"]


def _result(capsys, seconds="2"):
    run.main([*REHEARSAL, "--seconds", seconds])
    out = capsys.readouterr().out
    return json.loads(out.strip().splitlines()[-1]), out


def test_rehearsal_ends_in_the_contracts_line(capsys):
    import jax

    result, out = _result(capsys, seconds="3")
    assert set(result) == {"correct", "attempted", "failed", "metrics", "device"}
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert result["device"] == {"platform": "cpu", "kind": "cpu",
                                "count": len(jax.devices()),
                                "memory_peak_bytes": 0}
    assert set(result["metrics"]) == {
        "committed_req_per_s", "commit_latency_p50_ms",
        "commit_latency_p95_ms", "setup_s"}
    for metric in result["metrics"].values():
        assert set(metric) == {"value", "unit"} and metric["value"] > 0
    p50 = result["metrics"]["commit_latency_p50_ms"]["value"]
    assert p50 <= result["metrics"]["commit_latency_p95_ms"]["value"]
    assert "platform=cpu" in out and "post_warm_compiles=0" in out
    assert "check: failed" not in out


def test_a_flipped_planted_verdict_is_not_correct(monkeypatch, capsys):
    from simple_pbft_tpu.crypto.tpu_verifier import TpuVerifier

    real = TpuVerifier.verify_batch

    def lenient(self, items):
        got = real(self, items)
        if len(items) == 32:  # rehearsal-n4's kernel_check batch
            got[got.index(False)] = True
        return got

    monkeypatch.setattr(TpuVerifier, "verify_batch", lenient)
    result, out = _result(capsys)
    assert result["correct"] is False
    assert "oracle says False" in out


def test_a_compile_after_the_warm_up_is_not_correct(monkeypatch, capsys):
    from simple_pbft_tpu.crypto.coalesce import VerifyService

    real = VerifyService.snapshot

    def late_compile(self):
        snap = real(self)
        snap["device_shapes"] = {**snap["device_shapes"], "post_warm_compiles": 1}
        return snap

    monkeypatch.setattr(VerifyService, "snapshot", late_compile)
    result, out = _result(capsys)
    assert result["correct"] is False
    assert "a late compile was taken" in out and "'post_warm_compiles': 1" in out


def test_a_wrong_read_back_is_not_correct(monkeypatch, capsys):
    from simple_pbft_tpu.client import Client

    real = Client.submit

    async def stale(self, operation, retries=3):
        result = await real(self, operation, retries)
        return "stale" if operation.startswith("get ") else result

    monkeypatch.setattr(Client, "submit", stale)
    result, out = _result(capsys)
    assert result["correct"] is False
    assert "= 'stale', last acknowledged" in out


def test_a_real_cell_refuses_a_host_without_a_tpu():
    r = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "n64-inflight128",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr
    assert not any(line.startswith("{") for line in r.stdout.splitlines())


def test_an_unknown_cell_is_refused():
    with pytest.raises(SystemExit, match="no workload file"):
        run.main(["--workload", "no-such-cell"])
    with pytest.raises(SystemExit, match="not a name"):
        run.main(["--workload", "../configs/rehearsal-n4"])
