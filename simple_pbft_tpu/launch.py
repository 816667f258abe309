"""Launcher: ``python -m simple_pbft_tpu.launch`` — the run.bat analog.

The reference ships a Windows-only batch script that builds two binaries,
starts 4 node processes and fires one client (run.bat:19-26). This
launcher generates a fresh deployment, spawns N replica processes, runs a
client workload against them, prints the client's stats line, and tears
everything down — cross-platform, any committee size.

Each node is its own process, and a chip belongs to one process: the
first node to touch JAX holds it and every other one fails at start-up
(on the v5e machine, within seconds: "Unable to initialize backend 'tpu':
ABORTED: ... libtpu multi-process lockfile"; PR 21 chip run). So
``--verifier tpu`` is refused for more than one node. The arrangement that drives the chip today is the in-process
committee — ``committee.LocalCommittee`` with every replica sharing one
``VerifyService``, as chip_smoke.py (through ``node.make_verifier``) and
bench_consensus.py build it; a verify-service process that replica
processes submit to is ROADMAP R7.
"""

from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import tempfile
import time


def main() -> None:
    ap = argparse.ArgumentParser(description="launch a local PBFT committee")
    ap.add_argument("-n", type=int, default=4, help="replica count")
    ap.add_argument("--load", type=int, default=16, help="client requests")
    ap.add_argument("--verifier", default="cpu")
    ap.add_argument("--transport", default="tcp", choices=["tcp", "grpc"])
    ap.add_argument("--base-port", type=int, default=7000)
    ap.add_argument("--deploy-dir", default=None, help="reuse/keep a deployment dir")
    ap.add_argument("--keep", action="store_true", help="don't delete the deploy dir")
    ap.add_argument("--trace", action="store_true",
                    help="enable the cross-replica trace plane on every "
                    "node (<deploy>/log/r*.spans.jsonl; join with "
                    "tools/slot_trace.py)")
    args = ap.parse_args()
    if args.verifier == "tpu" and args.n > 1:
        sys.exit(
            f"launch: --verifier tpu with -n {args.n} would start "
            f"{args.n} processes that each need the chip, and a chip "
            "belongs to ONE process (the rest fail at start-up on the "
            "libtpu lockfile). "
            "Run the committee in one process instead: "
            "`python chip_smoke.py` or `python bench_consensus.py "
            "--verifier tpu` (a LocalCommittee whose replicas share one "
            "VerifyService). Separate replica processes sharing a chip "
            "wait on ROADMAP R7."
        )

    from . import deploy

    deploy_dir = args.deploy_dir or tempfile.mkdtemp(prefix="pbft_deploy_")
    deploy.generate(deploy_dir, n=args.n, clients=1, base_port=args.base_port)
    print(f"deployment: {deploy_dir} (n={args.n}, f={(args.n - 1) // 3})")

    env = dict(os.environ)
    procs = []
    try:
        for i in range(args.n):
            procs.append(
                subprocess.Popen(
                    [
                        sys.executable, "-m", "simple_pbft_tpu.node",
                        "--id", f"r{i}",
                        "--deploy-dir", deploy_dir,
                        "--verifier", args.verifier,
                        "--transport", args.transport,
                    ] + (["--trace", "1"] if args.trace else []),
                    env=env,
                )
            )
        time.sleep(1.0)  # let listeners come up (reference slept 3 s)
        rc = subprocess.call(
            [
                sys.executable, "-m", "simple_pbft_tpu.client_cli",
                "--id", "c0",
                "--deploy-dir", deploy_dir,
                "--load", str(args.load),
                "--transport", args.transport,
            ],
            env=env,
        )
    finally:
        for p in procs:
            p.send_signal(signal.SIGTERM)
        for p in procs:
            try:
                p.wait(timeout=5)
            except subprocess.TimeoutExpired:
                p.kill()
        if not args.keep and args.deploy_dir is None:
            import shutil

            shutil.rmtree(deploy_dir, ignore_errors=True)
    sys.exit(rc)


if __name__ == "__main__":
    main()
