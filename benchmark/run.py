"""benchmark/run.py - one cell of the benchmark, once, in one process.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

finds ``workloads/<cell>.json``, from it ``configs/<config>.json``, and every
``metrics/*.json`` whose ``cells`` is absent or lists the cell. Adding a
cell, a configuration or a per-layer metric is adding such a file (and its
entry in BENCHMARK.json): nothing here names one.

Set-up (``setup_s``: start of the process to start of the window) is
identify -> hold the configuration's stated keys to what the harness builds
(``stages.check_config``: a key it cannot build is refused) -> warm the
verifier -> planted-failure batch against the oracle -> in a certificate
committee, a planted-failure batch of certificates through the lane ->
start the committee -> a few seconds of the cell's own traffic, unmeasured.
Then the window: closed-loop puts for ``--seconds``. Then drain, read back,
and hold the committee to its configuration's guarantees (``correct``); the
certificates' verdicts meet their plain reference (``bls_ref.py``) only
then, outside set-up.
``--trace 1`` is a run of its own with the JAX profiler open for a few
seconds in the middle of the window; it reports the per-layer metrics, the
device's busy seconds and a breakdown. ``--trace 0`` starts no profiler and
reports the end-to-end metrics.

The last line of stdout is the one JSON object the driver reads. A real
cell on anything but a TPU exits nonzero and prints no such line.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # before the heavy imports: they are set-up

import argparse  # noqa: E402
import asyncio  # noqa: E402
import faulthandler  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
for _p in (os.path.dirname(HERE), HERE):  # the program's package; our modules
    if _p not in sys.path:
        sys.path.insert(0, _p)

import certificates  # noqa: E402
import stages  # noqa: E402
import trace_reduce  # noqa: E402

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
# a first run compiles and may take 1,200 s: past this a wedge becomes every
# thread's stack on stderr and a nonzero exit
TIME_LIMIT_S = 1150


def load(kind: str, name: str) -> dict:
    if not NAME.fullmatch(name):
        raise SystemExit(f"{kind}: {name!r} is not a name")
    path = os.path.join(HERE, kind, name + ".json")
    if not os.path.exists(path):
        raise SystemExit(f"no {kind[:-1]} file {path}")
    with open(path) as fh:
        return json.load(fh)


def metric_specs(cell_name: str) -> list:
    specs = []
    for path in sorted(glob.glob(os.path.join(HERE, "metrics", "*.json"))):
        with open(path) as fh:
            spec = json.load(fh)
        if "cells" not in spec or cell_name in spec["cells"]:
            specs.append(spec)
    return specs


def device_peaks(stamp: dict) -> dict:
    """This chip's row of peaks.json. A kind that is not there is an error."""
    with open(os.path.join(HERE, "peaks.json")) as fh:
        table = json.load(fh)["peaks"]
    if stamp["device_kind"] not in table:
        raise SystemExit(
            f"device_kind {stamp['device_kind']!r} is not in peaks.json "
            f"({sorted(table)}): add its published peaks, do not guess")
    return table[stamp["device_kind"]]


class Profiler:
    """jax.profiler around part of the window. Failures raise (the
    program's devledger.arm_profile swallows them, so it is not used). The
    Python tracer is off: it would record every call of the 64 replicas'
    event loop and slow the thing it measures."""

    def __init__(self, trace_dir: str) -> None:
        self.trace_dir = trace_dir

    def start(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)

    def stop(self) -> None:
        import jax

        jax.profiler.stop_trace()


def read_metric(spec: dict, seen: dict):
    """One per-layer metric from what the window left behind. ``source`` is
    ``span:<stage>:<p50|p90|p99|mean|sum>``, ``counter:<surface>.<key>`` or
    ``trace:<field>``; optional ``per`` divides and ``scale`` multiplies.
    Nothing to read = None, and the metric is left out of the line."""
    kind, _, rest = spec["source"].partition(":")
    if kind == "span":
        stage, _, stat = rest.rpartition(":")
        summary = seen["spans"].get(stage)
        if not summary or not summary["count"]:
            return None
        # logutil.Histogram.summary() has no sum of its own
        value = (summary["mean"] * summary["count"] if stat == "sum"
                 else summary[stat])
    elif kind == "counter":
        surface, _, key = rest.partition(".")
        value = seen["counters"].get(surface, {}).get(key)
    elif kind == "trace":
        value = seen["trace"].get(rest)
    else:
        raise SystemExit(f"metric {spec['name']}: no reader for "
                         f"{spec['source']!r}")
    if value is None:
        return None
    if "per" in spec:
        divisor = seen["divisors"].get(spec["per"])
        if not divisor:
            return None
        value = value / divisor
    return value * spec.get("scale", 1)


def run(args) -> dict:
    cell = load("workloads", args.workload)
    config = load("configs", cell["config"])
    specs = metric_specs(args.workload)
    what = f"benchmark cell {args.workload}"
    checks = stages.Checks()

    stamp = stages.identify(config["platform"], cell["chips"], what)
    if stamp["platform"] == "tpu":
        stages.say("identify", peaks=json.dumps(device_peaks(stamp)))
    stages.say("identify", cell=args.workload, config=cell["config"],
               seed=args.seed, seconds=args.seconds, trace=args.trace,
               cores=os.cpu_count())

    from simple_pbft_tpu.config import make_test_committee

    qc = stages.qc_mode(config)
    cfg, keys = make_test_committee(n=config["n"], clients=config["clients"],
                                    qc_mode=qc)
    pubkeys = [kp.pub for kp in keys.values()]
    stages.check_config(config, cfg, len(pubkeys))
    service = stages.build_verifier(config, pubkeys)
    try:
        stages.check_config(config, cfg, len(pubkeys), service)
    except SystemExit:
        service.close()
        raise
    stages.kernel_stage(config, args.seed, keys, service.device, stamp, checks)
    lane_checked = certificates.gate(args.seed, cfg, keys) if qc else []

    trace_dir = tempfile.mkdtemp(prefix="benchmark_trace_") if args.trace else None
    try:
        served = asyncio.run(stages.served_stage(
            cell, config, args.seed, args.seconds, service, pubkeys, checks,
            profiler=Profiler(trace_dir) if args.trace else None,
        ))
        stages.chip_stage(service, stamp, checks)
        service.close()
        certificates.compare(lane_checked, config, checks)
        trace: dict = {}
        if args.trace:
            try:
                trace = trace_reduce.reduce_file(
                    trace_reduce.find_xplane(trace_dir), **served["traced"])
            except trace_reduce.NoDevicePlane:
                if stamp["platform"] == "tpu":
                    raise
                stages.say("trace", device_plane="none (cpu rehearsal)")
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)

    setup_s = served["t_start"] - T_PROCESS
    window = served["window"]
    measured = dict(window["metrics"])
    measured["setup_s"] = (setup_s, "s")
    stages.say("window", **{k: round(v, 3) for k, (v, _u) in measured.items()})

    verify = served["counters"]["verify"]
    seen = {
        "spans": served["spans"],
        "counters": served["counters"],
        "trace": trace,
        "divisors": {
            "committed": window["committed"],
            "device_items": verify.get("device_pass_items", 0),
            "device_passes": verify.get("device_passes", 0),
            "verified_items": (verify.get("device_pass_items", 0)
                               + verify.get("cpu_pass_items", 0)),
        },
    }
    if args.trace:
        metrics = {}
        for spec in specs:
            value = read_metric(spec, seen)
            if value is not None:
                metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}
    else:
        metrics = {name: {"value": value, "unit": unit}
                   for name, (value, unit) in measured.items()}

    import jax

    stats = [d.memory_stats() or {} for d in jax.devices()]
    device = {
        "platform": stamp["platform"],
        "kind": stamp["device_kind"],
        "count": stamp["device_count"],
        "memory_peak_bytes": max(s.get("peak_bytes_in_use", 0) for s in stats),
    }
    result = {
        "correct": not checks.problems,
        "attempted": window["attempted"],
        "failed": window["failed"],
        "metrics": metrics,
        "device": device,
    }
    if trace:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        stages.say("trace", idle_share_pct=round(trace["idle_share"], 3),
                   module_events=trace["module_events"],
                   device_items=served["traced"]["device_items"])
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    return result


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds the kernel batch and the values written")
    ap.add_argument("--seconds", type=float, default=10.0,
                    help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    faulthandler.dump_traceback_later(TIME_LIMIT_S, exit=True,
                                      file=sys.__stderr__)
    try:
        result = run(args)
    finally:
        faulthandler.cancel_dump_traceback_later()
    stages.say("done", wall_s=round(time.perf_counter() - T_PROCESS, 1),
               correct=str(result["correct"]).lower())
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
