"""Benchmark: batched Ed25519 verification throughput on the attached chip.

Headline metric (BASELINE.md): Ed25519 verifies/sec on one chip; target is
>= 1,000,000/s (`vs_baseline` is value / 1e6 — the reference itself verifies
zero signatures, SURVEY.md §6, so the target ratio is the honest comparison).
This is the kernel alone — a layer metric, not the served path (for that,
chip_smoke.py and bench_consensus.py).

One process, which holds the chip: no probe child, no worker child.
Platform selection: without ``--smoke`` the run needs JAX's default
backend to be a TPU and exits nonzero when it is not; ``--smoke`` is the
explicit CPU run (tiny batch, selects the CPU platform in-process) and is
never chosen for you. The JSON line carries `platform`, `device_kind` and
`device_count`.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
The exit code is 0 only when every phase ran: a timeout (the watchdog), a
failed profiler capture or end-to-end phase, or any other exception still
prints the line — with an "error" field and the best rate measured so far —
and exits nonzero.

Methodology: sign a small set of distinct messages (pure-Python RFC 8032),
tile to the bench batch, stage prepared arrays on device, then time
steady-state jitted verify passes with block_until_ready. Compiles are
ramped (a small batch is compiled and timed first) so a pathological
compile fails fast. Host batch prep is timed and reported separately in
the JSON; the headline is device throughput (host prep overlaps with device
compute in the pipelined runtime — see crypto/tpu_verifier.py).

Env knobs: BENCH_BATCH (top batch size; capped at 8192 unless
BENCH_ALLOW_BIG=1), BENCH_SIGNERS, BENCH_TIMEOUT (wall-clock budget in
seconds, default 420), BENCH_MODE (fused|comb — fused is one gather + one
mixed add per nibble position, half the comb engine's madds), BENCH_WINDOW
(fused window bits, 4|5|6; default 5 — NOT what the served path builds:
TpuVerifier defaults to window=4 and every served-path constructor takes
that default, ROADMAP S1), BENCH_MUL (skew|padacc field-multiply
formulation), BENCH_ACCUM (auto|xla|pallas madd-loop implementation; auto =
pallas on a TPU), BENCH_PALLAS_TILE (batch lanes per Pallas program),
BENCH_RAMP (fast|full; default fast = one small fail-fast compile then the
top batch; full = the whole power-of-two ladder), BENCH_ROWPACK,
BENCH_PROFILE=<dir> (profiler trace of a few steady-state passes). The JSON
also reports e2e_verifies_per_sec: the overlapped host-prep + transfer +
device rate. The compile cache is simple_pbft_tpu.enable_jit_cache().
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

import numpy as np

_best = {"value": 0.0, "batch": 0, "note": "no measurement completed"}
# facts that must survive into a watchdog-truncated record (platform, mode,
# ...) — set as soon as known, merged into every emitted line
_sticky: dict = {}
_emit_lock = threading.Lock()
_emitted = False


def _emit(error: str | None = None, **extra) -> None:
    global _emitted
    with _emit_lock:
        if _emitted:
            return
        _emitted = True
        rec = {
            "metric": "ed25519_verifies_per_sec_per_chip",
            "value": round(_best["value"], 1),
            "unit": "verifies/s",
            "vs_baseline": round(_best["value"] / 1_000_000, 4),
            "batch": _best["batch"],
            "note": _best["note"],
        }
        rec.update(_sticky)
        if error is not None:
            rec["error"] = error[:500]
        rec.update(extra)
        # os.write on the raw fd: must succeed even if the main thread is
        # wedged inside a jaxlib C call holding buffered-stdout state.
        os.write(1, (json.dumps(rec) + "\n").encode())


def _start_watchdog(budget: float) -> None:
    """SIGALRM can't preempt a blocking jaxlib C call (compile /
    block_until_ready). A daemon thread + os._exit actually fires. The
    line it prints carries the best rate measured so far; the exit code
    says the run did not finish."""

    def fire():
        time.sleep(max(1.0, budget))
        _emit(error=f"timeout after {budget:.0f}s: {_best['note']}")
        os._exit(1)

    threading.Thread(target=fire, daemon=True).start()


def _measure(fn, arrays, batch: int, min_s: float, max_iters: int) -> float:
    """Steady-state verifies/s for a compiled fn at this batch size."""
    out = fn(*arrays)
    out.block_until_ready()  # warm pass (post-compile)
    iters = 0
    t0 = time.perf_counter()
    while True:
        out = fn(*arrays)
        iters += 1
        if iters >= max_iters or (
            iters >= 3 and time.perf_counter() - t0 > min_s
        ):
            break
    out.block_until_ready()
    elapsed = time.perf_counter() - t0
    return batch * iters / elapsed


def main() -> None:
    budget = float(os.environ.get("BENCH_TIMEOUT", "420"))
    _start_watchdog(budget)
    t_start = time.perf_counter()

    # The note rides along in the timeout JSON — keep it pointing at the
    # exact stage so a wedged run says *where* it wedged.
    _best["note"] = "initializing jax backend"
    import jax

    import simple_pbft_tpu

    # a re-run after a timeout skips straight to measuring
    simple_pbft_tpu.enable_jit_cache()

    # the platform is decided HERE, before anything is signed or built
    # (module docstring): --smoke selects the CPU itself, everything
    # else needs the chip and says so with a nonzero exit
    smoke = "--smoke" in sys.argv
    if smoke:
        os.environ.setdefault("BENCH_BATCH", "64")
    stamp = simple_pbft_tpu.select_platform(
        not smoke, "bench.py (without --smoke)"
    )
    platform = stamp["platform"]
    _sticky.update(stamp)

    from simple_pbft_tpu.ops import field25519 as fe

    mul_impl = os.environ.get("BENCH_MUL", "padacc")
    fe.use_mul_impl(mul_impl)  # must precede any jit trace

    from simple_pbft_tpu.ops import comb

    accum_impl = os.environ.get("BENCH_ACCUM", "auto")
    comb.use_accum_impl(accum_impl)
    comb.PALLAS_TILE = int(os.environ.get("BENCH_PALLAS_TILE", comb.PALLAS_TILE))
    from simple_pbft_tpu.crypto import ed25519_cpu as ref
    from simple_pbft_tpu.crypto.verifier import BatchItem
    from simple_pbft_tpu.crypto.tpu_verifier import (
        BUCKETS,
        KeyBank,
        prepare_comb_batch,
        prepare_wire_batch,
    )

    mode = os.environ.get("BENCH_MODE", "fused")
    assert mode in ("fused", "comb"), mode
    # comb mode is fixed at 4-bit windows; report what actually runs.
    # Default window is 5 (builder-recorded 2026-07-31 A/B, before PRs
    # 1-20, not reproduced: w4 610k / w5 777k / skew 322k verifies/s,
    # bench_results/chip_r04.jsonl). The served path builds window 4.
    wbits = int(os.environ.get("BENCH_WINDOW", "5")) if mode == "fused" else 4
    # BENCH_ROWPACK=1: 15-bit limb pairs share an int32 in the table
    # rows (128-byte rows instead of 256), halving the madd gather's HBM
    # traffic for two shift/mask ops per element — fused mode only. The
    # switch must precede KeyBank construction and every jit trace.
    rowpack = mode == "fused" and os.environ.get("BENCH_ROWPACK", "0") == "1"
    comb.use_row_packing(rowpack)
    _sticky.update(mode=mode, window=wbits, mul=mul_impl, rowpack=rowpack)
    _best["note"] = f"devices up ({platform}); preparing batch"
    top_batch = int(os.environ.get("BENCH_BATCH", str(BUCKETS[-1])))
    # comb kernel's batch inversion needs a power-of-two batch
    top_batch = 1 << max(0, top_batch - 1).bit_length()
    if top_batch > BUCKETS[-1] and os.environ.get("BENCH_ALLOW_BIG") != "1":
        print(
            f"capping batch {top_batch} -> {BUCKETS[-1]} "
            "(BENCH_ALLOW_BIG=1 to override)",
            file=sys.stderr,
        )
        top_batch = BUCKETS[-1]
    # committee-shaped workload: 16 signers (BASELINE config 2), distinct
    # messages per signer
    n_signers = int(os.environ.get("BENCH_SIGNERS", "16"))
    distinct = min(top_batch, 64)

    items = []
    for i in range(distinct):
        seed = bytes([i % n_signers]) * 32
        msg = b"bench vote %d" % i
        items.append(BatchItem(ref.public_key(seed), msg, ref.sign(seed, msg)))

    bank = KeyBank(mode=mode, window=wbits)
    _best["note"] = f"building {mode} key tables ({n_signers} keys)"
    t0 = time.perf_counter()
    for it in items:
        bank.lookup(it.pubkey)  # warm the bank: table build is one-time
    table_build_s = time.perf_counter() - t0

    # host prep cost, measured WARM at the top batch size (the per-item
    # number a pipelined replica actually pays; a cold 64-item batch
    # overstates it ~20x in fixed overheads)
    prepare = prepare_wire_batch if mode == "fused" else prepare_comb_batch
    items_top = items * (top_batch // distinct)
    prepare(items_top, bank)  # warm
    t0 = time.perf_counter()
    for _ in range(3):
        prep, _fallback = prepare(items_top, bank)
    prep_per_item_us = (time.perf_counter() - t0) / 3 / len(items_top) * 1e6

    prep, _fallback = prepare(items, bank)
    base_arrays = prep.arrays()
    tables = bank.device_tables()

    # The key tables are an ARGUMENT of the jitted fn, never a closure
    # capture: a closed-over array is embedded in the lowered program as a
    # constant, and XLA's constant handling scales with its bytes — the
    # fused bank is 67 MB at w=4 but 720 MB at w=6 (16 keys x 45 MB),
    # which pushed the w=6 compile past any sane budget. As a parameter
    # the table costs one transfer and zero compile time.
    if mode == "comb":
        b_table = comb.base_table_device()
        const_args = (tables, b_table)

        def fn(tables, b_table, s_nib, k_nib, a_idx, r_y, r_sign, precheck):
            return comb.comb_verify_kernel(
                s_nib, k_nib, a_idx, tables, b_table, r_y, r_sign, precheck
            )
    else:
        # fused staging is the WIRE path (raw (B, 96) uint8 on the link,
        # window/limb unpack fused into the kernel prologue) — the same
        # program TpuVerifier runs under consensus traffic
        const_args = (tables,)

        def fn(tables, wire, a_idx, precheck):
            return comb.fused_verify_wire_kernel(
                wire, a_idx, tables, precheck, window=1 << wbits
            )

    fn = jax.jit(fn)

    def effective(batch: int) -> int:
        return distinct * max(1, batch // distinct)

    # batch axis: trailing on comb's prepared arrays, LEADING on wire's
    stage_axis = 0 if mode == "fused" else -1

    def staged(batch: int):
        reps = batch // distinct
        return [
            *const_args,
            *(
                jax.device_put(np.concatenate([a] * reps, axis=stage_axis))
                for a in base_arrays
            ),
        ]

    # Ramp: compile small first so a runaway compile fails inside the
    # watchdog window with a useful note, then step up through
    # power-of-two batches while time and measured rate justify it.
    # Default is the fast ramp — two compiles is the quickest route to a
    # steady-state number.
    ramp = os.environ.get("BENCH_RAMP", "fast")
    assert ramp in ("fast", "full"), ramp
    if ramp != "full":
        # one small fail-fast compile, then the top batch
        ladder = sorted({effective(min(64, top_batch)), effective(top_batch)})
    else:
        ladder = sorted(
            {
                effective(b)
                for b in (min(64, top_batch), top_batch, *BUCKETS)
                if b <= top_batch
            }
            | {effective(top_batch)}
        )
    compile_s = {}
    best_note = _best["note"]
    for batch in ladder:
        remaining = budget - (time.perf_counter() - t_start)
        # the first compile is the slow one; later ones re-tile the same
        # kernel. Leave margin: skip the step if under 25% of budget left.
        if remaining < 0.25 * budget and compile_s:
            best_note += f"; skipped batch>={batch} (time budget)"
            break
        arrays = staged(batch)
        _best["note"] = f"compiling batch={batch} on {platform}; best: {best_note}"
        t0 = time.perf_counter()
        verdict = np.asarray(fn(*arrays))
        compile_s[batch] = time.perf_counter() - t0
        assert verdict.all(), "bench batch must verify valid"
        _best["note"] = f"measuring batch={batch} on {platform}; best: {best_note}"
        rate = _measure(fn, arrays, batch, min_s=2.0, max_iters=30)
        if rate > _best["value"]:
            _best["value"] = rate
            _best["batch"] = batch
            best_note = f"batch={batch} on {platform}"
        _best["note"] = best_note
        print(
            f"batch={batch} rate={rate:,.0f}/s compile={compile_s[batch]:.1f}s",
            file=sys.stderr,
        )
    _best["note"] = best_note

    # Optional profiler capture (SURVEY.md §5: "JAX profiler traces for
    # the verify kernel"): BENCH_PROFILE=<dir> records a trace of a few
    # steady-state passes at the best batch, viewable in TensorBoard /
    # Perfetto. Asked for means required: a failed capture fails the run.
    profile_dir = os.environ.get("BENCH_PROFILE")
    if profile_dir and _best["batch"]:
        arrays = staged(_best["batch"])
        with jax.profiler.trace(profile_dir):
            for _ in range(3):
                out = fn(*arrays)
            out.block_until_ready()
        print(f"profiler trace written to {profile_dir}", file=sys.stderr)

    # End-to-end: the full verify path per batch — host prep (wire bytes ->
    # arrays, native SHA-512 challenges), host->device transfer, kernel
    # dispatch. Dispatches are async, so the device verifies batch k while
    # the host preps batch k+1 — the overlap the pipelined runtime gets.
    e2e_rate = 0.0
    e2e_pipe_rate = 0.0
    if _best["batch"]:
        b_best = _best["batch"]
        items_big = items * (b_best // distinct)
        _best["note"] = f"e2e at batch={b_best}; best: {best_note}"

        def put_dispatch(arrays):
            return fn(*const_args, *(jax.device_put(a) for a in arrays))

        def e2e_loop(dispatch, finish) -> float:
            """One closed prepare->dispatch loop; `finish(last)` blocks
            on the final in-flight work. Shared by the serial and
            pipelined variants so the cutoff policy lives once."""
            last = None
            iters = 0
            t0 = time.perf_counter()
            while iters < 50 and (
                iters < 3 or time.perf_counter() - t0 < 3.0
            ):
                prep_i, _fb = prepare(items_big, bank)
                last = dispatch(prep_i.arrays(), last)
                iters += 1
            finish(last)
            return b_best * iters / (time.perf_counter() - t0)

        def remaining() -> float:
            return budget - (time.perf_counter() - t_start)

        # Budget-checked so a slow-prep config can't ride into the
        # watchdog: a SKIPPED phase reports null, a FAILED one raises
        # and fails the run.
        if remaining() > 0.10 * budget:
            e2e_rate = e2e_loop(
                lambda arrays, _prev: put_dispatch(arrays),
                lambda last: last.block_until_ready(),
            )
        # Pipelined: host prep of batch k+1 overlaps transfer + device
        # pass of batch k (a worker thread owns put+dispatch; JAX
        # dispatch is thread-safe) — the overlap the replica runtime's
        # two-worker verify pipeline gets for free.
        if remaining() > 0.10 * budget:
            from concurrent.futures import ThreadPoolExecutor

            with ThreadPoolExecutor(1) as pool:

                def disp(arrays, prev):
                    if prev is not None:
                        prev.result()  # keep queue depth at 1
                    return pool.submit(put_dispatch, arrays)

                e2e_pipe_rate = e2e_loop(
                    disp,
                    lambda last: last.result().block_until_ready(),
                )
        _best["note"] = best_note

    print(
        f"host_prep={prep_per_item_us:.1f}us/item "
        f"table_build={table_build_s:.1f}s device={platform} "
        f"best={_best['value']:,.0f}/s e2e={e2e_rate:,.0f}/s ({_best['note']})",
        file=sys.stderr,
    )
    _emit(
        host_prep_us_per_item=round(prep_per_item_us, 2),
        # null = not measured (budget skip) — a literal 0.0 would read
        # as a catastrophic regression in the jsonl record
        e2e_verifies_per_sec=round(e2e_rate, 1) if e2e_rate else None,
        e2e_pipelined_verifies_per_sec=(
            round(e2e_pipe_rate, 1) if e2e_pipe_rate else None
        ),
        table_build_s=round(table_build_s, 1),
        staging="wire" if mode == "fused" else "prep",
        mode=mode,
        window=wbits,
        mul=mul_impl,
        # what actually ran, not "auto"; comb mode has no Pallas path
        accum=comb._resolve_accum_impl() if mode == "fused" else "xla",
    )


if __name__ == "__main__":
    try:
        main()
    except BaseException as e:  # noqa: BLE001 — emit the JSON line, then fail
        if not isinstance(e, SystemExit):
            _emit(error=f"{type(e).__name__}: {e}")
        raise
