"""simple_pbft_tpu — a TPU-native PBFT consensus framework.

A from-scratch rebuild of the capabilities of the reference `simple_pbft`
(an educational pure-Go PBFT: three-phase pre-prepare/prepare/commit
consensus for an f=1 committee; see /root/reference, surveyed in SURVEY.md),
redesigned TPU-first:

- **Consensus plane** (pure Python, event-driven): per-sequence-number PBFT
  state machines (replacing the reference's single scalar ``CurrentState``,
  node.go:21), message pools keyed by (view, seq) (replacing the
  per-NodeID/per-ClientID pools in pool/*.go), an asyncio replica runtime
  with event-driven wakeups (replacing the 1 s polling tick, node.go:44,513),
  a client library with f+1 matching replies, checkpointing with h/H
  watermarks, and a full view-change protocol (the reference's view.go is
  dead code).

- **Crypto plane** (JAX/XLA/Pallas, the TPU-native part): every consensus
  message is Ed25519-signed (the reference has *no* signatures —
  see SURVEY.md §2.9), and signature verification — the hot path of any
  production PBFT — is batched and executed on TPU: pools drain pending
  (message, signature, pubkey) tuples into one vmapped Ed25519 verification
  pass, with GF(2^255-19) field arithmetic in limb-decomposed int32
  vector ops / Pallas kernels, returning a validity bitmap so
  quorum-certificate formation is one TPU call per round.
"""

import os

__version__ = "0.1.0"


def force_cpu() -> None:
    """Select the JAX CPU platform IN-PROCESS, before any backend
    initializes. The in-process switch wins over whatever JAX_PLATFORMS
    the environment carries, so a test or a ``--verifier cpu`` bench on a
    machine that has a chip never takes the chip from the one process
    that should hold it."""
    import jax

    jax.config.update("jax_platforms", "cpu")


def device_stamp() -> dict:
    """What JAX runs on, as JAX reports it (initializes the backend).
    Every record a bench or chip_smoke.py prints carries this, so a
    number can never be read without the device it came from."""
    import jax

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "device_count": len(devs),
    }


def select_platform(tpu: bool, what: str) -> dict:
    """Select the platform the caller (`what`, for the message) was asked
    for and return its device_stamp(). Not `tpu`: the CPU platform,
    in-process. `tpu`: JAX's default backend, which must BE a TPU — else
    a nonzero exit. A path that was asked for the chip never falls back
    to the CPU: a green run in which the chip did nothing is worse than
    a red one."""
    if not tpu:
        force_cpu()
        return device_stamp()
    stamp = device_stamp()
    if stamp["platform"] != "tpu":
        raise SystemExit(
            f"{what} needs a TPU but JAX reports platform="
            f"{stamp['platform']!r} ({stamp['device_kind']}, "
            f"{stamp['device_count']} device(s)); refusing to run it on "
            "the CPU under the tpu label"
        )
    return stamp


JIT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def enable_jit_cache() -> str:
    """Turn on JAX's persistent compilation cache so the crypto kernels
    compile once per machine, not once per process. Call before the
    first jit. Returns the directory in force.

    Where JAX_COMPILATION_CACHE_DIR is set JAX reads it itself and no
    directory is set in code; otherwise the cache is ONE fixed path
    inside the checkout (the path is part of what makes a later run find
    the entries, so nothing that varies between runs — uid, host, pid,
    time — may appear in it). tests/conftest.py, node.make_verifier, the
    benches and chip_smoke.py all come through here.

    Every compile is cached, however short: chip_smoke.py's second run
    must find EVERY bucket's program, and a threshold would leave the
    sub-second ones to recompile."""
    import jax

    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache:
        cache = JIT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache
