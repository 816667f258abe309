"""Sharded batch verify + quorum-certificate counting over a device mesh.

This is the framework's "training step": one fused device program that
  1. verifies a shard of the drained vote batch on each chip (the fixed
     Ed25519 ladder — pure VPU int32 work, no cross-chip traffic), and
  2. reduces per-instance valid-vote counts across the mesh with `psum`
     so every chip holds the replicated quorum tally.

The reference's analog is the per-vote loop inside `State.Prepare` /
`State.Commit` (pbft/consensus/pbft_impl.go:115-173) plus the pool-size
gates (pbft/network/node.go:393-420) — O(n) sequential vote checks per
round. Here the whole committee's pending votes for many in-flight
sequence numbers verify in one SPMD pass, and quorum formation is a single
ICI collective instead of mutex-guarded map counting.

Design notes (TPU-first):
- The batch axis is the only sharded axis (`dp`): signatures are
  embarrassingly parallel, so ICI carries just the (n_instances,) count
  vector — bytes, not signatures.
- Instance membership is a one-hot matrix so the tally is a matmul-shaped
  reduction, not a scatter (XLA-friendly, MXU-eligible for wide batches).
- Everything is constant-shape: callers must pad the batch to a multiple
  of the mesh size before sharding (shard_map rejects non-divisible
  batches at trace time).
"""

from __future__ import annotations

import time
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from .. import devledger
from ..crypto.tpu_verifier import verify_kernel
from ..ops import comb


def instrument_step(step, mesh: Mesh, mode: str = "ladder",
                    window: int = 4):
    """Wrap a jitted SPMD quorum step so every invocation lands in the
    device ledger as PER-DEVICE shard events (ISSUE 14): the 8-mesh
    shard-out inherits the exact schema the single-chip verify path
    records, day one — mode, bucket (the per-shard batch), pad, RTT,
    compile-vs-cache, host->device bytes.

    The wrapper BLOCKS on the result (``block_until_ready``) so the
    recorded RTT is dispatch->answer, like ``TpuVerifier``'s — callers
    that want async overlap should dispatch the raw step and record
    manually. ``n_valid`` is the pre-padding item count (pad waste);
    defaults to the full batch. Recording is per device because SPMD
    runs every chip for the whole pass: occupancy aggregates correctly
    only when busy seconds are attributed per device.
    """
    ndev = int(np.prod(mesh.devices.shape))
    seen_shapes: set = set()

    def run(*args, n_valid: Optional[int] = None):
        batch = next(
            (int(a.shape[-1]) for a in args
             if hasattr(a, "shape") and len(a.shape) == 1),
            0,
        )
        if batch == 0:  # no 1-D batch arg: run unrecorded, never raise
            return step(*args)
        bytes_up = sum(
            a.nbytes for a in args if isinstance(a, np.ndarray)
        )
        sig = (mode, window, batch)
        fresh = sig not in seen_shapes
        seen_shapes.add(sig)
        t0 = time.perf_counter()
        out = step(*args)
        out = jax.block_until_ready(out)
        rtt = time.perf_counter() - t0
        valid = batch if n_valid is None else int(n_valid)
        per = batch // ndev
        per_valid = valid // ndev
        rem = valid - per_valid * ndev
        for d in range(ndev):
            devledger.record(
                devledger.LANE_SHARD, mode, window, per,
                per_valid + (1 if d < rem else 0),
                # one SPMD trace = ONE XLA compile, not ndev: stamp it
                # on the first device row only so the lane's compile
                # counter matches reality
                rtt_s=rtt, compile_fresh=fresh and d == 0,
                bytes_up=bytes_up // ndev, bytes_down=per,
                device=f"d{d}",
            )
        return out

    return run


def make_comb_quorum_step(mesh: Mesh, axis: str = "dp"):
    """Build the jitted SPMD step for the comb engine (the fast path).

    Returns step(s_nib, k_nib, a_idx, a_table, b_table, r_y, r_sign,
                 precheck, inst_onehot) -> (verdict (B,) bool dp-sharded,
                                            counts (n_inst,) replicated)

    Per-item arrays shard over `axis` — their batch dimension is TRAILING
    (limb/position-major layout, see ops/field25519.py), so 2-D arrays
    use P(None, axis). The packed comb table banks replicate (they are
    the committee's keys — small and read-only, so replication costs HBM,
    not ICI). The quorum tally is the only cross-chip traffic: one psum
    of an (n_instances,) int32 vector.
    """
    vec = P(axis)  # (B,)
    mat = P(None, axis)  # (pos/limb, B)
    repl = P()

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(mat, mat, vec, repl, repl, mat, vec, vec, P(axis, None)),
        out_specs=(vec, repl),
    )
    def _step(s_nib, k_nib, a_idx, a_table, b_table, r_y, r_sign, precheck, onehot):
        verdict = comb.comb_verify_kernel(
            s_nib, k_nib, a_idx, a_table, b_table, r_y, r_sign, precheck
        )
        local = jnp.sum(onehot * verdict[:, None].astype(jnp.int32), axis=0)
        counts = jax.lax.psum(local, axis)
        return verdict, counts

    return jax.jit(_step)


def make_quorum_step(mesh: Mesh, axis: str = "dp"):
    """Build the jitted SPMD step for `mesh`.

    Returns step(a_y, a_sign, r_y, r_sign, s_bits, k_bits, precheck,
                 inst_onehot) -> (verdict (B,) bool sharded over dp,
                                  counts (n_instances,) int32 replicated)

    where inst_onehot is (B, n_instances) int32 mapping each vote to its
    consensus instance (all-zero rows = padding). Limb/bit-major arrays
    (a_y, r_y, s_bits, k_bits) have the batch axis trailing.
    """
    vec = P(axis)
    mat = P(None, axis)
    repl = P()

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(mat, vec, mat, vec, mat, mat, vec, P(axis, None)),
        out_specs=(vec, repl),
    )
    def _step(a_y, a_sign, r_y, r_sign, s_bits, k_bits, precheck, inst_onehot):
        verdict = verify_kernel(a_y, a_sign, r_y, r_sign, s_bits, k_bits, precheck)
        local = jnp.sum(
            inst_onehot * verdict[:, None].astype(jnp.int32), axis=0
        )
        counts = jax.lax.psum(local, axis)
        return verdict, counts

    return jax.jit(_step)
