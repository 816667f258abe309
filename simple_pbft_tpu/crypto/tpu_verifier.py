"""Batched Ed25519 verification on TPU — the flagship compute path.

The reference has no signatures at all (SURVEY.md §2.1: grep over
/root/reference finds only SHA-256 in utils/utils.go:13-17), yet every
production PBFT spends its hot path verifying O(n) votes per round per node
(the quorum predicates at pbft/consensus/pbft_impl.go:207-232 are where
those verifies would sit). This module fills that gap TPU-first:

- The consensus plane drains every pending (pubkey, message, signature)
  tuple into one batch.
- Host prep is one Python pass over the pile (byte joins, the key
  bank's dict lookup) and ONE call into the native library
  (simple_pbft_tpu/native/: prepare_wire), which hashes the challenge
  scalars k = SHA-512(R||A||M) mod L, applies the canonicality reject
  policy and writes the padded (B, 96) rows: the dispatcher's thread
  shares the interpreter lock with the event loop, and every numpy step
  that gave the lock up cost a wait to get it back. The raw (B, 96)
  bytes go to the device, which unpacks windows and limbs itself.
- One jitted device pass per batch (the fused comb kernel — see
  ops/comb.py). Constant shapes, no data-dependent control flow — every
  signature costs the same fixed sequence, so XLA compiles one kernel
  per bucket size.
- Device arrays are limb-major / batch-minor ((17, B) etc., see
  ops/field25519.py) so the batch fills the vector lanes.
- Batches are padded to bucketed sizes (powers of two) so recompiles are
  bounded; the verdict bitmap maps back per item, so one bad signature
  never poisons a quorum that still holds 2f+1 valid votes (SURVEY.md §7
  "Correct Byzantine semantics under batching").
- A key the bank has no room for (more signers than the device's share of
  tables) takes the table-free program (ops/ladder.py) in the same pass:
  its rows go along with the key's 32 bytes, the device decompresses the
  key and runs a windowed ladder, and one finisher merges the two
  programs' verdicts. Which program a row takes follows from whether its
  key has a table; a pile with no such row launches the comb alone.

Verification equation (cofactorless, RFC 8032 permits): [S]B == R + [k]A,
rearranged to [S]B + [k](−A) == R so the device computes a single
double-scalar multiplication and an equality — no second ladder.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import native
from ..ops import comb, ladder
from . import ed25519_cpu as ref
from .verifier import BatchItem

# Bucketed batch sizes: drained pools are padded up to the next bucket so
# XLA compiles at most len(BUCKETS) kernels, never one per batch size.
BUCKETS = (8, 32, 128, 512, 2048, 8192)

# The largest bucket whose pile is hashed on the calling thread with the
# interpreter lock HELD. 512 items are under 0.3 ms of hashing; giving the
# lock up costs up to the interpreter's 5 ms switch interval to get it
# back from a busy event loop, and waking the OpenMP pool 0.35-0.65 ms when
# passes come 20 ms apart (a 130-item pile: 0.08 ms hot, the sandbox's
# CPU, ISSUE 33). A larger pile releases the lock and fans out.
LOCK_HELD_BUCKET = 512

_L_BYTES = ref.L.to_bytes(32, "little")

_ZERO32 = bytes(32)
_ZERO64 = bytes(64)


# ---------------------------------------------------------------------------
# The numpy staging's canonicality checks (the native call has its own;
# these run where the library is absent, and the tests compare the two)
# ---------------------------------------------------------------------------


def _ge_p_np(y_bytes: np.ndarray) -> np.ndarray:
    """(n, 32) uint8 little-endian, bit 255 ignored -> (n,) bool: is the
    encoded y non-canonical (y >= p)? p = 2^255 - 19, so y >= p iff bits
    1..254 are all ones and the low byte is >= 0xed."""
    mid_all_ones = (y_bytes[:, 1:31] == 0xFF).all(axis=1)
    top_ok = (y_bytes[:, 31] & 0x7F) == 0x7F
    low_ok = y_bytes[:, 0] >= 0xED
    return mid_all_ones & top_ok & low_ok


def _ge_l_np(s_bytes: np.ndarray) -> np.ndarray:
    """(n, 32) uint8 little-endian -> (n,) bool: S >= L (non-canonical,
    malleable — reject). Lexicographic compare from the most significant
    byte down, vectorized."""
    l_arr = np.frombuffer(_L_BYTES, dtype=np.uint8)
    gt = np.zeros(len(s_bytes), dtype=bool)
    undecided = np.ones(len(s_bytes), dtype=bool)
    for i in range(31, -1, -1):
        b = s_bytes[:, i]
        gt |= undecided & (b > l_arr[i])
        undecided &= b == l_arr[i]
    return gt | undecided  # equal counts as >= L


def _bucket_size(n: int) -> int:
    for b in BUCKETS:
        if n <= b:
            return b
    return BUCKETS[-1]


# ---------------------------------------------------------------------------
# Host prep: committee pubkey table bank + per-batch wire staging
# ---------------------------------------------------------------------------


# One key's table on the device: comb.ROWS_PER_KEY Niels rows of 64 int32
# words, 4 MiB.
KEY_BYTES = comb.ROWS_PER_KEY * comb.ROW * 4
# The flat table is indexed in int32: it stays under 2^31 elements (a
# power-of-two capacity of 2,048 keys is exactly 2^31, and 8.6 GB).
MAX_INDEXED_KEYS = (2**31 - 1) // (comb.ROWS_PER_KEY * comb.ROW)
# Up to here a sized bank's capacity is the next power of two, as it
# always was (128 keys for an n=64 committee with 8 clients, 64 for
# n=16). Past it a power of two wastes up to half the table (1,096 keys
# -> 2,048), so capacity goes up in granules of KEY_GRANULE keys (1,096
# -> 1,152, 4.83 GB).
POW2_KEYS = 512
KEY_GRANULE = 128
# The share of the device's memory (memory_stats()["bytes_limit"]) the
# tables may take. Beside the table the six buckets' working set is
# small (64 positions x 8,192 rows x 512 B = 268 MB gathered at the
# largest bucket, 544 MB of temporaries in all), but a key registered
# after the upload sends the whole table again, and the old copy lives
# until the new one has landed: two tables and the working set have to
# fit.
TABLE_SHARE = 0.45
# Where the platform reports no memory (the CPU): 2 GiB, 512 keys.
DEFAULT_TABLE_BYTES = 2 << 30


def bank_capacity(population: int, device_bytes: Optional[int]) -> int:
    """Keys a bank holds for a published `population` (keys plus headroom)
    on a device of `device_bytes` (None: the platform reports none). The
    population rounded up (a power of two up to POW2_KEYS, KEY_GRANULE
    past it), bounded by TABLE_SHARE of the device and by the int32
    index. A population over the bound gets the bound: its over-cap keys
    report UNCACHED and verify on the device by the table-free ladder."""
    if population <= POW2_KEYS:
        want = 1 << max(3, int(population - 1).bit_length())
    else:
        want = -(-population // KEY_GRANULE) * KEY_GRANULE
    budget = (
        DEFAULT_TABLE_BYTES if device_bytes is None
        else int(device_bytes * TABLE_SHARE)
    )
    return max(1, min(want, budget // KEY_BYTES, MAX_INDEXED_KEYS))


class KeyBank:
    """Cache of per-pubkey fused comb tables (the committee's key set).

    PBFT pubkeys are few and endlessly reused, so each is decompressed and
    expanded into Niels rows once on the host (exact bigints) and kept on
    device, KEY_BYTES a key. A verifier built for a published population
    (TpuVerifier(initial_keys=...)) fixes the capacity at construction
    (bank_capacity) and gives it as `max_keys` too, so the table's shape,
    and with it the jit signature, never moves. Only an unsized bank
    grows, in powers of two up to `max_keys`.

    `max_keys` bounds the bank: a Byzantine sender must not be able to
    grow device memory and force recompiles by spraying fresh valid curve
    points through the Verifier seam. Keys beyond the cap report UNCACHED
    and cost the host nothing further: no decompression, no state. Their
    rows take the table-free ladder (ops/ladder.py), which decompresses
    the key on the device.
    """

    UNCACHED = -2

    # An unsized bank's bound: DEFAULT_TABLE_BYTES of tables, which is
    # also what a sized one may take where the platform reports no memory.
    MAX_KEYS = DEFAULT_TABLE_BYTES // KEY_BYTES

    def __init__(self, initial_capacity: int = 8, max_keys: int = MAX_KEYS):
        self._index: Dict[bytes, int] = {}
        self._invalid_cache: set = set()
        if max_keys > MAX_INDEXED_KEYS:
            # here, and not in a gather: past 2^31 int32 elements the
            # flat table's offsets no longer fit the kernel's indices
            raise ValueError(
                f"KeyBank of {max_keys} keys: the flat table would hold "
                f"2^31 elements or more (at most {MAX_INDEXED_KEYS} keys)"
            )
        self._max_keys = max_keys
        # clamp: capacity beyond max_keys would allocate (and upload)
        # table memory the lookup path refuses to ever use
        self._cap = max(1, min(initial_capacity, self._max_keys))
        self._np = np.zeros((self._cap, comb.ROWS_PER_KEY, comb.ROW), np.int32)
        self._dev = None
        self._dirty = True
        # whole-table host-to-device copies so far: one, in the warm, for
        # a population registered before it (4.6 GB at 1,096 keys is
        # seconds under the device lock)
        self.uploads = 0
        # the replica pipeline verifies sweep k+1 in a second worker thread
        # while sweep k is in flight — bank mutation must be atomic or two
        # first-sighted pubkeys can race `len(self._index)` and share a
        # table row (one key permanently verifying against the wrong point)
        self._lock = threading.Lock()

    def lookup(self, pubkey: bytes) -> int:
        """-> table row for pubkey, -1 if the key is invalid (bad length;
        not a curve point, where the bank had room to find out), or
        UNCACHED if the bank is full. Builds and caches the table on miss.
        Thread-safe."""
        with self._lock:
            idx = self._index.get(pubkey)
            if idx is not None:
                return idx
            if len(pubkey) != 32 or pubkey in self._invalid_cache:
                return -1
            if len(self._index) >= self._max_keys:
                # a full bank builds nothing and decompresses nothing: a
                # key past the cap costs a dict miss, whoever sends it
                # (the device decides whether it is a curve point)
                return self.UNCACHED
        # table construction runs outside the lock, re-checking on
        # re-entry (native C++ builds ~11 ms/key — a cold n=64 bank is
        # ~0.7 s; the pure-Python bigint fallback is ~0.2 s/key)
        pt = ref.point_decompress(pubkey)
        if pt is None:
            with self._lock:
                if len(self._invalid_cache) < 4096:  # bounded negative cache
                    self._invalid_cache.add(pubkey)
            return -1
        table = comb.fused_table_np(pt)
        with self._lock:
            idx = self._index.get(pubkey)
            if idx is not None:  # raced: another thread built it first
                return idx
            idx = len(self._index)
            if idx >= self._max_keys:
                return self.UNCACHED
            if idx >= self._cap:
                self._cap = min(self._cap * 2, self._max_keys)
                grown = np.zeros((self._cap,) + self._np.shape[1:], np.int32)
                grown[:idx] = self._np[:idx]
                self._np = grown
            self._np[idx] = table
            self._index[pubkey] = idx
            self._dirty = True
            return idx

    def lookup_pile(self, items: Sequence[BatchItem], size: int):
        """One pass over a pile: -> (pub, sig, msgs, ok, a_idx, uncached).

        `pub` and `sig` are the items' keys (32 bytes a row) and
        signatures (64) joined, `msgs` their messages, `ok` one byte a row
        (0 = malformed lengths, whose key and signature are zeroed, or a
        key known to be no curve point), `a_idx` the (size,) int32 table
        rows padded to the bucket in its one allocation, `uncached` the
        positions of well-formed rows whose key has no table (the bank is
        full): `ok` stays 1 there, so the row is staged in full for the
        ladder. Plain Python under one lock acquisition, so the
        interpreter lock is never given up: no numpy call here loops over
        the rows. A miss takes lookup(), which builds a table only while
        the bank has room."""
        n = len(items)
        pubs: List[bytes] = []
        sigs: List[bytes] = []
        msgs: List[bytes] = []
        rows = [0] * size
        uncached: List[int] = []
        bad: List[int] = []  # rows that miss a bank with room, or carry a wrong length
        with self._lock:
            row_of = self._index.get
            full = len(self._index) >= self._max_keys
            for i, it in enumerate(items):
                pk, sg = it.pubkey, it.sig
                idx = row_of(pk)
                if idx is not None and len(sg) == 64:
                    rows[i] = idx
                elif idx is None and full and len(pk) == 32 and len(sg) == 64:
                    uncached.append(i)  # what lookup() would answer
                else:
                    bad.append(i)
                pubs.append(pk)
                sigs.append(sg)
                msgs.append(it.msg)
        ok = bytearray(b"\x01") * n
        for i in bad:
            idx = self.lookup(pubs[i])
            if idx >= 0:
                rows[i] = idx
            if len(pubs[i]) != 32 or len(sigs[i]) != 64:
                pubs[i], sigs[i] = _ZERO32, _ZERO64
                ok[i] = 0
            elif idx == KeyBank.UNCACHED:  # the bank filled meanwhile
                uncached.append(i)
            elif idx < 0:
                ok[i] = 0
        a_idx = np.array(rows, dtype=np.int32)
        return b"".join(pubs), b"".join(sigs), msgs, ok, a_idx, uncached

    def table_shape(self) -> "tuple[int, int]":
        """The device table's shape at the current capacity: two Niels
        rows a line (comb._gather_rows; the host array is the same
        bytes)."""
        return self._cap * comb.ROWS_PER_KEY // 2, comb.LINE

    def device_tables(self) -> jnp.ndarray:
        """The flat table on device, table_shape()."""
        with self._lock:
            if self._dirty or self._dev is None:
                self._dev = jnp.asarray(self._np.reshape(self.table_shape()))
                self._dirty = False
                self.uploads += 1
            return self._dev


class LadderBatch(NamedTuple):
    """The rows of a pile whose key has no table, staged for the
    table-free program (ops/ladder.ladder_verify_wire_kernel) and padded
    to a bucket of their own."""

    rows: np.ndarray  # (n,) positions in the pile
    wire: np.ndarray  # (size, 128) uint8: S ‖ k ‖ R ‖ A per row
    precheck: np.ndarray  # (size,) bool


class WireBatch(NamedTuple):
    """Raw-bytes staging for the kernel, padded to its bucket: one
    (size, 96) uint8 array (S ‖ k ‖ R per row) plus key rows and the
    precheck mask (pad rows carry precheck=False, and so do the rows
    that `ladder` took). Window extraction, limb decomposition and the
    sign bit happen on the device (ops/comb.fused_verify_wire_kernel)."""

    wire: np.ndarray  # (size, 96) uint8
    a_idx: np.ndarray  # (size,) int32
    precheck: np.ndarray  # (size,) bool
    ladder: Optional[LadderBatch]  # None: every key of the pile has a table
    native: bool  # staged by native.prepare_wire, not by numpy


def _stage_numpy(pub: bytes, sig: bytes, msgs: Sequence[bytes],
                 ok: bytearray, size: int):
    """The staging native.prepare_wire does, in numpy: what runs where the
    native library is absent, and the reference the tests hold it to."""
    n = len(msgs)
    pub_np = np.frombuffer(pub, dtype=np.uint8).reshape(n, 32)
    sig_np = np.frombuffer(sig, dtype=np.uint8).reshape(n, 64)
    r_raw, s_raw = sig_np[:, :32], sig_np[:, 32:]
    k_raw = native.challenge_batch(r_raw, pub_np, msgs)
    precheck = np.frombuffer(ok, dtype=np.uint8).astype(bool)
    precheck &= ~_ge_l_np(s_raw)
    precheck &= ~_ge_p_np(r_raw)
    wire = np.concatenate([s_raw, k_raw, r_raw], axis=1)  # (n, 96) uint8
    pad = size - n
    return np.pad(wire, ((0, pad), (0, 0))), np.pad(precheck, (0, pad))


def _ladder_batch(
    pub: bytes, wire: np.ndarray, precheck: np.ndarray,
    uncached: List[int], align: int,
) -> LadderBatch:
    """Copy the uncached rows out of a staged pile, each with its key's
    32 bytes behind it, and mask them in the pile: the comb then answers
    False there, and the finisher writes the ladder's verdicts over it.
    One native call that keeps the interpreter lock (native.ladder_rows),
    or its numpy stand-in where the library is absent."""
    n = len(uncached)
    size = _bucket_size(max(n, align))
    staged = native.ladder_rows(wire, pub, precheck, uncached, size)
    if staged is not None:
        return LadderBatch(*staged)
    rows = np.array(uncached, dtype=np.int64)
    out = np.zeros((size, ladder.ROW_BYTES), dtype=np.uint8)
    out[:n, :96] = wire[rows]
    out[:n, 96:] = np.frombuffer(pub, dtype=np.uint8).reshape(-1, 32)[rows]
    pre = np.zeros(size, dtype=np.bool_)
    pre[:n] = precheck[rows]
    precheck[rows] = False
    return LadderBatch(rows, out, pre)


def prepare_wire_batch(
    items: Sequence[BatchItem], bank: KeyBank, size: int, align: int = 1
) -> WireBatch:
    """Wire bytes -> WireBatch padded to the bucket `size`, registering
    pubkeys in `bank` while it has room.

    Rows whose key has no table (the bank is full) come back a second
    time in `ladder`, with their keys' bytes, padded to their own bucket
    (a multiple of `align`), and masked in the pile. Host work is one
    Python pass over the items (the byte joins and the bank's dict
    lookup) and one native call for the challenge hash, the canonicality
    reject policy (S >= L malleability, non-canonical R.y) and the
    padding — no window/limb unpacking. The pile's size alone decides
    how that call is made (LOCK_HELD_BUCKET)."""
    pub, sig, msgs, ok, a_idx, uncached = bank.lookup_pile(items, size)
    staged = native.prepare_wire(
        pub, sig, msgs, ok, size, hold_lock=size <= LOCK_HELD_BUCKET
    )
    wire, precheck = (
        staged if staged is not None
        else _stage_numpy(pub, sig, msgs, ok, size)
    )
    lad = (
        _ladder_batch(pub, wire, precheck, uncached, align)
        if uncached else None
    )
    return WireBatch(wire, a_idx, precheck, lad, staged is not None)


# One device pass at a time, process-wide. The replica runtime calls
# verify_batch from worker threads (asyncio.to_thread) so the event loop
# never blocks on the device; without this lock N replicas' first calls
# would TRACE AND COMPILE the same jit signature concurrently — N
# GIL-interleaved compiles of identical kernels (minutes on a small CPU
# host) instead of one compile plus N-1 cache hits. Steady-state cost is
# nil: a single chip serializes execution anyway.
_DEVICE_LOCK = threading.Lock()


class _CompileWatch:
    """Counts, while open, the XLA compile requests that consulted the
    persistent cache and how many of them it served (jax.monitoring
    events) — what tells a warm bucket that was read from disk from one
    that was compiled. Both stay 0 when nothing compiled (the program
    was already live in this process) or the cache is off."""

    def __enter__(self) -> "_CompileWatch":
        self.requests = 0
        self.hits = 0
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def __exit__(self, *_exc) -> None:
        jax.monitoring.unregister_event_listener(self._on_event)


def _device_bytes(mesh: Optional[jax.sharding.Mesh]) -> Optional[int]:
    """What one device may hold, as its runtime reports it; None where it
    reports nothing (the CPU). The tables replicate across a mesh, so one
    device's limit is the limit."""
    dev = jax.devices()[0] if mesh is None else mesh.devices.flat[0]
    return (dev.memory_stats() or {}).get("bytes_limit")


# The one jitted kernel, shared by every unmeshed TpuVerifier. A
# per-instance `jax.jit` wrapper would give each verifier its own compile
# cache — an N-replica committee would then compile the same kernel N
# times per bucket size (minutes of wasted wall clock, and a practical
# deadlock on single-core CI hosts).
_SHARED_JIT = jax.jit(comb.fused_verify_wire_kernel)
# and the table-free program, for rows whose key has no table
_SHARED_LADDER_JIT = jax.jit(ladder.ladder_verify_wire_kernel)


class TpuVerifier:
    """The `tpu` backend behind the crypto.Verifier seam.

    The fused comb (ops/comb.py) for every key with a table: cached
    per-pubkey dual-scalar tables, zero doublings, no on-device
    decompression, one madd per nibble position, batch-amortized
    inversion. The ladder (ops/ladder.py) for the keys the bank has no
    room for, in the same pass.

    Pads drained batches to bucketed sizes, runs one jitted device pass per
    chunk, and returns the per-item bitmap. Pass a `jax.sharding.Mesh` via
    `mesh` to shard the batch dimension across chips (tables replicate;
    verdict gather rides ICI).
    """

    name = "tpu"

    # what the device ledger's rows and the harnesses' warm lines call
    # the kernel and its scalar window
    _mode = "fused"
    _window = comb.WBITS

    def __init__(
        self,
        mesh: Optional[jax.sharding.Mesh] = None,
        initial_keys: Optional[int] = None,
    ):
        self._mesh = mesh
        # initial_keys sizes the bank for the EXPECTED key population
        # (committee + clients). This is not an optimization nicety: the
        # jit signature includes the table shape, which is a function of
        # the bank's capacity — letting the bank grow 8 -> 16 -> 32 under
        # live traffic means each (bucket, capacity) pair is a FRESH
        # 40-150 s compile, serialized under the device lock across every
        # replica in the process (measured: an n=16 committee spending
        # its entire 120 s client patience inside back-to-back compiles,
        # committing nothing). A PBFT deployment knows its key set up
        # front — size the bank once and the shape never moves: the
        # capacity bank_capacity gives (a power of two up to 512 keys,
        # granules of 128 past it, bounded by the device's memory) is
        # the bank's cap as well.
        if initial_keys is None:
            self._bank = KeyBank()
        else:
            cap = bank_capacity(initial_keys, _device_bytes(mesh))
            self._bank = KeyBank(initial_capacity=cap, max_keys=cap)
        # seconds the warm spent building the population's tables and
        # putting them on the device
        self.bank_build_s = 0.0
        # lazy batched native verifier: uncached rows of a verifier that
        # was warmed WITHOUT the ladder (see _dispatch_chunk)
        self._cpu_fb = None
        if mesh is not None:
            # shard_map, not a GSPMD-sharded jit: each device runs the
            # kernel on its LOCAL batch shard, so the Pallas Mosaic
            # accumulator needs no GSPMD partitioning rule and stays
            # active on TPU meshes (accum resolves per backend: Pallas
            # on TPU, XLA fori_loop on the CPU mesh). Per-shard batches
            # stay powers of two (bucket sizes / power-of-two mesh),
            # which the kernel's batch inversion requires.
            from jax import shard_map
            from jax.sharding import PartitionSpec as PS

            axis = mesh.axis_names[0]
            # args are (wire (B,96), a_idx (B,), f_table (replicated),
            # precheck (B,)) — batch axis LEADS the wire array, so
            # shards split rows.
            #
            # check_vma=False: under jax 0.9.0's varying-axes check a
            # pallas_call fails to trace here — compiled, because its
            # out_shape carries no vma ("vma on jax.ShapeDtypeStruct
            # must not be None"); interpreted (how tier-1 runs this
            # path), inside the interpreter's own grid scan even with
            # the vma given ("Scan carry input and output got
            # mismatched varying manual axes"). The body has no
            # collectives and every output is per-shard, so the
            # check has nothing to protect.
            def sharded(kernel, in_specs):
                return jax.jit(shard_map(
                    kernel, mesh=mesh, in_specs=in_specs,
                    out_specs=PS(axis), check_vma=False,
                ))

            self._fn = sharded(
                comb.fused_verify_wire_kernel,
                (PS(axis, None), PS(axis), PS(None, None), PS(axis)),
            )
            # the ladder's rows split the same way: (wire (B,128), precheck)
            self._ladder_fn = sharded(
                ladder.ladder_verify_wire_kernel, (PS(axis, None), PS(axis))
            )
            self._align = int(np.prod(mesh.devices.shape))
            if self._align & (self._align - 1):
                # batches pad to power-of-two BUCKETS (and the kernel's
                # batch inversion needs a power of two); a
                # non-power-of-two mesh cannot divide them evenly and the
                # sharded jit would fail at runtime instead of here
                raise ValueError(
                    f"TpuVerifier needs a power-of-two mesh size, got "
                    f"{self._align} devices"
                )
        else:
            self._fn = _SHARED_JIT
            self._ladder_fn = _SHARED_LADDER_JIT
            self._align = 1
        # Device-side accounting, owned by the verifier: seconds are
        # measured INSIDE the device lock by the holder, so they are
        # dispatch+execute time only. Summing caller-side wall clocks
        # across N replicas sharing this verifier counts lock WAIT once
        # per blocked caller and underreports the device rate by up to
        # N x. Monotonic (read-only) counters; the device lock already
        # serializes writers.
        self.device_calls = 0
        self.device_items = 0
        self.device_seconds = 0.0
        # Shape-stability accounting (ISSUE 3 tentpole). The jit
        # signature is a function of (kernel, padded batch bucket, table
        # capacity); a signature never dispatched before means XLA traces
        # and compiles — 40-150 s under the device lock on a small host,
        # which mid-run is a committee-wide stall (the r5 qc256 8127-item
        # pile). `shape_compiles` counts first-time signatures,
        # `post_warm_compiles` the ones AFTER warmup declared the shape
        # set closed — the invariant is post_warm_compiles == 0, asserted
        # by tests via this hook and exported through VerifyService
        # snapshots for live runs.
        self.shape_signatures: set = set()
        self.shape_compiles = 0
        self.post_warm_compiles = 0
        self.bucket_hits: Dict[int, int] = {}
        self._warm_done = False
        # one row per warmed bucket: wall seconds of its first pass
        # (compile or cache load included) and the persistent cache's
        # part in it — see _CompileWatch
        self.warm_log: List[dict] = []
        # items answered by the CPU instead of the device because their
        # key had no table and no ladder bucket was warmed for them: 0
        # wherever the published population was given to the warm
        self.overcap_fallback_items = 0
        # items of finished passes that took the table-free ladder, the
        # passes that launched it, and the seconds those passes waited
        # for its verdicts AFTER the comb's had come: what the pass would
        # not have cost had every key had a table (VerifyService takes it
        # out of its round-trip estimate)
        self.ladder_items = 0
        self.ladder_passes = 0
        self.ladder_seconds = 0.0
        # items of finished passes by who staged them: the native library's
        # one call (prepare_wire) or the numpy staging that stands in for
        # it where the library is absent
        self.native_prep_items = 0
        self.fallback_prep_items = 0
        # summed over finished passes: the distinct table rows a pass's
        # items name (72 at most at n=64 with 8 clients; one a client
        # where a thousand sign)
        self.pass_distinct_keys = 0

    @classmethod
    def for_population(
        cls,
        pubkeys: Sequence[bytes],
        max_sweep: int,
        headroom: int = 32,
        **kwargs,
    ) -> "TpuVerifier":
        """Build + warm a verifier for a known deployment in one step:
        size the bank to the published key population (+headroom for
        walk-in client keys) and pre-pay every device compile a drain
        sweep of up to `max_sweep` items can hit. THE constructor for
        production nodes — an unsized bank recompiles (minutes, under
        the device lock) the first time live traffic grows it."""
        v = cls(initial_keys=len(pubkeys) + headroom, **kwargs)
        v.warm_for_population(pubkeys, max_sweep)
        return v

    def warm_for_population(
        self, pubkeys: Sequence[bytes], max_sweep: int
    ) -> None:
        """Register the key population and warm every batch bucket up
        to the one covering `max_sweep` items. Single-sourced bucket
        policy for node.py and the committee benches. Where the
        population exceeds the bank's capacity, which happens only where
        bank_capacity's bound (the device's memory, the int32 index) lies
        under it, the keys past it have no table: the same buckets of
        the table-free ladder are warmed for them, and logged. A
        population that fits warms the comb alone."""
        top = _bucket_size(max(1, min(max_sweep, BUCKETS[-1])))
        buckets = [b for b in BUCKETS if b <= top]
        self.warm(pubkeys=pubkeys, buckets=buckets)
        if len(pubkeys) > self._bank._max_keys:
            import logging

            logging.warning(
                "TpuVerifier bank clamped: %d published keys > max_keys=%d; "
                "over-cap keys verify on the device by the table-free "
                "ladder, warmed at buckets %s",
                len(pubkeys), self._bank._max_keys, buckets,
            )
            # a well-formed key that no one holds: the bank is full, so
            # it reports UNCACHED and every row takes the ladder
            self._warm_buckets(
                buckets, BatchItem(bytes(32), b"", bytes(64)), "ladder")
        # the shape set is now closed: any later first-time signature is
        # a mid-run compile — counted in post_warm_compiles and surfaced
        # through the telemetry plane (the r5 qc256 suspect made visible)
        self._warm_done = True

    def warm(
        self,
        pubkeys: Sequence[bytes] = (),
        buckets: Sequence[int] = (8,),
    ) -> None:
        """Pre-pay every device compile this verifier will hit under
        traffic: register the known key population (committee members +
        enrolled clients — a PBFT deployment publishes these up front),
        then run one throwaway device pass per batch bucket at the
        resulting table shape. Because the jitted kernels are shared
        process-wide (_SHARED_JIT), warming ONE verifier warms every
        replica in a simulated committee — provided they were built with
        the same initial_keys, so their table shapes match."""
        from .. import spans

        # one build and one upload, before the first pass asks for the
        # tables under the device lock
        t0 = time.perf_counter()
        with spans.annotation(spans.VERIFY_BANK_BUILD):
            for pk in pubkeys:
                self._bank.lookup(pk)
            self._bank.device_tables().block_until_ready()
        build_s = time.perf_counter() - t0
        self.bank_build_s += build_s
        spans.record(spans.VERIFY_BANK_BUILD, build_s, n=len(pubkeys))
        # wrong-length pubkey: KeyBank.lookup_pile masks the row and the bank
        # rejects it without registering — an all-zero 32-byte key would
        # decompress to a valid (order-4) point and permanently occupy a
        # bank slot, skewing the very capacity this warmup pins
        self._warm_buckets(buckets, BatchItem(bytes(31), b"", bytes(64)), "comb")

    def _warm_buckets(
        self, buckets: Sequence[int], dummy: BatchItem, program: str
    ) -> None:
        """One throwaway pass of `dummy` rows a bucket, through the same
        call path traffic takes, and its row in warm_log."""
        for b in buckets:
            t0 = time.perf_counter()
            with _CompileWatch() as watch:
                self.verify_batch([dummy] * b)
            self.warm_log.append({
                "bucket": b,
                "program": program,
                "seconds": round(time.perf_counter() - t0, 3),
                "compile_requests": watch.requests,
                "cache_hits": watch.hits,
            })

    def _record_shape(self, sig: tuple) -> bool:
        """Track the jit signature this dispatch hits. Must run AFTER
        host prep (bank lookups can grow the table capacity, which is
        part of the comb's signature) and records under the bank lock's
        protection being unnecessary: GIL-atomic set/dict ops, and the
        counters are observability, not control flow. Returns whether
        the signature is FRESH (this dispatch traces and compiles) —
        the device ledger's compile-vs-cache column."""
        fresh = sig not in self.shape_signatures
        if fresh:
            self.shape_signatures.add(sig)
            self.shape_compiles += 1
            if self._warm_done:
                self.post_warm_compiles += 1
                import logging

                logging.getLogger(__name__).warning(
                    "TpuVerifier: fresh jit signature %s AFTER warmup — "
                    "mid-run XLA compile (extend warm_for_population's "
                    "bucket set or initial_keys)", sig,
                )
        return fresh

    def shape_snapshot(self) -> dict:
        """Shape-stability counters for the telemetry plane: after
        warmup, post_warm_compiles must stay 0 (asserted in tests via
        this hook; scraped live via VerifyService.snapshot)."""
        return {
            "warmed": self._warm_done,
            "shape_compiles": self.shape_compiles,
            "post_warm_compiles": self.post_warm_compiles,
            "bucket_hits": {str(k): v for k, v in sorted(self.bucket_hits.items())},
            "overcap_fallback_items": self.overcap_fallback_items,
            "ladder_items": self.ladder_items,
            "ladder_passes": self.ladder_passes,
            "native_prep_items": self.native_prep_items,
            "fallback_prep_items": self.fallback_prep_items,
            "pass_distinct_keys": self.pass_distinct_keys,
            # the key bank as the warm left it
            "bank_keys": len(self._bank._index),
            "bank_capacity": self._bank._cap,
            "table_bytes": self._bank._np.nbytes,
            "bank_uploads": self._bank.uploads,
            "bank_build_s": round(self.bank_build_s, 3),
        }

    def lowered_text(self, size: int) -> str:
        """The program this verifier's jit lowers to at batch bucket
        `size` and the bank's current table shape, as text. chip_smoke.py
        reads it to show the Pallas accumulator went through Mosaic (a
        ``tpu_custom_call``) and was not interpreted."""
        struct = jax.ShapeDtypeStruct
        return self._fn.lower(
            struct((size, 96), jnp.uint8),
            struct((size,), jnp.int32),
            struct(self._bank.table_shape(), jnp.int32),
            struct((size,), jnp.bool_),
        ).as_text()

    def verify_batch(self, items: Sequence[BatchItem]) -> List[bool]:
        return self.dispatch_batch(items)()

    def dispatch_batch(self, items: Sequence[BatchItem]):
        """Host-prep + ASYNC device dispatch; returns a zero-arg finisher
        that blocks on the device result and maps verdicts back per item.

        The device lock covers only tracing/enqueue — jax dispatch is
        asynchronous, so the device executes this batch while the caller
        preps and dispatches the next one (the coalescing service's
        double-buffering; VERDICT r4 next #1). `verify_batch` is just
        dispatch + immediate finish."""
        if not items:
            return lambda: []
        from .. import devledger

        finishers = []
        maxb = BUCKETS[-1]
        # the dispatcher's queue-wait annotation covers the WHOLE take:
        # consume it once here and attribute it to the first chunk —
        # later chunks of an oversized take record (0, 0), so the lane's
        # submission count matches the service's truth
        annotation = devledger.take_annotation()
        for start in range(0, len(items), maxb):
            chunk = items[start : start + maxb]
            finishers.append(self._dispatch_chunk(chunk, annotation))
            annotation = (0.0, 0)

        def finish() -> List[bool]:
            out: List[bool] = []
            for fin in finishers:
                out.extend(fin())
            return out

        return finish

    def _dispatch_chunk(
        self,
        items: Sequence[BatchItem],
        annotation: "tuple[float, int]" = (0.0, 1),
    ):
        from .. import devledger, spans

        t_prep = time.perf_counter()
        # annotated while a profiler capture is open, so the trace's host
        # planes show the prep beside the device's modules
        with spans.annotation(spans.VERIFY_HOST_PREP):
            size = _bucket_size(max(len(items), self._align))
            prep = prepare_wire_batch(items, self._bank, size, self._align)
            args = (
                prep.wire, prep.a_idx, self._bank.device_tables(),
                prep.precheck,
            )
            self.bucket_hits[size] = self.bucket_hits.get(size, 0) + 1
            compile_fresh = self._record_shape(
                (self._mode, self._window, size, self._bank._cap))
            lad = prep.ladder
            cpu_rows = None
            lad_fresh = False
            if lad is not None:
                lad_sig = ("ladder", self._window, len(lad.precheck))
                if self._warm_done and lad_sig not in self.shape_signatures:
                    # the warm was given a population that fits the bank,
                    # so it compiled no ladder bucket, and this key walked
                    # in after the bank filled: nothing compiles under
                    # traffic, the rows keep the batched CPU route (their
                    # comb rows are masked either way)
                    cpu_rows, lad = lad.rows, None
                else:
                    lad_fresh = self._record_shape(lad_sig)
        # host-side prep (byte joins, challenge hashes, padding) is CPU
        # work on the dispatcher's thread — if it rivals the
        # device RTT the pipeline is host-bound, and only a span can say
        # so (spans.py; the r5 "where do the other 96% go" question)
        prep_s = time.perf_counter() - t_prep
        spans.record(spans.VERIFY_HOST_PREP, prep_s, n=len(items))
        # host->device upload: the freshly-built host arrays (persistent
        # device tables are excluded — they upload once per bank change,
        # not per dispatch); the verdict bitmap comes back one byte/row
        bytes_up = sum(
            a.nbytes for a in args if isinstance(a, np.ndarray)
        )
        # queue-wait annotation consumed once per take by dispatch_batch
        # (the coalescing dispatcher sets it on this thread; direct
        # callers default to zero wait / one submission)
        queue_wait_s, submissions = annotation
        lad_out = None
        with _DEVICE_LOCK:
            t0 = time.perf_counter()
            dev_out = self._fn(*args)  # async: enqueue only
            if lad is not None:
                # behind the comb on the device's queue, under the same
                # hold of the lock: one pass, two programs
                with spans.annotation(spans.VERIFY_LADDER):
                    lad_out = self._ladder_fn(lad.wire, lad.precheck)
            self.device_calls += 1
            self.device_items += len(items)
        # while the device works on the pass: the distinct table rows its
        # items name, added to pass_distinct_keys where the pass ends
        distinct = int(np.unique(prep.a_idx[: len(items)]).size)

        def finish() -> List[bool]:
            # np.array (copy): the other program's rows are written in place
            verdict = np.array(dev_out)  # blocks until the device answers
            t_comb = time.perf_counter()
            n_lad = 0
            if lad_out is not None:
                n_lad = len(lad.rows)
                with spans.annotation(spans.VERIFY_LADDER):
                    verdict[lad.rows] = np.asarray(lad_out)[:n_lad]
            t_end = time.perf_counter()
            rtt = t_end - t0
            # what the pass waited for the ladder after the comb's
            # verdicts had come
            lad_wait = t_end - t_comb
            # dispatch->result wall time. Overlapped calls each count
            # their full span, so the sum can exceed wall clock under
            # pipelining — device_seconds is a latency integral, not an
            # occupancy figure (verify_per_s_device derived from it is a
            # LOWER bound on the device rate when calls overlap).
            with _DEVICE_LOCK:
                self.device_seconds += rtt
                # counted where the pass ends, as the service counts its
                # device_pass_items, so the two cover the same passes
                if prep.native:
                    self.native_prep_items += len(items)
                else:
                    self.fallback_prep_items += len(items)
                self.pass_distinct_keys += distinct
                if n_lad:
                    self.ladder_items += n_lad
                    self.ladder_passes += 1
                    self.ladder_seconds += lad_wait
            # per-dispatch device ledger event (ISSUE 14): one row per
            # jit dispatch with the full cost tuple — the continuously-
            # measured form of the r05 hand decomposition. A pass that
            # launched both programs leaves two rows that add up to it:
            # the comb's has the rows it answered (the ladder's are pad
            # to it) and the time to its own verdicts, the ladder's its
            # own rows and the wait from there to the pass's end
            devledger.record(
                devledger.LANE_ED25519, self._mode, self._window, size,
                len(items) - n_lad, host_prep_s=prep_s, rtt_s=t_comb - t0,
                compile_fresh=compile_fresh, bytes_up=bytes_up,
                bytes_down=size, queue_wait_s=queue_wait_s,
                submissions=submissions,
            )
            if n_lad:
                # dispatch to result of the ladder program, a round trip
                # like verify.device (which goes on covering the pass)
                spans.record(spans.VERIFY_LADDER, rtt, n=n_lad)
                devledger.record(
                    devledger.LANE_ED25519, "ladder", self._window,
                    len(lad.precheck), n_lad, rtt_s=lad_wait,
                    compile_fresh=lad_fresh,
                    bytes_up=lad.wire.nbytes + lad.precheck.nbytes,
                    bytes_down=len(lad.precheck), queue_wait_s=0.0,
                    submissions=0,
                )
            if cpu_rows is not None:
                if self._cpu_fb is None:
                    from .verifier import kernel_equivalent_cpu_verifier

                    # kernel-EQUIVALENT only (native batched Ed25519,
                    # else the RFC 8032 oracle — never OpenSSL): these
                    # rows share a verdict bitmap with kernel rows, so
                    # the two accept/reject sets must agree on every edge
                    # vector (non-canonical R/S, off-curve points) or a
                    # crafted signature splits the pile (ADVICE r5)
                    self._cpu_fb = kernel_equivalent_cpu_verifier()
                # ONE batched native-CPU pass, not a scalar loop
                verdict[cpu_rows] = self._cpu_fb.verify_batch(
                    [items[i] for i in cpu_rows]
                )
                self.overcap_fallback_items += len(cpu_rows)
            return verdict[: len(items)].tolist()

        return finish
