"""ctypes loader for the native host-prep library (pbft_native.cpp).

The shared object is built on demand with g++ next to the source and
loaded via ctypes — no pybind11 dependency. The built file's name carries
a hash of the source's content and the compiler flags (`_ensure_built`),
so what runs is always a build of the source that is there: a binary is
never trusted for its mtime, and never loaded when its source is absent.
Every entry point has a pure-Python fallback so the framework works on
machines without a toolchain; `status()` reports which path is active
and chip_smoke.py refuses to call the chip proven on the fallback.

API (numpy in, numpy out, zero per-item Python work):
- challenge_batch(r, a, msgs) -> (n, 32) uint8 little-endian scalars
  k_i = SHA-512(R_i || A_i || M_i) mod L   (the Ed25519 challenge)
- prepare_wire(pub, sig, msgs, ok, size, hold_lock) -> the verifier's
  staged (size, 96) uint8 rows S || k || R and (size,) bool precheck, or
  None without the library (the one entry point with no fallback of its
  own: the numpy staging in crypto/tpu_verifier.py is it)
- sha512_batch(msgs) -> (n, 64) uint8 digests
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import itertools
import logging
import os
import subprocess
import threading
from typing import Dict, List, Optional, Sequence

import numpy as np

log = logging.getLogger(__name__)

_SRC = os.path.join(os.path.dirname(__file__), "pbft_native.cpp")
_SRC_BLS = os.path.join(os.path.dirname(__file__), "bls381.cpp")
_SRC_ED = os.path.join(os.path.dirname(__file__), "ed25519.cpp")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
# the same library through a handle that keeps the interpreter lock across
# a call (ctypes.CDLL gives it up); prepare_wire's small piles go this way
_lib_held: Optional[ctypes.PyDLL] = None
_tried = False
# own lock: a first-use BLS build (g++, up to ~2 min) must not stall
# Ed25519 host-prep calls on the unrelated library
_bls_lock = threading.Lock()
_bls_lib: Optional[ctypes.CDLL] = None
_bls_tried = False
_ed_lock = threading.Lock()
_ed_lib: Optional[ctypes.CDLL] = None
_ed_tried = False

_u8p = np.ctypeslib.ndpointer(dtype=np.uint8, flags="C_CONTIGUOUS")
_i64p = np.ctypeslib.ndpointer(dtype=np.int64, flags="C_CONTIGUOUS")


# source path -> {"path": the .so in use, "built": compiled by THIS
# process (False = a build of the same source and flags was already
# there)}; what status() reports per library
_builds: Dict[str, dict] = {}


def _build_so(src: str, so: str, flags: Sequence[str]) -> bool:
    # per-process temp name: concurrent builders (multi-process launch,
    # parallel test workers) must never interleave linker output in a
    # shared file; os.replace keeps the final install atomic
    tmp = f"{so}.{os.getpid()}.tmp"
    cmd = ["g++", *flags, "-o", tmp, src]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, so)
        return True
    except (OSError, subprocess.SubprocessError) as e:
        detail = getattr(e, "stderr", b"") or b""
        log.warning("native build failed (%s) %s — using Python fallback",
                    e, detail.decode(errors="replace")[:500])
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return False


def _ensure_built(src: str, extra: Sequence[str] = ()) -> Optional[str]:
    """Path of a build of `src` (building it if needed), None when there
    is no source or no working compiler. Shared by the ctypes loader
    below and the extension loader.

    The file name is `_<stem>.<hash>.so`, the hash taken over the
    source's CONTENT and the flags: a copy of the tree may give files any
    mtime, and a binary left behind by other source must not be picked up
    — so freshness is a property of the name, and an absent source means
    nothing is loaded. Builds of older content are removed."""
    flags = ["-O3", *extra, "-shared", "-fPIC"]
    try:
        with open(src, "rb") as f:
            body = f.read()
    except OSError as e:
        log.warning("native source unreadable (%s) — using Python fallback", e)
        return None
    digest = hashlib.sha256(body + "\0".join(flags).encode()).hexdigest()[:16]
    stem = os.path.splitext(os.path.basename(src))[0]
    prefix = os.path.join(os.path.dirname(src), f"_{stem}")
    so = f"{prefix}.{digest}.so"
    built = not os.path.exists(so)
    if built:
        if not _build_so(src, so, flags):
            return None
        for stale in glob.glob(f"{prefix}*.so"):
            if stale != so:
                try:
                    os.unlink(stale)
                except OSError:
                    pass
    _builds[src] = {"path": so, "built": built}
    return so


def _load_library(src: str, configure, extra=()) -> Optional[ctypes.CDLL]:
    """Shared build-on-demand loader: `_ensure_built`, CDLL-load, then
    run ``configure(lib)`` (argtypes + optional selftest; return None to
    reject). Any failure degrades to the caller's Python fallback."""
    so = _ensure_built(src, extra=extra)
    if so is None:
        return None
    try:
        lib = ctypes.CDLL(so)
    except OSError as e:
        log.warning("%s load failed: %s — using Python fallback",
                    os.path.basename(so), e)
        return None
    return configure(lib)


def _configure_hostprep(lib):
    lib.challenge_batch.argtypes = [
        _u8p, _u8p, _u8p, _i64p, ctypes.c_int64, _u8p,
    ]
    lib.challenge_batch.restype = None
    lib.sha512_batch.argtypes = [_u8p, _i64p, ctypes.c_int64, _u8p]
    lib.sha512_batch.restype = None
    lib.sc_reduce_batch.argtypes = [_u8p, ctypes.c_int64, _u8p]
    lib.sc_reduce_batch.restype = None
    lib.native_num_threads.argtypes = []
    lib.native_num_threads.restype = ctypes.c_int
    global _lib_held
    _lib_held = ctypes.PyDLL(lib._name)
    for handle in (lib, _lib_held):
        handle.prepare_wire.argtypes = [
            _u8p, _u8p, _u8p, _i64p, _u8p, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int, _u8p, _u8p,
        ]
        handle.prepare_wire.restype = None
    _lib_held.ladder_rows.argtypes = [
        _u8p, _u8p, _u8p, _i64p, ctypes.c_int64, ctypes.c_int64, _u8p, _u8p,
    ]
    _lib_held.ladder_rows.restype = None
    return lib


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    with _lock:
        if not _tried:
            _tried = True
            _lib = _load_library(
                _SRC, _configure_hostprep, extra=("-fopenmp",)
            )
        return _lib


def _configure_bls(lib):
    u8p = ctypes.POINTER(ctypes.c_uint8)
    i64 = ctypes.c_int64
    lib.bls_verify_one.argtypes = [
        u8p, u8p, i64, u8p, u8p, i64, ctypes.c_int,
    ]
    lib.bls_verify_one.restype = ctypes.c_int
    lib.bls_verify_aggregate.argtypes = [
        u8p, i64, u8p, i64, u8p, u8p, i64,
    ]
    lib.bls_verify_aggregate.restype = ctypes.c_int
    lib.bls_verify_batch_rlc.argtypes = [
        u8p, i64, u8p, _i64p, i64, u8p, u8p, u8p, i64,
    ]
    lib.bls_verify_batch_rlc.restype = ctypes.c_int
    lib.bls_sign.argtypes = [u8p, u8p, i64, u8p, i64, u8p]
    lib.bls_sign.restype = ctypes.c_int
    lib.bls_pubkey.argtypes = [u8p, u8p]
    lib.bls_pubkey.restype = ctypes.c_int
    lib.bls_selftest.argtypes = []
    lib.bls_selftest.restype = ctypes.c_int
    if lib.bls_selftest() != 1:
        log.warning("bls381 selftest FAILED — using Python fallback")
        return None
    return lib


def _load_bls() -> Optional[ctypes.CDLL]:
    global _bls_lib, _bls_tried
    with _bls_lock:
        if not _bls_tried:
            _bls_tried = True
            _bls_lib = _load_library(_SRC_BLS, _configure_bls)
        return _bls_lib


def _configure_ed(lib):
    _i32p = np.ctypeslib.ndpointer(dtype=np.int32, flags="C_CONTIGUOUS")
    lib.ed25519_batch_verify.argtypes = [
        _u8p, ctypes.c_int, _i32p, _u8p, _u8p, _u8p, _u8p, _u8p,
        ctypes.c_int,
    ]
    lib.ed25519_batch_verify.restype = ctypes.c_int
    lib.ed25519_fused_table.argtypes = [_u8p, ctypes.c_int, _u8p]
    lib.ed25519_fused_table.restype = ctypes.c_int
    return lib


def _load_ed() -> Optional[ctypes.CDLL]:
    global _ed_lib, _ed_tried
    with _ed_lock:
        if not _ed_tried:
            _ed_tried = True
            _ed_lib = _load_library(_SRC_ED, _configure_ed)
        return _ed_lib


def ed25519_available() -> bool:
    return _load_ed() is not None


def ed25519_fused_table(
    a_xy: np.ndarray, wbits: int
) -> Optional[np.ndarray]:
    """Affine pubkey (64,) uint8 (x||y LE) -> (npos * 4^wbits, 96) uint8
    affine-Niels field-element bytes for the fused dual-scalar comb
    (KeyBank cold-start fast path); None = library unavailable."""
    lib = _load_ed()
    if lib is None:
        return None
    npos = -(-256 // wbits)
    n = npos * (1 << wbits) ** 2
    out = np.empty((n, 96), dtype=np.uint8)
    rc = lib.ed25519_fused_table(
        np.ascontiguousarray(a_xy, dtype=np.uint8), wbits, out
    )
    return out if rc == 0 else None


def ed25519_batch_verify(
    a_xy: np.ndarray,       # (n_keys, 64) uint8: affine x||y, 32B LE each
    key_idx: np.ndarray,    # (B,) int32 into a_xy (-1 = invalid key)
    s_scalars: np.ndarray,  # (B, 32) uint8, already range-checked < L
    k_scalars: np.ndarray,  # (B, 32) uint8, SHA-512(R||A||M) mod L
    r_wire: np.ndarray,     # (B, 32) uint8, signature R wire bytes
    precheck: np.ndarray,   # (B,) uint8 validity mask
) -> Optional[np.ndarray]:
    """Batched [S]B + [k](-A) == R verification; None = unavailable."""
    lib = _load_ed()
    if lib is None:
        return None
    batch = len(key_idx)
    out = np.zeros(batch, dtype=np.uint8)
    rc = lib.ed25519_batch_verify(
        np.ascontiguousarray(a_xy, dtype=np.uint8),
        len(a_xy),
        np.ascontiguousarray(key_idx, dtype=np.int32),
        np.ascontiguousarray(s_scalars, dtype=np.uint8),
        np.ascontiguousarray(k_scalars, dtype=np.uint8),
        np.ascontiguousarray(r_wire, dtype=np.uint8),
        np.ascontiguousarray(precheck, dtype=np.uint8),
        out,
        batch,
    )
    if rc != 0:
        return None
    return out


def _cbuf(b: bytes):
    return (ctypes.c_uint8 * max(1, len(b))).from_buffer_copy(b or b"\0")


def bls_available() -> bool:
    return _load_bls() is not None


def bls_verify_one(
    pubkey: bytes, msg: bytes, sig: bytes, dst: bytes, check_pk: bool
) -> Optional[bool]:
    """Native single-signature BLS verify; None = library unavailable
    (caller falls back to the Python path)."""
    if len(pubkey) != 192 or len(sig) != 96:
        return False
    lib = _load_bls()
    if lib is None:
        return None
    r = lib.bls_verify_one(
        _cbuf(pubkey), _cbuf(msg), len(msg), _cbuf(sig), _cbuf(dst),
        len(dst), 1 if check_pk else 0,
    )
    return bool(r)


def bls_sign(sk: int, msg: bytes, dst: bytes) -> Optional[bytes]:
    """Native BLS sign (bit-identical to the Python path — deterministic
    hash-and-multiply); None = unavailable (caller falls back, including
    out-of-range scalars the bigint path accepts)."""
    if not 0 <= sk < (1 << 256):
        return None
    lib = _load_bls()
    if lib is None:
        return None
    out = (ctypes.c_uint8 * 96)()
    r = lib.bls_sign(
        _cbuf(sk.to_bytes(32, "big")), _cbuf(msg), len(msg), _cbuf(dst),
        len(dst), out,
    )
    return bytes(out) if r else None


def bls_pubkey(sk: int) -> Optional[bytes]:
    """Native G2 pubkey derivation; None = unavailable (caller falls
    back, including out-of-range scalars)."""
    if not 0 <= sk < (1 << 256):
        return None
    lib = _load_bls()
    if lib is None:
        return None
    out = (ctypes.c_uint8 * 192)()
    r = lib.bls_pubkey(_cbuf(sk.to_bytes(32, "big")), out)
    return bytes(out) if r else None


def bls_verify_aggregate(
    pubkeys: Sequence[bytes], msg: bytes, sig: bytes, dst: bytes
) -> Optional[bool]:
    """Native aggregate BLS verify; None = library unavailable."""
    if not pubkeys or len(sig) != 96 or any(len(p) != 192 for p in pubkeys):
        return False
    lib = _load_bls()
    if lib is None:
        return None
    cat = b"".join(pubkeys)
    r = lib.bls_verify_aggregate(
        _cbuf(cat), len(pubkeys), _cbuf(msg), len(msg), _cbuf(sig),
        _cbuf(dst), len(dst),
    )
    return bool(r)


def bls_verify_batch_rlc(
    pubkeys: Sequence[bytes],
    msgs: Sequence[bytes],
    sigs: Sequence[bytes],
    rands: Sequence[int],
    dst: bytes,
) -> Optional[bool]:
    """Native random-linear-combination batch verify of k aggregate
    signatures sharing ONE signer set (the QC-plane fast path): checks
    e(sum r_i*sig_i, G2) == e(sum r_i*H(m_i), agg_pk) with two Miller
    loops total. True = every cert in the batch is valid; False = the
    batch fails (the caller bisects); None = library unavailable."""
    k = len(msgs)
    if (
        k == 0
        or len(sigs) != k
        or len(rands) != k
        or not pubkeys
        or any(len(p) != 192 for p in pubkeys)
        or any(len(s) != 96 for s in sigs)
        or any(not 0 < r < (1 << 256) for r in rands)
    ):
        return False
    lib = _load_bls()
    if lib is None:
        return None
    cat_msgs, offs = b"".join(msgs), np.zeros(k + 1, dtype=np.int64)
    np.cumsum([len(m) for m in msgs], out=offs[1:])
    r = lib.bls_verify_batch_rlc(
        _cbuf(b"".join(pubkeys)), len(pubkeys),
        _cbuf(cat_msgs), np.ascontiguousarray(offs), k,
        _cbuf(b"".join(sigs)),
        _cbuf(b"".join(ri.to_bytes(32, "big") for ri in rands)),
        _cbuf(dst), len(dst),
    )
    return bool(r)


def available() -> bool:
    return _load() is not None


def num_threads() -> int:
    lib = _load()
    return lib.native_num_threads() if lib is not None else 1


def _concat_offsets(msgs: Sequence[bytes]):
    # summed in Python: numpy gives the interpreter lock up around a loop
    # over 500 elements, and prepare_wire's caller must not queue for it
    offs = np.fromiter(
        itertools.accumulate(map(len, msgs), initial=0), np.int64,
        len(msgs) + 1,
    )
    cat = b"".join(msgs)
    buf = np.frombuffer(cat, dtype=np.uint8) if cat else np.zeros(1, np.uint8)
    return np.ascontiguousarray(buf), offs


def challenge_batch(
    r: np.ndarray, a: np.ndarray, msgs: Sequence[bytes]
) -> np.ndarray:
    """(n, 32) R encodings, (n, 32) A encodings, n message byte strings ->
    (n, 32) uint8 little-endian challenge scalars (mod L, canonical)."""
    n = len(msgs)
    assert r.shape == (n, 32) and a.shape == (n, 32), (r.shape, a.shape)
    out = np.empty((n, 32), dtype=np.uint8)
    if n == 0:
        return out
    lib = _load()
    if lib is not None:
        cat, offs = _concat_offsets(msgs)
        lib.challenge_batch(
            np.ascontiguousarray(r), np.ascontiguousarray(a),
            cat, offs, n, out,
        )
        return out
    from ..crypto import ed25519_cpu as ref  # fallback: per-item Python

    for i, m in enumerate(msgs):
        k = ref.challenge_scalar(r[i].tobytes(), a[i].tobytes(), m)
        out[i] = np.frombuffer(k.to_bytes(32, "little"), np.uint8)
    return out


def prepare_wire(
    pub: bytes, sig: bytes, msgs: Sequence[bytes], ok: bytearray,
    size: int, hold_lock: bool,
) -> "Optional[tuple[np.ndarray, np.ndarray]]":
    """A pile's joined bytes -> what the verify kernel takes, in one call.

    `pub` is the n public keys joined (32 bytes each), `sig` the n
    signatures R || S joined (64 each), `ok` one byte a row (0 = already
    rejected). Returns (wire (size, 96) uint8 of S || k || R rows,
    precheck (size,) bool = ok and S < L and R.y < p), rows n..size zeroed
    and false; None when the library is absent.

    `hold_lock` keeps the interpreter lock and hashes on the calling
    thread; otherwise the call gives the lock up and fans out over the
    OpenMP pool. The bytes are the same either way. Nothing here but the
    lock-released call itself lets go of the lock (no numpy loop over 500
    elements), so a pass queues for it at most once."""
    lib = _load()
    if lib is None:
        return None
    n = len(msgs)
    if (len(pub), len(sig), len(ok)) != (32 * n, 64 * n, n) or size < n:
        raise ValueError(
            f"prepare_wire: {n} messages with {len(pub)} key bytes, "
            f"{len(sig)} signature bytes, {len(ok)} ok bytes, size {size}"
        )
    cat, offs = _concat_offsets(msgs)
    wire = np.empty((size, 96), dtype=np.uint8)
    precheck = np.empty(size, dtype=np.bool_)
    (_lib_held if hold_lock else lib).prepare_wire(
        np.frombuffer(pub, np.uint8), np.frombuffer(sig, np.uint8), cat, offs,
        np.frombuffer(ok, np.uint8), n, size, 0 if hold_lock else 1,
        wire, precheck.view(np.uint8),
    )
    return wire, precheck


def ladder_rows(
    wire: np.ndarray, pub: bytes, precheck: np.ndarray,
    rows: Sequence[int], size: int,
) -> "Optional[tuple[np.ndarray, np.ndarray, np.ndarray]]":
    """The rows of a staged pile (prepare_wire's `wire` and `precheck`,
    `pub` the keys it was staged from) whose key has no table, copied out
    for the table-free program: -> (rows as an int64 array, out (size, 128)
    uint8 of S || k || R || A rows, out_pre (size,) bool), padded with
    zeros, and `precheck` cleared at `rows`; None when the library is
    absent. Memory copies under the interpreter lock, whatever the size:
    the numpy equivalent is four loops over more than 500 elements, each
    of which gives the lock up and queues for it behind the event loop."""
    if _load() is None:
        return None
    idx = np.array(rows, dtype=np.int64)
    out = np.empty((size, 128), dtype=np.uint8)
    out_pre = np.empty(size, dtype=np.bool_)
    _lib_held.ladder_rows(
        wire, np.frombuffer(pub, np.uint8), precheck.view(np.uint8), idx,
        len(idx), size, out, out_pre.view(np.uint8),
    )
    return idx, out, out_pre


def sc_reduce_batch(digests: np.ndarray) -> np.ndarray:
    """(n, 64) uint8 little-endian 512-bit values -> (n, 32) uint8
    canonical scalars mod L (the Ed25519 group order)."""
    n = len(digests)
    assert digests.shape == (n, 64), digests.shape
    out = np.empty((n, 32), dtype=np.uint8)
    if n == 0:
        return out
    lib = _load()
    if lib is not None:
        lib.sc_reduce_batch(np.ascontiguousarray(digests), n, out)
        return out
    from ..crypto import ed25519_cpu as ref  # fallback: per-item Python

    for i in range(n):
        v = int.from_bytes(digests[i].tobytes(), "little") % ref.L
        out[i] = np.frombuffer(v.to_bytes(32, "little"), np.uint8)
    return out


def sha512_batch(msgs: Sequence[bytes]) -> np.ndarray:
    """n message byte strings -> (n, 64) uint8 SHA-512 digests."""
    n = len(msgs)
    out = np.empty((n, 64), dtype=np.uint8)
    if n == 0:
        return out
    lib = _load()
    if lib is not None:
        cat, offs = _concat_offsets(msgs)
        lib.sha512_batch(cat, offs, n, out)
        return out
    import hashlib

    for i, m in enumerate(msgs):
        out[i] = np.frombuffer(hashlib.sha512(m).digest(), np.uint8)
    return out


# ---------------------------------------------------------------------------
# canonical-JSON encoder (CPython extension module, canonjson.cpp)
# ---------------------------------------------------------------------------

_SRC_CANON = os.path.join(os.path.dirname(__file__), "canonjson.cpp")
_canon_lock = threading.Lock()
_canon_mod = None
_canon_tried = False


def _python_includes():
    import sysconfig

    return [f"-I{sysconfig.get_path('include')}"]


def _load_canonjson():
    """Build (on demand) and import the _canonjson extension; None on any
    failure — callers keep the pure-json path. Unlike the ctypes
    libraries this is a real CPython extension (it walks Python objects),
    so it is imported via ExtensionFileLoader, not CDLL."""
    global _canon_mod, _canon_tried
    if _canon_tried:  # lock-free fast path: _canon_mod is write-once
        return _canon_mod
    with _canon_lock:
        if _canon_tried:
            return _canon_mod
        _canon_tried = True  # every exit below is final (no per-call retry)
        so = _ensure_built(_SRC_CANON, extra=_python_includes())
        if so is None:
            return None
        try:
            import importlib.machinery
            import importlib.util

            loader = importlib.machinery.ExtensionFileLoader("_canonjson", so)
            spec = importlib.util.spec_from_loader("_canonjson", loader)
            mod = importlib.util.module_from_spec(spec)
            loader.exec_module(mod)
        except (ImportError, OSError) as e:
            log.warning("canonjson load failed: %s — json fallback", e)
            return None
        # self-test: byte-exact equivalence on a representative sample; a
        # silently divergent encoder would FORK the committee (digests),
        # so any mismatch rejects the library outright
        import json as _json

        samples = [
            {"kind": "commit", "seq": 1, "view": 0, "digest": "ab" * 32,
             "sig": "", "b": [1, 2, [3]], "n": None, "t": True},
            {"z": "\x00\x1f\"\\\né€\U0001f600", "a": -(2**80)},
            {"": {"nested": ["\ud800", 2**63 - 1, -(2**63)]}},
        ]
        for s in samples:
            want = _json.dumps(s, sort_keys=True, separators=(",", ":")).encode(
                "utf-8", "surrogatepass"
            )
            if mod.encode(s) != want:
                log.warning("canonjson self-test mismatch — json fallback")
                return None
        _canon_mod = mod
        return mod


def canonjson_encode(obj):
    """Native canonical encode, or None when the library is unavailable
    or the object leaves the wire subset (caller falls back to json)."""
    mod = _load_canonjson()
    if mod is None:
        return None
    try:
        return mod.encode(obj)
    except (TypeError, RecursionError):
        return None


def canonjson_available() -> bool:
    return _load_canonjson() is not None


def status() -> Dict[str, dict]:
    """Which of the four native libraries are in use (loading, and if
    need be building, each one now), from which file, and whether this
    process compiled it."""
    loaded = {
        _SRC: available(),
        _SRC_ED: ed25519_available(),
        _SRC_BLS: bls_available(),
        _SRC_CANON: canonjson_available(),
    }
    return {
        os.path.splitext(os.path.basename(src))[0]: {
            "loaded": ok, **_builds.get(src, {"path": None, "built": False})
        }
        for src, ok in loaded.items()
    }
