"""The collector's policy for a process that serves.

The interpreter's defaults (a young collection every 700 net container
allocations, the middle and old generations every tenth of the one below)
suit a script. A serving node's heap is mostly immortal (the modules, JAX,
the key tables' host copies, the committee, every client's state), and what
the protocol allocates lives for one block period: decoded votes held in an
instance's log, batch items, futures, sweeps. A young collection a hundred
times a second falls far inside one block, finds nearly every protocol
object alive, promotes it, and examines it again in the middle and the old
generation before it dies by reference count; every full collection walks
the immortal part besides.

``settle_heap()`` is called once what a node serves from is built and warm,
before its first request: one full collection, then everything alive moves
to the permanent generation (``gc.freeze``: no collection examines it
again), then the young threshold is raised so that a young collection falls
about once a block and finds most of the block's objects already dead.
Full collections stay enabled (thresholds 1 and 2 keep the values found);
what they walk is what was allocated since the freeze. ``release_heap()``
undoes both on the way down. The pair nests by a count: two committees in
one process settle once and release once, on the last one's stop.

Nothing a user sets reaches the policy: no flag, variable or config key.
Who calls: ``LocalCommittee.start()`` / ``stop()`` and ``node.run_node``.
What shows that it is on: ``gc.get_freeze_count()`` in the heartbeat's
snapshot (``loop_lag.gc_frozen``), and ``gc.full`` beside ``gc.pause``
(spans.py).
"""

from __future__ import annotations

import gc

# Net container allocations between two young collections while settled.
# Sized to a block, which every deployment has. A saturated loop allocates
# some 80,000 containers a second whatever the committee's size (read on
# the chip's host at n=16 and n=64 alike), so at 50,000 a young collection
# falls 1.5 times a second against 150-175 at the default: once every
# block or two where blocks take 0.3-0.4 s, and what it finds alive is the
# open blocks, not every message of them. Chip runs at 10,000 / 20,000 /
# 50,000 / 100,000 (PERF.md sec. 6, PR 37): the collector's share of the
# wall time falls 14.6 -> 3.8 / 2.6 / 1.8 / 1.4% at n=64 and 12.7 -> 4.0 /
# 3.3 / 2.3 / 1.8% at n=16; the rate is level from 20,000 on, and at
# 100,000 the longest pause doubles (97-112 ms) for no rate.
YOUNG_THRESHOLD = 50_000

_settled = 0  # settles outstanding: the collector is the process's, so is this
_found = gc.get_threshold()  # what the first settle replaced, for the last release


def settle_heap() -> None:
    """Collect, freeze what is alive, raise the young threshold. A call
    while settled is counted and does nothing else."""
    global _settled, _found
    _settled += 1
    if _settled > 1:
        return
    _found = gc.get_threshold()
    gc.collect()
    gc.freeze()
    gc.set_threshold(YOUNG_THRESHOLD, *_found[1:])


def release_heap() -> None:
    """Undo one ``settle_heap()``; the last one unfreezes and restores
    the thresholds the first found. Without a settle before it, nothing."""
    global _settled
    if not _settled:
        return
    _settled -= 1
    if not _settled:
        gc.unfreeze()
        gc.set_threshold(*_found)
