"""Steady-state hole repair and failover deferral (round-4 fixes for the
qc-n64 chaos near-stall, VERDICT round-3 weak #3 / next-round #6).

Execution is sequential per replica, so one lost frame (a commit QC, a
pre-prepare, a NEW-VIEW) left a replica stalled forever while the
committee progressed; its unilateral view change was never joined,
freezing it into a deaf zombie. These tests pin the repair machinery:

1. A fully-partitioned replica catches up after healing via slot probes
   (blocks adopted against commit QCs) WITHOUT any view change.
2. The failover timer defers while the committee demonstrably commits
   (max_committed_seen advances) and the stall is local.
3. A replica that misses the NEW-VIEW broadcast re-fetches it from a
   peer (NewViewFetch) and rejoins the new view.
4. A dead primary with no committee progress still fails over (the
   deferral must not break classic liveness).
"""

import asyncio

import pytest

from simple_pbft_tpu.committee import LocalCommittee
from simple_pbft_tpu.sim import sim_run
from simple_pbft_tpu.transport.local import FaultPlan


def run(coro, timeout=120):
    # Virtual clock (ISSUE 13 satellite): these tests are TIMER-SHAPED —
    # deferral windows, probe cadences, failover ladders — and were the
    # suite's flake source under full-suite CPU saturation (view
    # timeouts repeatedly lengthened: 0.6 -> 1.5 -> 2.5 s, see the
    # in-test comments' history). Under the simulation runtime the
    # timers are VIRTUAL: a saturated host cannot stall the loop past a
    # deadline because deadlines only advance when the loop is idle —
    # and the sleeps compress, so the tests are faster too. ``timeout``
    # is now a virtual bound (generous; it no longer needs host slack).
    return sim_run(asyncio.wait_for(coro, timeout))


def _cut_all(plan: FaultPlan, com: LocalCommittee, rid: str) -> None:
    """Symmetric partition of one replica from every other endpoint."""
    for other in list(com.cfg.replica_ids) + [c.id for c in com.clients]:
        if other != rid:
            plan.partitions.add((other, rid))
            plan.partitions.add((rid, other))


async def _pump_n(client, n, prefix="x"):
    for i in range(n):
        await client.submit(f"put {prefix}{i} v{i}")


def test_partitioned_replica_catches_up_without_view_change():
    """QC mode: cut r3 off mid-load; after healing, slot probes must
    repair its holes (commit QCs + adopted blocks) with zero view
    changes committee-wide."""

    async def scenario():
        plan = FaultPlan(seed=7)
        com = LocalCommittee.build(
            n=4, clients=1, fault_plan=plan, qc_mode=True,
            # 2.5 s: the assertion is BEHAVIORAL (repair happens in-view,
            # zero failovers) — at 1.0 s a saturated full-suite host can
            # stall the event loop past the timer and fail it spuriously
            view_timeout=2.5, checkpoint_interval=512,
        )
        com.start()
        c = com.clients[0]
        c.request_timeout = 2.0
        await _pump_n(c, 3, "pre")
        victim = com.replica("r3")
        # the client accepts on 2f+1 speculative replies, so the last
        # "pre" request can return before the victim has executed it:
        # let the victim draw level before cutting it off, or base_exec
        # is read one slot early
        deadline = asyncio.get_event_loop().time() + 10.0
        while (
            victim.executed_seq < max(r.executed_seq for r in com.replicas)
            and asyncio.get_event_loop().time() < deadline
        ):
            await asyncio.sleep(0.05)
        base_exec = victim.executed_seq
        assert base_exec == max(r.executed_seq for r in com.replicas)
        _cut_all(plan, com, "r3")
        await _pump_n(c, 6, "cut")
        assert victim.executed_seq == base_exec  # truly isolated
        plan.heal()
        # post-heal traffic gives the victim the signal something is
        # missing (new pre-prepares/QCs beyond its frontier arm the
        # probe chain); a totally quiet committee has nothing to repair
        # toward until the next checkpoint broadcast
        await _pump_n(c, 2, "post")
        # probes fire at view_timeout/2 (jittered); give generous rounds —
        # under batch-run CPU contention a round trip can take seconds
        deadline = asyncio.get_event_loop().time() + 45.0
        target = max(r.executed_seq for r in com.replicas)
        while (
            victim.executed_seq < target
            and asyncio.get_event_loop().time() < deadline
        ):
            await asyncio.sleep(0.25)
        assert victim.executed_seq == target, (
            victim.executed_seq, target, victim.metrics)
        # repair happened in-view: no failover anywhere
        assert all(r.view == 0 for r in com.replicas)
        assert sum(r.metrics.get("views_installed", 0) for r in com.replicas) == 0
        assert victim.metrics.get("slot_probes_sent", 0) > 0
        await com.stop()

    run(scenario())


def test_failover_defers_while_committee_commits():
    """The victim's timer must defer (metrics: failover_deferred) rather
    than start a view change while observed commits advance."""

    async def scenario():
        plan = FaultPlan(seed=11)
        com = LocalCommittee.build(
            n=4, clients=1, fault_plan=plan, qc_mode=True,
            # 1.5 s: like the catch-up test above, the assertion is
            # BEHAVIORAL (no failover while commits advance) — at 0.6 s a
            # saturated full-suite host stalls the loop past the timer
            # with no observable progress and fires it spuriously
            view_timeout=1.5, checkpoint_interval=512,
        )
        com.start()
        c = com.clients[0]
        c.request_timeout = 2.0
        await _pump_n(c, 2, "pre")
        victim = com.replica("r3")
        _cut_all(plan, com, "r3")
        await _pump_n(c, 4, "cut")
        plan.heal()
        # park client work on the victim so its timer arms: relay a
        # request through it by healing first (normal traffic resumes)
        await _pump_n(c, 8, "post")
        # long enough for an (incorrectly) undeferred timer to fire
        await asyncio.sleep(2.5)
        assert sum(
            r.metrics.get("view_changes_started", 0) for r in com.replicas
        ) == 0
        await com.stop()

    run(scenario())


def test_newview_refetch_after_missed_broadcast():
    """Crash the primary of view 0 and cut ONLY the new primary's link
    TO r3 (one-directional): r3's VIEW-CHANGE still reaches r1, the
    failover completes, but r3 never receives the NEW-VIEW broadcast.
    Seeing view-1 traffic from r2, r3 must fetch the certificate from
    the rotating peer (NewViewFetch) and install view 1."""

    async def scenario():
        plan = FaultPlan(seed=13)
        com = LocalCommittee.build(
            n=4, clients=1, fault_plan=plan, qc_mode=False,
            view_timeout=0.8, checkpoint_interval=512,
        )
        com.start()
        c = com.clients[0]
        c.request_timeout = 2.0
        c.hedge = 2
        await _pump_n(c, 2, "pre")
        victim = com.replica("r3")
        plan.partitions.add(("r1", "r3"))  # new primary -> victim only
        com.replica("r0").kill()
        # keep load flowing so view-1 traffic exists for the hint
        pump = asyncio.get_event_loop().create_task(_pump_n(c, 30, "post"))
        deadline = asyncio.get_event_loop().time() + 25.0
        while (
            victim.view < 1 and asyncio.get_event_loop().time() < deadline
        ):
            await asyncio.sleep(0.2)
        pump.cancel()
        try:
            await pump
        except (asyncio.CancelledError, asyncio.TimeoutError, TimeoutError):
            pass
        assert victim.view >= 1, (victim.view, victim.metrics)
        assert victim.metrics.get("newview_fetches_sent", 0) > 0
        assert any(
            r.metrics.get("newview_fetches_served", 0) > 0
            for r in com.replicas
        )
        await com.stop()

    run(scenario())


def test_dead_primary_still_fails_over():
    """No committee progress + outstanding work => the classic view
    change fires despite the deferral logic."""

    async def scenario():
        com = LocalCommittee.build(
            n=4, clients=1, qc_mode=False,
            view_timeout=0.6, checkpoint_interval=512,
        )
        com.start()
        c = com.clients[0]
        c.request_timeout = 2.0
        c.hedge = 2
        await _pump_n(c, 2, "pre")
        com.replica("r0").kill()
        # next request must commit under the successor primary
        await asyncio.wait_for(c.submit("put after crash"), 20.0)
        assert all(
            r.view >= 1 for r in com.replicas if r._running
        ), [r.view for r in com.replicas]
        await com.stop()

    run(scenario())
