"""Gap attribution on synthetic planes, and on the recorded round-5 trace
(which has no annotations, so every gap reads ``unannotated``)."""

from types import SimpleNamespace as NS

import pytest

import host_gaps
import trace_reduce
from test_trace_reduce import RECORDED

MS = 1_000_000  # ns


def ev(name, start_ms, dur_ms):
    return NS(name=name, start_ns=start_ms * MS, duration_ns=dur_ms * MS)


def trace(modules, *threads):
    """A device plane with ``modules`` [(start_ms, dur_ms)] and a host
    plane with one line per thread of [(name, start_ms, dur_ms)]."""
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules",
           events=[ev("jit_fn", s, d) for s, d in modules])])
    host = NS(name="/host:CPU", lines=[
        NS(name=f"thread{i}", events=[ev(*e) for e in thread])
        for i, thread in enumerate(threads)])
    return NS(planes=[device, host])


def found(data):
    return host_gaps.attribute_gaps(
        host_gaps.device_gaps(data), host_gaps.host_segments(data))


def test_flatten_gives_each_instant_to_the_innermost_event():
    flat = host_gaps.flatten([
        (0, 100, "loop.route"), (10, 30, "loop.sign_vote"),
        (30, 60, "loop.send"), (200, 250, "loop.ingest"),
        (40, 50, "gc.pause")])
    assert flat == [
        (0, 10, "loop.route"), (10, 30, "loop.sign_vote"),
        (30, 40, "loop.send"), (40, 50, "gc.pause"), (50, 60, "loop.send"),
        (60, 100, "loop.route"), (200, 250, "loop.ingest")]
    # a child that outlives its parent is cut to it
    assert host_gaps.flatten([(0, 10, "loop.route"), (5, 20, "loop.send")]) \
        == [(0, 5, "loop.route"), (5, 10, "loop.send")]


def test_the_largest_overlap_names_the_gap():
    # one gap, 10..110 ms: route holds 60 ms of it less a nested 25 ms of
    # send, ingest 30 ms; other events are not the program's
    data = trace([(0, 10), (110, 10)], [
        ("loop.route", 5, 65), ("loop.send", 20, 25),
        ("loop.ingest", 75, 30), ("$threading.py:1 wait", 0, 200)])
    got = found(data)
    assert got["gaps"] == 1
    assert got["idle_gaps"] == [["loop.route", pytest.approx(0.100)]]
    shares = got["idle_share_by_stage"]
    assert shares["loop.route"] == pytest.approx(35.0)  # 60 - 25 nested
    assert shares["loop.ingest"] == pytest.approx(30.0)
    assert shares["loop.send"] == pytest.approx(25.0)
    assert got["unannotated_share"] == pytest.approx(10.0)
    assert got["wait_only_share"] == pytest.approx(0.0)


def test_under_half_coverage_reads_unannotated():
    data = trace([(0, 10), (110, 10)], [("loop.route", 20, 40)])
    got = found(data)
    assert got["idle_gaps"] == [["unannotated", pytest.approx(0.100)]]
    assert got["unannotated_share"] == pytest.approx(60.0)
    # exactly half is enough
    data = trace([(0, 10), (110, 10)], [("loop.route", 20, 50)])
    assert found(data)["idle_gaps"][0][0] == "loop.route"


def test_two_host_threads_sum_and_a_wait_names_only_an_empty_gap():
    loop = [("loop.execute", 10, 30), ("loop.route", 40, 20),
            ("loop.ingest", 130, 10)]
    dispatcher = [("verify.collect", 10, 100), ("verify.host_prep", 60, 45),
                  ("verify.collect", 120, 100)]
    data = trace([(0, 10), (110, 10), (220, 10)], loop, dispatcher)
    got = found(data)
    # gap 1 (10..110): execute 30, route 20, host_prep 45 on the second
    # thread, the wait 100 less the nested prep = 55: work covers 95 ms
    # gap 2 (120..220): ingest 10 ms of work, the wait covers all of it
    assert got["idle_gaps"] == [
        ["verify.host_prep", pytest.approx(0.100)],
        ["verify.collect", pytest.approx(0.100)]]
    shares = got["idle_share_by_stage"]
    assert shares["verify.collect"] == pytest.approx(100 * 155 / 200)
    assert shares["verify.host_prep"] == pytest.approx(100 * 45 / 200)
    assert shares["loop.execute"] == pytest.approx(15.0)
    assert sum(shares.values()) > 100.0  # two threads at once
    assert got["unannotated_share"] == pytest.approx(0.0)
    # no thread worked in 5 ms of gap 1 and 90 ms of gap 2
    assert got["wait_only_share"] == pytest.approx(100 * 95 / 200)


def test_gaps_are_found_as_trace_reduce_finds_them():
    data = trace([(0, 10), (5, 10), (30, 5), (50, 5)])
    assert host_gaps.device_gaps(data) == [
        (15 * MS, 30 * MS), (35 * MS, 50 * MS)]
    assert found(data)["idle_gaps"] == [
        ["unannotated", pytest.approx(0.015)]] * 2


def test_the_recorded_trace_has_no_annotations():
    got = host_gaps.attribute(RECORDED)
    plain = trace_reduce.reduce_file(RECORDED)
    assert [s for _n, s in got["idle_gaps"]] == \
        [s for _n, s in plain["idle_gaps"]]
    assert {n for n, _s in got["idle_gaps"]} == {"unannotated"}
    assert got["idle_share_by_stage"] == {}
    assert got["unannotated_share"] == pytest.approx(100.0)
