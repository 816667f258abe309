"""Logging + instrumentation: rotating per-node files (zapConfig parity)
and histogram correctness."""

import json
import logging
import os

from simple_pbft_tpu.logutil import (
    ROTATE_BACKUPS,
    Histogram,
    ReplicaStats,
    setup_node_logging,
)


def test_histogram_summary():
    h = Histogram(bounds=[1, 2, 4, 8])
    for v in [0.5, 1.5, 3, 3, 7, 100]:
        h.record(v)
    s = h.summary()
    assert s["count"] == 6
    assert s["min"] == 0.5 and s["max"] == 100
    assert 0 < s["p50"] <= 8
    assert s["p99"] >= s["p50"]
    # empty histograms emit the FULL zeroed schema (ISSUE 2 satellite):
    # snapshot consumers index p50/p99 unconditionally on idle nodes
    empty = Histogram().summary()
    assert empty == {
        "count": 0, "mean": 0.0, "min": 0.0, "max": 0.0,
        "p50": 0.0, "p90": 0.0, "p99": 0.0,
    }


def test_replica_stats_dump_is_json():
    st = ReplicaStats()
    st.sweep_size.record(3)
    st.sweep_ms.record(1.5)
    st.verify_items += 10
    st.verify_seconds += 0.01
    doc = json.loads(st.dump({"committed_blocks": 2}))
    assert doc["metrics"]["committed_blocks"] == 2
    assert doc["verify_per_s"] == 1000.0
    assert doc["sweep_size"]["count"] == 1
    assert doc["sweep_ms"]["count"] == 1


def test_per_node_rotating_file(tmp_path):
    root = setup_node_logging("rX", str(tmp_path), level="INFO", console=False)
    logging.getLogger("pbft.test").info("hello %s", "world")
    for h in root.handlers:
        h.flush()
    path = tmp_path / "rX.log"
    assert path.exists()
    line = path.read_text().strip()
    # caller annotation + tab-separated structure (zap parity)
    assert "hello world" in line and "test_logutil.py" in line
    handler = root.handlers[0]
    assert handler.backupCount == ROTATE_BACKUPS
    # idempotent: re-setup must not duplicate handlers
    root2 = setup_node_logging("rX", str(tmp_path), console=False)
    assert len(root2.handlers) == 1
    for h in root2.handlers:
        root2.removeHandler(h)  # leave global state clean for other tests