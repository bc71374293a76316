"""Tier-1 wiring for scripts/trace_smoke.py: the end-to-end guarantee
`colearn train --trace-dir` makes (trace parses, expected phase spans
present, spans cover the round wall time) holds on 2 synthetic rounds."""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "scripts"))

import trace_smoke  # noqa: E402


def test_trace_smoke(tmp_path):
    out = trace_smoke.main(str(tmp_path))
    assert out["coverage"] >= 0.95
    assert "enqueue" in out["phases"]
    assert "client_update" not in out["phases"]
    assert os.path.exists(out["trace_file"])
    assert "phase coverage" in out["summary"]
