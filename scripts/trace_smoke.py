"""Trace-pipeline smoke: 2 synthetic rounds with --trace-dir semantics.

Runs a tiny FederatedLearner with span tracing on, then asserts the
written Chrome-trace JSON parses, contains the expected per-round phase
spans, and that the phase spans cover (>= 95% of) the round wall time —
the end-to-end guarantee `colearn train --trace-dir` makes.  Exits
non-zero on any violation; importable (``main(tmpdir)``) so the test
suite runs it in-process without a subprocess jax re-init.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REQUIRED_PHASES = {"fit", "round", "enqueue", "sync_metrics", "bookkeeping",
                   "evaluate"}


def main(trace_dir: str | None = None) -> dict:
    from colearn_federated_learning_tpu import telemetry
    from colearn_federated_learning_tpu.fed.engine import FederatedLearner
    from colearn_federated_learning_tpu.utils.config import get_config

    trace_dir = trace_dir or tempfile.mkdtemp(prefix="colearn_trace_smoke_")
    cfg = get_config("mnist_mlp_fedavg")
    cfg = cfg.replace(
        data=dataclasses.replace(cfg.data, dataset="mnist_tiny",
                                 num_clients=4),
        fed=dataclasses.replace(cfg.fed, rounds=2, local_steps=2,
                                batch_size=8, cohort_size=4),
        run=dataclasses.replace(cfg.run, backend="cpu", eval_every=1,
                                name="trace_smoke", trace_dir=trace_dir),
    )
    learner = FederatedLearner.from_config(cfg)
    learner.fit()

    path = learner.last_trace_path
    assert path, "fit() with trace_dir set did not write a trace"
    doc = telemetry.load_trace(path)           # raises if it doesn't parse
    spans = telemetry.trace_spans(doc)
    names = {s.name for s in spans}
    missing = REQUIRED_PHASES - names
    assert not missing, f"trace is missing phase spans: {sorted(missing)}"

    rounds = [s for s in spans if s.name == "round"]
    assert len(rounds) == 2, f"expected 2 round spans, got {len(rounds)}"
    round_total = sum(s.duration_s for s in rounds)
    child_total = sum(
        s.duration_s for s in spans
        if s.parent_id in {r.span_id for r in rounds}
    )
    coverage = child_total / round_total if round_total else 0.0
    assert coverage >= 0.95, (
        f"phase spans cover only {coverage:.1%} of round time"
    )
    assert doc["otherData"]["metrics"]["engine.rounds_total"] >= 2

    out = {
        "trace_file": path,
        "spans": len(spans),
        "phases": sorted(names),
        "coverage": coverage,
        "summary": telemetry.summarize_trace(doc),
    }
    return out


if __name__ == "__main__":
    result = main(sys.argv[1] if len(sys.argv) > 1 else None)
    print(result["summary"])
    print(json.dumps({k: v for k, v in result.items() if k != "summary"}))
