"""Orchestration (``FederatedLearner.from_config``): seconds in the
program's ``from_config`` span (partition, packing, model init, placement),
from the gauge ``engine.from_config_s`` set where the span closes."""

from benchmarks.layer_metrics import _program


def read(r):
    return _program.counter("engine.from_config_s")
