"""Parallel (``parallel/``, the mesh path of ``fed/programs.py``): device
time of the all-reduce operations on the first chip, per round, in ms.
Includes the time an all-reduce waits for the slowest chip."""

from benchmarks.harness import xplane


def read(r):
    if r.trace is None or not r.trace.devices or not r.rounds:
        return None
    first = r.trace.devices[min(r.trace.devices)]
    times = xplane.self_times(xplane.clip(first.ops, r.trace.window_ns))
    spent = sum(t for name, t in times.items()
                if name.startswith("all-reduce"))
    return spent * 1e3 / r.rounds if spent else None
