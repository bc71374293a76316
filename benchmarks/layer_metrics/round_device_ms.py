"""Round program (``fed/programs.py``): device time of one round, in ms.
Sum over the traced window of the executions of every compiled program
but the evaluation's (told by its module name), averaged over the chips,
divided by the rounds traced."""

import statistics

from benchmarks.harness import xplane


def read(r):
    if r.trace is None or not r.trace.devices or not r.rounds:
        return None
    window = r.trace.window_ns
    per_chip = [
        sum(dur for name, _, dur in xplane.clip(d.modules, window)
            if "eval" not in xplane.module_name(name))
        for d in r.trace.devices.values()]
    if not any(per_chip):
        return None
    return statistics.fmean(per_chip) / 1e6 / r.rounds
