"""Device: what the first chip idles between programs, in ms a round.  The
idle intervals of the traced window (complement of the union of ``XLA
Ops``) that are not wholly inside one ``XLA Modules`` execution: the device
has run out of work and waits for the host.  The rest of
``device_idle_share`` is between two operations of one running program."""

from benchmarks.layer_metrics import _program


def read(r):
    if r.trace is None or not r.trace.devices or not r.rounds:
        return None
    gaps, _ = _program.host_gaps(r.trace, min(r.trace.devices))
    return sum(end - start for start, end in gaps) / 1e6 / r.rounds
