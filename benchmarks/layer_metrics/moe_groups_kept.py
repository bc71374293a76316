"""Models (``models/moe.py`` ``ExpertShare``): the groups of experts a
token's choice is made within, from the gauge ``moe.groups_kept`` (4 of
``moe.groups`` 8 in ``ling_kda_mla_hybrid``; 1 of 1 where the choice is
over all the experts).  A program that never set it (the parent of the PR
that added the gauge, or one without the share layer) reads None, and the
line leaves the metric out."""

from benchmarks.layer_metrics import _program


def read(r):
    return _program.counter("moe.groups_kept")
