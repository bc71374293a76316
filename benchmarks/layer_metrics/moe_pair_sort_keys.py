"""Models (``models/moe.py`` ``ExpertShare``): the keys one block's sort
takes to put the held pairs in rows by expert, from the gauge
``moe.pair_sort_keys``: the block's membership table, held experts times
tokens (8 x 4,096 in ``nemotron_hybrid_seq16k`` and ``xing_mla_mhc_seq8k``;
a sort of every (token, choice) pair took 4,096 x 22 and 4,096 x 4), 0 if no
sort is left.  A program that never set it (the parent of the PR that
added the gauge, or one without the share layer) reads None, and the line
leaves the metric out."""

from benchmarks.layer_metrics import _program


def read(r):
    return _program.counter("moe.pair_sort_keys")
