"""Models (``models/ling3.py`` ``KdaMixer``, scope ``kda``): device time on
the first chip, per round, of the Kimi-delta-attention mixers whole in the
round program: the maps of the stream, the short convolutions and the norms
of q and k (``kda.conv``), the gates and decays (``kda.gate``), the chunked
rule (``kda.rule``) and the output map, every pass, in ms (``_scopes.py``,
by part); ``kda_rule_ms_per_round`` is the rule alone.  A program without
the scope (the parent of the PR that added it) reads None."""

from benchmarks.layer_metrics import _scopes


def read(r):
    return _scopes.under_ms(r, "kda")
