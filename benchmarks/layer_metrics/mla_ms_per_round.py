"""Models (``models/mla.py`` under ``models/xing4.py``'s scope ``mla``): device
time on the first chip, per round, of latent attention whole in the round
program: the low-rank projections, rotary, the heads' relayouts and the
three flash calls, every pass, in ms (``_scopes.py``, by part);
``mla_attention_ms_per_round`` is the kernel alone."""

from benchmarks.layer_metrics import _scopes


def read(r):
    return _scopes.under_ms(r, "mla")
