"""Device microseconds per prefill call of the operations under the
program's ``mla`` scope (latent attention: its projections and its core),
in the traced window (``perfbench/scopes.py``)."""

from perfbench import scopes


def read(run):
    return scopes.per_call_us(run, __file__, "mla")
