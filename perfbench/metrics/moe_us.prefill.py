"""Device microseconds per prefill call of the operations under the
program's ``moe`` scope (router, held experts, shared experts and the
combine), in the traced window (``perfbench/scopes.py``)."""

from perfbench import scopes


def read(run):
    return scopes.per_call_us(run, __file__, "moe")
