"""Device milliseconds per prefill step of the attention layers: the
summed device time of the traced window's operations under the program's
``model.mla`` scope (projections, rope, scores, softmax, context, the
cache write), over the window's steps (``chipbench/op_scopes.py``)."""
from chipbench import op_scopes


def read(ctx):
    return op_scopes.ms_per_call(ctx, lambda p: p == "model.mla")
