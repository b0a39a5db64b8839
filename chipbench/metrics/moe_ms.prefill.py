"""Device milliseconds per prefill step of the MoE layers: the summed
device time of the traced window's operations under the program's
``smat.moe.*`` scopes (route, dispatch, experts, combine, shared), over
the window's steps (``chipbench/op_scopes.py``)."""
from chipbench import op_scopes


def read(ctx):
    return op_scopes.ms_per_call(ctx, lambda p: p.startswith("smat.moe."))
