"""Device milliseconds per call of the library op's padding of B: the
summed device time of the traced window's operations whose ``op_name``
lies under the program's ``smat.pad`` scope, over the window's calls
(``chipbench/xplane_ops.py``)."""
from chipbench import xplane_ops


def read(ctx):
    return xplane_ops.scope_ms_per_call(ctx, "smat.pad")
