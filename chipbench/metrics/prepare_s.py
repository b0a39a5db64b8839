"""Host seconds of the program's preparation of the matrix: the user's
``bcsr.from_scipy`` and ``ops.prepare_sparse`` with the configured reorder,
timed on the host clock by the benchmark's ``bench.prepare`` span."""


def read(ctx):
    return ctx.setup.get("prepare_s")
