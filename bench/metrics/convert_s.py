"""Registration of the cell's matrix, once per run in set-up: host
triplets through ``to_coo`` and ``SparseOperator.from_coo``, blocked until
the plan's arrays are on the device."""


def read(ctx):
    return ctx.convert_s
