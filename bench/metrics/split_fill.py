"""The share of the slots that hold virtual rows which hold a stored
nonzero of one, in %: the program's ``sellcs/split_lanes`` over
``sellcs/split_slots`` counters. Where no row is split, or the program
keeps no such counters, there is nothing to read."""


def read(ctx):
    reg = ctx.registry
    if reg is None:
        return None
    slots = reg.counter("sellcs/split_slots").value
    if not slots:
        return None
    return reg.counter("sellcs/split_lanes").value / slots * 100
