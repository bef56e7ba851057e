"""The share of the live lanes the kernel walked in the window whose X
value came from VMEM, in %: the program's ``sellcs/x_resident_lanes``
over ``sellcs/lanes_live`` counters. The rest were gathered from HBM, one
DMA each. A program that keeps no such counter has nothing to read."""


def read(ctx):
    reg = ctx.registry
    if reg is None or not any(c["name"] == "sellcs/x_resident_lanes"
                              for c in reg.as_dict()["counters"]):
        return None
    live = reg.counter("sellcs/lanes_live").value
    if not live:
        return None
    return reg.counter("sellcs/x_resident_lanes").value / live * 100
