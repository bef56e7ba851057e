"""The 95th percentile, over every request answered in the window, of the
seconds from its submission to its answer being ready on the device
(linear interpolation between order statistics)."""
import numpy as np


def read(ctx):
    lat = ctx.window.latencies_s
    return float(np.percentile(lat, 95)) if lat else None
