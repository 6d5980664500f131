"""95th percentile of the wall times of every round in the window."""

import numpy as np


def read(ctx):
    return float(np.percentile(ctx.window.walls, 95))
