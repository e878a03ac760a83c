"""95th percentile of the wall time of one run_block call, each ending in
host values, over every call in the window (ms); host clock."""
import numpy as np


def read(ctx):
    return float(np.percentile(np.asarray(ctx["blocks_s"]) * 1e3, 95))
