"""95th percentile of every predict request due in the window, each timed
from the moment it was due on the open-loop schedule until its result
arrived."""
import numpy as np


def read(ctx):
    lat = ctx.window["latency_ms"]
    return float(np.percentile(lat, 95)) if len(lat) else None
