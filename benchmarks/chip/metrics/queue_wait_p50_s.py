"""Median, over the requests due in the window, of the start of the call
that took a request minus its due time (the benchmark's own clock)."""
import numpy as np


def read(run):
    waits = [s.start - s.req.due for s in run.window.served]
    return float(np.median(waits)) if waits else None
