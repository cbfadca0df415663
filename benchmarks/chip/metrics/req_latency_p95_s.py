"""95th percentile, over every request due in the window, of the return of
the call that answered it minus the request's due time. A request that was
not answered counts as answered when the window closed, a lower bound; such
a run is not correct."""
import numpy as np


def read(run):
    win = run.window
    if not win.served:
        return None
    lat = [s.end - s.req.due for s in win.served]
    lat += [win.calls[-1]["end"]] * (win.attempted - len(win.served))
    return float(np.percentile(lat, 95))
