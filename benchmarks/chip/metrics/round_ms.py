"""Mean host wall time of one round of the actor pipeline: the session
history's round makespans over the window, in milliseconds."""


def read(run):
    ms = [m for c in run.window.calls for m in c["makespans"]]
    return 1e3 * sum(ms) / len(ms)
