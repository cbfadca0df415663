"""Output tokens of every call in the window over the wall time from the
first call's start to the last call's return."""


def read(run):
    win = run.window
    return sum(len(s.tokens) for s in win.served) / win.span
