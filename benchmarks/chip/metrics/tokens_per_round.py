"""Tokens the serve loop produced per scheduling round: the sessions'
``last_stats`` tokens over the rounds of every call in the window."""


def read(run):
    calls = run.window.calls
    rounds = sum(len(c["makespans"]) for c in calls)
    return sum(c["stats"]["tokens"] for c in calls) / rounds
