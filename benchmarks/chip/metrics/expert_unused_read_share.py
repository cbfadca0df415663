"""The share of the held experts whose weights the expert layers read
though no pair was routed to them: 100 * (1 - touched / read), summed over
every call in the window, from the sessions' ``last_stats``
(``moe_touched``, ``moe_read``). A program without those counts gives
nothing."""


def read(run):
    stats = [c["stats"] for c in run.window.calls]
    if not stats or any("moe_read" not in s for s in stats):
        return None
    read_ = sum(s["moe_read"] for s in stats)
    return 100.0 * (1.0 - sum(s["moe_touched"] for s in stats) / read_)
