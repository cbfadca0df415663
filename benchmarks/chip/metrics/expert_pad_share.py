"""The share of the expert layers' computed rows that carried no routed
pair: 100 * (1 - pairs / rows), summed over every call in the window, from
the sessions' ``last_stats`` (``moe_pairs``, ``moe_rows``). A program
without those counts gives nothing."""


def read(run):
    stats = [c["stats"] for c in run.window.calls]
    if not stats or any("moe_rows" not in s for s in stats):
        return None
    rows = sum(s["moe_rows"] for s in stats)
    return 100.0 * (1.0 - sum(s["moe_pairs"] for s in stats) / rows)
