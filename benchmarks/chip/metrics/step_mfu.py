"""The model step's share of the chip's peak: the FLOPs the math needs for
every prompt token prefilled and every token decoded in the window (counted
from the configuration's widths by its ``configs/<name>.py``), over the
rounds' wall time times chips times the peak bf16 rate, in percent."""


def read(run):
    m, spec = run.module, run.spec
    flops = 0.0
    for s in run.window.served:
        p = s.req.prompt.size
        flops += m.prefill_flops(spec, p)
        flops += sum(m.token_flops(spec, p + k)
                     for k in range(1, len(s.tokens)))
    wall = sum(x for c in run.window.calls for x in c["makespans"])
    return 100.0 * flops / (wall * run.chips * run.peaks["bf16_flops"])
