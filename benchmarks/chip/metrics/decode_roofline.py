"""The decode path's share of its roofline: the least time the chip could
take for every decode step in the traced window, over the device time of
the programs that ran them, in percent.

The least time of one decode step is the larger of its FLOPs over the peak
rate and its bytes over the HBM bandwidth (``benchchip.peaks``), counted by
the configuration's ``decode_work`` from its widths at the served dtype:
the weights once, KV or recurrent state at the live positions only (not
the padded window), the new token's entries written. The live positions
come from the decode work items the first stage received.

The programs are found by the module names the TPU trace gives them: the
stage decode programs (``local_decode``) and the paged cache's gather and
decode scatter, which only decode steps run.
"""
from benchchip.peaks import least_time
from benchchip.xplane import module_seconds

DECODE_MODULES = (r"local_decode", r"^jit_gather$", r"scatter_decode")


def read(run):
    if run.trace is None or not run.decode_ctxs:
        return None
    device = module_seconds(run.trace, DECODE_MODULES)
    if not device:
        return None
    least = sum(least_time(*run.module.decode_work(run.spec, ctxs),
                           run.peaks)
                for ctxs in run.decode_ctxs if ctxs)
    return 100.0 * least / device
