"""Set-up seconds: process start to the first timed call (weights from the
seed, api.compile with its lowering and static check, and one warm-up call
over every prompt length the mix draws; compiles or compile-cache loads)."""


def read(run):
    return run.setup_s
