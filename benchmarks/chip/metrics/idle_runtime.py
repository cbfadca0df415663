"""Share of the traced window in which the device was idle while a runtime
span was the innermost open program span (``runtime.round``,
``runtime.epoch_start``, ``runtime.epoch_end``: worker threads started and
joined, mailbox hand-offs while no fire is open), in percent
(``benchchip/spans.py``)."""
from benchchip.spans import idle_share


def read(run):
    return idle_share(run, "runtime")
