"""Share of the traced window in which the device was idle while a stage
fire was the innermost open program span (``actor.fire``, ``stage.*``:
host dispatch of the gather, stage program and scatter, and the sync's
return inside a fire), in percent (``benchchip/spans.py``)."""
from benchchip.spans import idle_share


def read(run):
    return idle_share(run, "stage")
