"""Share of the traced window in which the device was idle while the serve
loop's span was the innermost open program span (``serve.generate``,
``serve.plan``, ``serve.absorb``: planning, greedy picks and the token
sync between rounds), in percent (``benchchip/spans.py``)."""
from benchchip.spans import idle_share


def read(run):
    return idle_share(run, "serve_loop")
