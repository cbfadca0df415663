"""Mean hand-off latency between pipeline stages, from the ``actor.fire``
spans alone: over epochs, fires ``k`` and stage pairs ``s -> s+1``, the
start of ``stage{s+1}``'s fire ``k`` minus the later of the end of
``stage{s}``'s fire ``k`` and of ``stage{s+1}``'s fire ``k-1``, in
milliseconds (``benchchip/spans.py``)."""


def read(run):
    red = getattr(run, "spans", None)
    return red["actor_hop_ms"] if red else None
