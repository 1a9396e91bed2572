"""Device time a step of every operation with ``bd_rows`` in its name stack:
what builds and splits the doubled rows of block-diffusion training (the
concatenation of the noised and the clean copy at the embedding, the position
ids, the slice of the noised copy before the head, and their backward). Not a
scope of ``trace_scopes.VOCABULARY``."""

from _named_scopes import step_ms_under


def read(sources):
    return step_ms_under(sources, "bd_rows")
