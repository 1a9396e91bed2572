"""Device time a step under ``mtp``, the outermost scope of the whole
multi-token-prediction module (its embedding, joining projection, decoder
layer and head). Overlaps the other ``step_device_ms`` rows by design, as
``recompute`` does: an attention kernel of the module counts in both."""

from _named_scopes import step_ms_under


def read(sources):
    return step_ms_under(sources, "mtp")
