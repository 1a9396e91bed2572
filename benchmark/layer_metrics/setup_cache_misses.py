"""Programs that missed the persistent compile cache during set-up, so were
compiled and written to it: ``stages.cache_misses`` of the run's ``compile``
event plus the ``miss`` outcomes among ``xla_compiled`` of the ``step_window``
events before the first timed step (``_setup.py``). 0 on a warm run; a
checkout's first run reads at least 1, and the event's ``misses`` names them.
None where the run's ``compile`` event has no ``phases``."""

import _setup


def read(sources):
    found = _setup.account(sources)
    return None if found is None else found[1]
