"""From the end of the first ``train.dispatch`` to the window's start: the checked
and warm steps after step 1 and what the benchmark runs between them. One of the seven pieces of ``setup_s``
(``_setup.py``: they sum to it by construction). None where the run's ``compile``
event has no ``phases``."""

import _setup


def read(sources):
    return _setup.part(sources, "steps_to_window")
