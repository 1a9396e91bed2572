"""From the process's start to the start of the trainer's first ``init.*`` phase:
the interpreter, the imports, the runtime's start and, in this benchmark, the
shards it writes. One of the seven pieces of ``setup_s``
(``_setup.py``: they sum to it by construction). None where the run's ``compile``
event has no ``phases``."""

import _setup


def read(sources):
    return _setup.part(sources, "before_trainer")
