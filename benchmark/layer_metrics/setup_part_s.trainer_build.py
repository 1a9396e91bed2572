"""From the start of the trainer's first ``init.*`` phase to the end of its last:
the constructor (``init.system``, ``init.tokenizer``, ``init.model`` with
``init.params`` inside it, ``init.data``, ``init.optimizer``, ``init.telemetry``,
``init.restore`` where a checkpoint is loaded). One of the seven pieces of ``setup_s``
(``_setup.py``: they sum to it by construction). None where the run's ``compile``
event has no ``phases``."""

import _setup


def read(sources):
    return _setup.part(sources, "trainer_build")
