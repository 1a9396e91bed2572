"""Backend compiles and persistent-cache loads inside the first ``train.dispatch``
(that phase's ``backend_s``): the step's executable and whatever else was built
there, this benchmark's side programs among them. Seconds warm, minutes cold. One of the seven pieces of ``setup_s``
(``_setup.py``: they sum to it by construction). None where the run's ``compile``
event has no ``phases``."""

import _setup


def read(sources):
    return _setup.part(sources, "step_compile_or_load")
