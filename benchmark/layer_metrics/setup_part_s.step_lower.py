"""The lowering of the step's jaxpr to an MLIR module, Mosaic kernels' bodies
included: ``step_stages.lower_s``. Cold or warm alike. One of the seven pieces of ``setup_s``
(``_setup.py``: they sum to it by construction). None where the run's ``compile``
event has no ``phases``."""

import _setup


def read(sources):
    return _setup.part(sources, "step_lower")
