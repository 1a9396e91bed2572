"""The trace of the jitted train step to a jaxpr, in Python: ``step_stages.trace_s``,
the outermost ``jaxpr_trace_duration`` spans of ``step_fun``. Cold or warm alike. One of the seven pieces of ``setup_s``
(``_setup.py``: they sum to it by construction). None where the run's ``compile``
event has no ``phases``."""

import _setup


def read(sources):
    return _setup.part(sources, "step_trace")
