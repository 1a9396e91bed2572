"""The whole, the process's start to the window's start, less the six named
pieces: the hand-over between the build and ``train()`` (this benchmark places its
seeded weights there), ``train.start``, the first batch, and step 1's first dispatch
less its trace, lowering and backend compiles (its execution and the benchmark's
reads of its state). One of the seven pieces of ``setup_s``
(``_setup.py``: they sum to it by construction). None where the run's ``compile``
event has no ``phases``."""

import _setup


def read(sources):
    return _setup.part(sources, "other")
