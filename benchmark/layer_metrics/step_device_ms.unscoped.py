"""Device time a step of operations whose name stack holds no scope of the
vocabulary (``benchmark/trace_scopes.py``): what the breakdown cannot
attribute. A program that opens no scope reads its whole step here."""

from benchmark.trace_scopes import UNSCOPED, step_ms


def read(sources):
    return step_ms(sources, (UNSCOPED,))
