"""Host time a step in the trainer loop's own phases, on the profiler's
clock: every ``train.*`` annotation of the trace's host plane that starts
inside the whole steps, except ``train.dispatch`` (the call into the step,
which in this benchmark also waits for the device) and ``train.loss_sync``
(the read of the loss, a wait for the device in a job)."""

from benchmark.trace_scopes import of_run

WAITS_FOR_DEVICE = ("train.dispatch", "train.loss_sync")


def read(sources):
    red = of_run(sources)
    if red is None:
        return None
    phases = {k: v for k, v in red["host_s"].items()
              if k.startswith("train.") and k not in WAITS_FOR_DEVICE}
    if not phases:
        return None
    return 1e3 * sum(phases.values()) / red["steps"]
