"""Delta-rule cores the step traced in their XLA form (``ops/kda.py``: the chunk
function under ``vmap`` and a scan, where the kernels were not taken): the
``xla`` count of the program's ``kda_plan`` tally, which the trainer writes on
the run's first ``step_window`` event beside ``moe_plan`` and ``flash_plan`` and
``traffic_kinds/train_job_kda.py`` hands on. 0 on the chip; more is a step that
left the kernels, by a mesh, a backend override or a row no chunk of whole
sub-blocks divides. None where the run carries no such tally (a program
without it)."""


def read(sources):
    plan = sources.get("kda_plan")
    if not plan:
        return None
    return float(int(plan.get("xla", 0)))
