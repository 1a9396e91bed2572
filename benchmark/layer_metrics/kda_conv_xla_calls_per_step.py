"""Prologues of a KDA mixer's q, k and v the step traced in their XLA form
(``ops/short_conv.py``: the convolution, SiLU and head norm as separate passes
over float32 ``[B, S, H d]``, where the kernel pair was not taken): the
``conv_xla`` count of the program's ``kda_plan`` tally, which the trainer writes
on the run's first ``step_window`` event and ``traffic_kinds/train_job_kda.py``
hands on whole. 0 on the chip; more is a step whose prologue left the kernels,
by a mesh, a backend override, or channels or rows that fill no registers. None
where the run carries no such count (a program without the kernel pair, whose
tally names the cores alone)."""


def read(sources):
    plan = sources.get("kda_plan")
    if not plan or "conv_xla" not in plan:
        return None
    return float(int(plan["conv_xla"]))
