"""Delta-rule cores the step traced with a doubled write strength (``beta = 2
sigmoid(.)`` in (0, 2), ``kda_allow_neg_eigval``: a transition with the eigenvalue
``1 - beta`` in (-1, 1) along its key): the ``neg_eig_cores`` count of the
program's ``kda_plan`` tally (``models/kimi_linear.py::kda_plan_counts``), which
the trainer writes on the run's first ``step_window`` event and the traffic kind
hands on whole. As many as the step traced cores (``kernel`` + ``xla``) where the
configuration has the setting; 0 is a run that lost it. None where the run
carries no such count (a program from before the mixer took the factor)."""


def read(sources):
    plan = sources.get("kda_plan")
    if not plan or "neg_eig_cores" not in plan:
        return None
    return float(int(plan["neg_eig_cores"]))
