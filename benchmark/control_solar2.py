#!/usr/bin/env python3
"""``control_kda.py`` for cells of the traffic kind ``train_job_solar2``: that
file's ``main`` refuses any kind but ``train_job_kda`` by name and may not be
edited, so this one registers the architecture (importing the kind) and hands
``main`` the cell's traffic under that name, and the kind's ``compare`` (the
harness's four numbers and the two over the leaves no router feeds) in the place of
``train_job.compare``; every option and last line is that file's:

    python3 benchmark/control_solar2.py --workload <cell> --seeds 11,12,13 \\
        [--precisions fp8,float32_bf16_kda] [--sound 11,12,13,21,22] [--dump DIR]

``--precisions``: the reference in each of these in the program's place (the
configuration's ``precision.control``, ``fp8``, if none is named;
``float32_bf16_kda`` is the diagnosis: the float32 reference with the delta
rule's decay and state alone in bfloat16). ``--sound``: the program's checked
steps on these seeds against the same float32 reference, one trainer and one
compiled step for all of them. Exits 1 if a control came out correct or a sound
run did not.
"""

from __future__ import annotations

import functools
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import control_kda  # noqa: E402
from benchmark.traffic_kinds import train_job_solar2  # noqa: E402  (registers "solar_open2")

KIND = "train_job_solar2"


def main(argv=None, say=functools.partial(print, flush=True)) -> int:
    load = control_kda.harness.load_cell

    def under_kdas_name(workload, rehearse=False):
        bench, cell, config, mix = load(workload, rehearse)
        if mix["kind"] != KIND:
            raise SystemExit(f"{workload} is of kind {mix['kind']!r}: use control.py, control_arch.py or control_kda.py")
        return bench, cell, config, dict(mix, kind="train_job_kda")

    base_compare = control_kda.train_job.compare
    control_kda.harness.load_cell, control_kda.train_job.compare = under_kdas_name, train_job_solar2.compare
    try:
        return control_kda.main(argv, say)
    finally:
        control_kda.harness.load_cell, control_kda.train_job.compare = load, base_compare


if __name__ == "__main__":
    sys.exit(main())
