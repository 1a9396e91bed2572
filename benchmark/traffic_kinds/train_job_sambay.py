"""Traffic kind ``train_job_sambay``: ``train_job_arch`` for architecture
``sambay``. ``train_job_arch.MODEL_SECTIONS`` is a closed table in a file this
one may not edit, so the architecture's entry (the trainer's ``model`` section
from a configuration file) is added to it here, on import, and ``run`` is
handed through, as ``train_job_afmoe.py`` does. Added to the run: the
configuration's ``sliding_window`` among its sources (the band a window
layer's kernels are counted with), and one line that says what the step
traced (the first ``step_window`` event's ``ssm_plan``, ``attn_plan``,
``flash_plan``, ``fused_ce_plan``), since the benchmark builds its trainer
quiet.
"""

from __future__ import annotations

import os
from typing import Any, Dict

from benchmark.traffic_kinds import train_job_arch as arch


def _sambay_model(c, job):
    return {
        "architecture": "sambay",
        "dimensions": {"hidden_size": c["hidden_size"], "intermediate_size": c["intermediate_size"],
                       "num_layers": c["num_hidden_layers"], "layer_kinds": list(c["layer_kinds"])},
        "attention": {"num_heads": c["num_attention_heads"], "num_kv_heads": c["num_key_value_heads"],
                      "head_dim": c["head_dim"],
                      "max_position_embeddings": c["max_position_embeddings"],
                      "sliding_window": c["sliding_window"],
                      "use_flash_attention": job["attention_type"] == "flash"},
        "normalization": {"layer_norm_eps": c["layer_norm_eps"]},
        "ssm": dict(c["ssm"]),
        "misc": {"tie_word_embeddings": bool(c["tie_word_embeddings"])},
    }


arch.MODEL_SECTIONS["sambay"] = _sambay_model


def run(ctx) -> Dict[str, Any]:
    res = arch.run(ctx)
    res["sources"]["sliding_window"] = int(ctx.config["sliding_window"])
    runs = os.path.join(ctx.workdir, "runs")
    for run_dir in sorted(os.listdir(runs)):
        first = next((e for e in arch.base._read_events(os.path.join(runs, run_dir))
                      if e.get("type") == "step_window"), {})
        ctx.say("traced: " + "; ".join(f"{k} {first.get(k)}" for k in
                                       ("ssm_plan", "attn_plan", "flash_plan", "fused_ce_plan")))
    return res
