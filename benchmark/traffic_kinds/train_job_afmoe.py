"""Traffic kind ``train_job_afmoe``: ``train_job_arch`` for architecture
``afmoe``. ``train_job_arch.MODEL_SECTIONS`` is a closed table in a file this
one may not edit, so the architecture's entry (the trainer's ``model`` section
from a configuration file) is added to it here, on import, and ``run`` is
handed through. Added to the run: the configuration's ``sliding_window`` among
its sources, which the readers of the window layers' kernels count a band's
operations with (``layer_metrics/_attn_kinds.py``), and one line that says
what the step traced (the first ``step_window`` event's ``attn_plan``,
``flash_plan``, ``moe_plan``), since the benchmark builds its trainer quiet.
"""

from __future__ import annotations

import os
from typing import Any, Dict

from benchmark.traffic_kinds import train_job_arch as arch


def _afmoe_model(c, job):
    return {
        "architecture": "afmoe",
        "dimensions": {"hidden_size": c["hidden_size"], "intermediate_size": c["intermediate_size"],
                       "num_layers": c["num_hidden_layers"]},
        "attention": {"num_heads": c["num_attention_heads"], "num_kv_heads": c["num_key_value_heads"],
                      "head_dim": c["head_dim"],
                      "max_position_embeddings": c["max_position_embeddings"],
                      "layer_types": list(c["layer_types"]), "sliding_window": c["sliding_window"],
                      "use_flash_attention": job["attention_type"] == "flash"},
        "normalization": {"rms_norm_eps": c["rms_norm_eps"]},
        "rope": {"theta": c["rope_theta"]},
        "moe": {**{k: c[k] for k in ("num_experts", "num_experts_per_tok", "moe_intermediate_size",
                                     "num_shared_experts", "num_dense_layers", "route_scale")},
                "experts_held": [c["experts_held"]["first"], c["experts_held"]["count"]],
                "held_chunk_rows": c["held_chunk_rows"]},
        "misc": {"tie_word_embeddings": bool(c["tie_word_embeddings"]),
                 "mup_enabled": bool(c["mup_enabled"])},
    }


arch.MODEL_SECTIONS["afmoe"] = _afmoe_model


def run(ctx) -> Dict[str, Any]:
    res = arch.run(ctx)
    res["sources"]["sliding_window"] = int(ctx.config["sliding_window"])
    runs = os.path.join(ctx.workdir, "runs")
    for run_dir in sorted(os.listdir(runs)):
        first = next((e for e in arch.base._read_events(os.path.join(runs, run_dir))
                      if e.get("type") == "step_window"), {})
        ctx.say("traced: " + "; ".join(f"{k} {first.get(k)}" for k in
                                       ("attn_plan", "flash_plan", "moe_plan")))
    return res
