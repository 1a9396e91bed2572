"""Traffic kind ``train_job_kda``: ``train_job_arch`` for architecture
``kimi_linear``. ``train_job_arch.MODEL_SECTIONS`` is a closed table in a file
this one may not edit, so the architecture's entry (the trainer's ``model``
section from a configuration file, its ``linear_attn`` section the published
``linear_attn_config`` as it stands) is added to it here, on import, and ``run``
is handed through, as ``train_job_afmoe.py`` does. Added to the run: the
configuration's delta-rule head count and size among its sources (the readers
of the new kernels' share of their roof count a call with them:
``layer_metrics/_kda.py``), the ``kda_plan`` tally of the first ``step_window``
event (the trainer writes what a step traced once, there, and the window's
events come after it: ``layer_metrics/kda_xla_calls_per_step.py`` reads it), and
one line that says what the step traced (that event's ``kda_plan``,
``flash_plan``, ``moe_plan``, ``fused_ce_plan``), since the benchmark builds its
trainer quiet.
"""

from __future__ import annotations

import os
from typing import Any, Dict

from benchmark.traffic_kinds import train_job_arch as arch


def _kimi_model(c, job):
    return {
        "architecture": "kimi_linear",
        "dimensions": {"hidden_size": c["hidden_size"], "intermediate_size": c["intermediate_size"],
                       "num_layers": c["num_hidden_layers"]},
        "attention": {"num_heads": c["num_attention_heads"],
                      "max_position_embeddings": c["model_max_length"],
                      "use_flash_attention": job["attention_type"] == "flash"},
        "linear_attn": dict(c["linear_attn_config"]),
        "mla": {k: c[k] for k in ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                                  "qk_rope_head_dim", "v_head_dim")},
        "normalization": {"rms_norm_eps": c["rms_norm_eps"]},
        "moe": {**{k: c[k] for k in ("num_experts", "num_experts_per_token", "moe_intermediate_size",
                                     "num_shared_experts", "first_k_dense_replace",
                                     "routed_scaling_factor")},
                "experts_held": [c["experts_held"]["first"], c["experts_held"]["count"]],
                "held_chunk_rows": c["held_chunk_rows"]},
        "misc": {"tie_word_embeddings": bool(c["tie_word_embeddings"])},
    }


arch.MODEL_SECTIONS["kimi_linear"] = _kimi_model


def run(ctx) -> Dict[str, Any]:
    if ctx.mix.get("scan_layers"):
        ctx.say("job: scan_layers is asked (the traffic is pack16k-afmoe's, key for key) and scans nothing "
                "here: this architecture's layers are trees of two kinds in a Python loop (models/kimi_linear.py)")
    res = arch.run(ctx)
    lin = ctx.config["linear_attn_config"]
    res["sources"]["kda_heads"], res["sources"]["kda_head_dim"] = int(lin["num_heads"]), int(lin["head_dim"])
    runs = os.path.join(ctx.workdir, "runs")
    for run_dir in sorted(os.listdir(runs)):
        first = next((e for e in arch.base._read_events(os.path.join(runs, run_dir))
                      if e.get("type") == "step_window"), {})
        res["sources"]["kda_plan"] = first.get("kda_plan")
        ctx.say("traced: " + "; ".join(f"{k} {first.get(k)}" for k in
                                       ("kda_plan", "flash_plan", "moe_plan", "fused_ce_plan")))
    return res
