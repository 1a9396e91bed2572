"""Traffic kind ``train_job_solar2``: ``train_job_arch`` for architecture
``solar_open2``. ``train_job_arch.MODEL_SECTIONS`` is a closed table in a file
this one may not edit, so the architecture's entry (the trainer's ``model``
section from a configuration file: the published keys under their own names,
``linear_attn`` the published ``linear_attn_config`` as it stands beside the two
``kda_*`` flags) is added to it here, on import, and ``run`` is handed through,
as ``train_job_kda.py`` does. Added to the run, as there: the configuration's
delta-rule head count and size among its sources (``layer_metrics/_kda.py``
counts a call with them), the ``kda_plan`` tally of the first ``step_window``
event (``layer_metrics/kda_neg_eig_cores_per_step.py`` and the two
``kda_*_xla_calls_per_step`` read it), and one line that says what the step
traced, since the benchmark builds its trainer quiet. And, as ``train_job_sdar.py``
has them and by its own ``compare``, two numbers beside the harness's four:
``unrouted_grad_norm_gap`` and ``unrouted_grad_profile_gap``, the first gradient's
worst-leaf gaps over the leaves no router feeds. Here too they are what tells a
float8 control from the program with room: every layer routes, the first straight
after the embedding and one attention layer, where the rows of one frequent token
id choose their experts as a cluster, and where such a cluster's eighth and ninth
choice lie a rounding apart it goes to another expert in bfloat16 than in float32
and a held bank's gradient moves by several percent (0.052 and 0.099 on one sound
seed in fifteen where the others read 0.005-0.015 and 0.053-0.065).
"""

from __future__ import annotations

import os
from typing import Any, Dict

from benchmark.traffic_kinds import train_job_arch as arch
from benchmark.traffic_kinds.train_job_sdar import compare   # train_job.compare and the two unrouted numbers


def _solar2_model(c, job):
    return {
        "architecture": "solar_open2",
        "dimensions": {"hidden_size": c["hidden_size"], "intermediate_size": c["intermediate_size"],
                       "num_layers": c["num_hidden_layers"]},
        "attention": {"num_heads": c["num_attention_heads"], "num_kv_heads": c["num_key_value_heads"],
                      "head_dim": c["head_dim"],
                      "max_position_embeddings": c["max_position_embeddings"],
                      "gqa_layers": list(c["gqa_layers"]), "use_gqa_gate": c["use_gqa_gate"],
                      "use_rope": c["use_rope"],
                      "use_flash_attention": job["attention_type"] == "flash"},
        "linear_attn": {**c["linear_attn_config"],
                        **{k: c[k] for k in ("kda_allow_neg_eigval", "kda_use_full_proj")}},
        "normalization": {"rms_norm_eps": c["rms_norm_eps"]},
        "moe": {**{k: c[k] for k in ("n_routed_experts", "num_experts_per_tok", "moe_intermediate_size",
                                     "n_shared_experts", "norm_topk_prob", "routed_scaling_factor",
                                     "first_k_dense_replace")},
                "experts_held": [c["experts_held"]["first"], c["experts_held"]["count"]],
                "held_chunk_rows": c["held_chunk_rows"]},
        "misc": {"tie_word_embeddings": bool(c["tie_word_embeddings"])},
    }


arch.MODEL_SECTIONS["solar_open2"] = _solar2_model


def run(ctx) -> Dict[str, Any]:
    if ctx.mix.get("scan_layers"):
        ctx.say("job: scan_layers is asked (the traffic is pack8k-b2-kda's, key for key) and scans nothing "
                "here: this architecture's layers are trees of two kinds in a Python loop (models/solar_open2.py)")
    base_compare, arch.base.compare = arch.base.compare, compare
    try:
        res = arch.run(ctx)
    finally:
        arch.base.compare = base_compare
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
