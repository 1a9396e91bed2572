"""Traffic kind ``train_job_sdar``: ``train_job_arch`` for architecture
``sdar_moe``, whose job is block-diffusion training. ``train_job_arch``'s table
of architectures, its recorder and its shard writer are in files this one may
not edit, so what differs is set here and the run handed through:

- the architecture's entry in ``train_job_arch.MODEL_SECTIONS`` (the trainer's
  ``model`` section from a configuration file, its ``diffusion`` section from
  the file's ``block_length``, ``noise_eps`` and ``mask_token_id``), on import;
- the token ids: drawn below the MASK id (``write_shards``), which no clean
  token may equal;
- the batches the reference follows: the program's loader draws the noise
  (``data/block_diffusion.py``, from the job's seed and the batch's index), and
  the recorder keeps each checked batch's own ``noised_inputs`` and
  ``loss_weights`` in the two arrays the reference is handed
  (``reference_batch``: ``inputs [B, 2, L]`` = noised copy, clean copy;
  ``targets [B, L]`` = the weights).

- the comparison: ``train_job.compare``'s numbers and, beside them, the two
  first-gradient gaps again over the leaves no router feeds (``compare``): in
  this model a quarter of a step's rows are the one MASK embedding, they choose
  their experts nearly alike, and where their eighth and ninth choice lie a
  rounding apart a whole cluster of rows goes to another expert in bfloat16
  than in float32. A routed layer's banks and router then read 0.05-0.11 and
  0.12-0.29 on a sound seed in ten, which is where the float8 control reads, so
  over every leaf the two gaps hold a wrong gradient off and tell no precision;
  over the attention projections, the norms beside them and the two tables
  they do (the cell's record has the readings).

Added to the run: the configuration's ``block_length`` among its sources, which
the readers of the new kernels' share of peak count the mask's pairs with
(``layer_metrics/_blockdiff.py``), and one line that says what the step traced.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict

import numpy as np

from benchmark import synthetic
from benchmark.traffic_kinds import train_job_arch as arch


def _sdar_model(c, job):
    return {
        "architecture": "sdar_moe",
        "dimensions": {"hidden_size": c["hidden_size"], "num_layers": c["num_hidden_layers"]},
        "attention": {"num_heads": c["num_attention_heads"], "num_kv_heads": c["num_key_value_heads"],
                      "head_dim": c["head_dim"],
                      "max_position_embeddings": c["max_position_embeddings"],
                      "use_flash_attention": job["attention_type"] == "flash"},
        "normalization": {"rms_norm_eps": c["rms_norm_eps"]},
        "rope": {"theta": c["rope_theta"]},
        "moe": {**{k: c[k] for k in ("num_experts", "num_experts_per_tok", "moe_intermediate_size")},
                "experts_held": [c["experts_held"]["first"], c["experts_held"]["count"]],
                "held_chunk_rows": c["held_chunk_rows"]},
        "diffusion": {"block_length": c["block_length"], "eps": c["noise_eps"],
                      "mask_id": c["mask_token_id"]},
        "misc": {"tie_word_embeddings": bool(c["tie_word_embeddings"])},
    }


arch.MODEL_SECTIONS["sdar_moe"] = _sdar_model


def write_shards(write, mask_id: int):
    """``synthetic.write_token_shards`` with the ids drawn below ``mask_id``
    for a model whose vocabulary holds it: the index names the whole
    vocabulary, which is what the trainer sizes the model by."""
    def below_mask(job, vocab_size, seed, out_dir, steps):
        info = write(job, mask_id, seed, out_dir, steps)
        path = os.path.join(out_dir, "index.json")
        with open(path) as f:
            index = json.load(f)
        index["vocab_size"] = int(vocab_size)
        with open(path, "w") as f:
            json.dump(index, f)
        return info

    return below_mask


def reference_batch(batch: Dict[str, Any]) -> Dict[str, np.ndarray]:
    """A block-diffusion batch as ``reference/sdar_moe.py`` takes it."""
    if not np.all(np.asarray(batch["mask"]) == 1):
        raise RuntimeError("a packed row with padding: the reference divides by B L")
    return {"inputs": np.stack([np.asarray(batch["noised_inputs"]), np.asarray(batch["inputs"])],
                               axis=1).astype(np.int32),
            "targets": np.asarray(batch["loss_weights"], np.float32)}


def unrouted(name: str) -> bool:
    """A leaf that no router's choice feeds directly: not a routed layer's
    banks or router, nor the norm they read."""
    return "feed_forward" not in name and "ffn_norm" not in name


def compare(got: Dict[str, Any], want: Dict[str, Any], limits: Dict[str, float], say,
            base_compare=arch.base.compare) -> Dict[str, Any]:
    """``train_job.compare`` and, each beside its limit too, ``unrouted_grad_norm_gap``
    and ``unrouted_grad_profile_gap``: the same two worst-leaf gaps over the
    :func:`unrouted` leaves alone, against their own median leaf."""
    verdict = base_compare(got, want, limits, say)
    keep = [i for i, name in enumerate(want["names"]) if unrouted(name)]
    pick = lambda values: [values[i] for i in keep]
    names = pick(want["names"])
    for key, worst, field in (("unrouted_grad_norm_gap", arch.base.worst_leaf_gap, "grad_norms"),
                              ("unrouted_grad_profile_gap", arch.base.worst_profile_gap, "grad_profiles")):
        value, leaf = worst(pick(got[field]), pick(want[field]), names)
        inside = bool(np.isfinite(value)) and value <= float(limits[key])
        verdict["numbers"][key] = value
        verdict["ok"] = verdict["ok"] and inside
        say(f"check {key}: {value:.6g} (limit {float(limits[key]):g}) {'ok' if inside else 'OUTSIDE'}"
            f" at {leaf}")
    return verdict


class NoiseRecorder(arch.base.StepRecorder):
    """``train_job``'s recorder, whose checked batches keep their own noise."""

    def __call__(self, state, batch):
        out = super().__call__(state, batch)
        if self.n <= self.checked:
            self.batches[self.n - 1] = reference_batch(batch)
        return out


def run(ctx) -> Dict[str, Any]:
    write, recorder, base_compare = (synthetic.write_token_shards, arch.base.StepRecorder,
                                     arch.base.compare)
    synthetic.write_token_shards = write_shards(write, int(ctx.config["mask_token_id"]))
    arch.base.StepRecorder, arch.base.compare = NoiseRecorder, compare
    try:
        res = arch.run(ctx)
    finally:
        synthetic.write_token_shards, arch.base.StepRecorder, arch.base.compare = (
            write, recorder, base_compare)
    res["sources"]["block_length"] = int(ctx.config["block_length"])
    runs = os.path.join(ctx.workdir, "runs")
    for run_dir in sorted(os.listdir(runs)):
        first = next((e for e in arch.base._read_events(os.path.join(runs, run_dir))
                      if e.get("type") == "step_window"), {})
        ctx.say("traced: " + "; ".join(f"{k} {first.get(k)}" for k in
                                       ("attn_plan", "flash_plan", "moe_plan", "bd_tiles_live",
                                        "bd_tiles_grid", "bd_loss_rows")))
    return res
