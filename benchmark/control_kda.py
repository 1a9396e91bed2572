#!/usr/bin/env python3
"""``control_arch.py`` for cells of the traffic kind ``train_job_kda``: that
file refuses any kind but ``train_job_arch`` by name, so this one registers the
architecture (importing the kind) and runs the same comparison and the same
last lines. A run of this cell's reference is minutes, so one seed's float32
reference serves every precision asked for, and with ``--sound`` the program's
own first steps too:

    python3 benchmark/control_kda.py --workload <cell> --seeds 11,12,13 \\
        [--precisions fp8,float32_bf16_kda] [--sound 11,12,13,21,22]

Every seed's batches are those the trainer's loader hands a run of that seed.
``--precisions``: the reference in each of these in the program's place (the
configuration's ``precision.control`` if none is named; ``float32_bf16_kda`` is
the diagnosis: the float32 reference with the delta rule's decay and state alone
in bfloat16). Each has to come out NOT correct. ``--sound``: the program's
checked steps on these seeds (any of ``--seeds`` or others, which get no
control) against the same float32 reference, through the trainer's own jitted
step and the harness's recorder, built once for all seeds: what ``run.py``
reads as ``check_numbers`` on that seed, without the window. Each has to come
out correct. Exits 1 if a control came out correct or a sound run did not.
"""

from __future__ import annotations

import argparse
import functools
import gc
import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as harness  # noqa: E402
from benchmark.traffic_kinds import train_job, train_job_arch  # noqa: E402
from benchmark.traffic_kinds import train_job_kda  # noqa: E402,F401  (registers "kimi_linear")


def loader_batches(ctx, workdir: str):
    """The first ``checked_steps`` batches the trainer's loader hands a run of this
    seed (its shuffled windows of the seeded shard: not ``control.first_batches``'s
    rows in file order), as ``run.py`` records them."""
    from mlx_cuda_distributed_pretraining_tpu.config import Config
    from mlx_cuda_distributed_pretraining_tpu.data import build_data_manager

    shard_dir = os.path.join(workdir, f"shards_{ctx.seed}")
    train_job_arch.synthetic.write_token_shards(ctx.mix, int(ctx.config["vocab_size"]), ctx.seed, shard_dir,
                                                int(ctx.mix["shard_steps"]))
    cfg = Config.from_dict(train_job_arch.trainer_config(ctx, shard_dir))
    data = build_data_manager(cfg, None, batch_size=cfg.training.batch_size,   # token shards: no tokenizer
                              seq_len=cfg.data.max_context_size, seed=cfg.system.seed,
                              process_index=0, process_count=1)
    return [data.generate_batch(i) for i in range(int(ctx.mix["checked_steps"]))]


class Program:
    """The program's side of the comparison: one trainer, so one traced and
    compiled step for every seed, and the harness's own recorder around it. The
    state is the trainer's (``init_train_state``) over the reference's seeded
    weights."""

    def __init__(self, ctx, workdir: str):
        from mlx_cuda_distributed_pretraining_tpu.config import Config
        from mlx_cuda_distributed_pretraining_tpu.train.trainer import Trainer

        self.ref, _ = train_job_arch.modules_for(ctx.config)
        train_job.ref = self.ref   # the recorder's change_norms reads it
        shard_dir = os.path.join(workdir, "shards")
        train_job_arch.synthetic.write_token_shards(ctx.mix, int(ctx.config["vocab_size"]), ctx.seed, shard_dir,
                                                    int(ctx.mix["checked_steps"]) + 1)
        self.tr = Trainer(Config.from_dict(train_job_arch.trainer_config(ctx, shard_dir)),
                          runs_root=os.path.join(workdir, "runs"), quiet=True)
        self.tr.state = None
        gc.collect()

    def steps(self, ctx, batches):
        import jax

        from mlx_cuda_distributed_pretraining_tpu.train.train_step import init_train_state

        hp = dict(ctx.mix["optimizer"])
        rec = train_job.StepRecorder(ctx, self.tr, hp["name"], hp)
        state = init_train_state(self.ref.init_params(ctx.seed, ctx.config), self.tr.optimizer)
        for b in batches:
            state, _ = rec(state, jax.device_put(b))
        return {"losses": [s["loss"] for s in rec.steps], "grad_norms": rec.grad_norms,
                "grad_profiles": rec.grad_profiles, "changes": rec.changes}

    def close(self):
        if self.tr.events is not None:
            self.tr.events.close()
        self.tr.logger.close()
        self.tr = None
        gc.collect()


def dump_leaves(path: str, got, want) -> None:
    """Every leaf's gap in the three per-leaf numbers, worst first (the last lines
    name the worst alone): what a limit over some of the leaves would have read."""
    os.makedirs(os.path.dirname(path), exist_ok=True)
    every = len(want["names"])
    with open(path, "w") as f:
        for title, field, profile in (("first gradient's norm", "grad_norms", False),
                                      ("first gradient's profile", "grad_profiles", True),
                                      ("weights' change", "changes", False)):
            f.write(f"{title}: " + train_job_arch.worst_leaves(got[field], want[field], want["names"],
                                                               profile, top=every) + "\n")


def main(argv=None, say=functools.partial(print, flush=True)) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True, help="comma-separated; each gets every control")
    p.add_argument("--precisions", default=None,
                   help="comma-separated precisions of the reference; the configuration's control if none")
    p.add_argument("--sound", default="", help="comma-separated seeds for the program's own first steps")
    p.add_argument("--dump", default=None, help="a directory for every comparison's per-leaf gaps")
    p.add_argument("--rehearse", default=None, help="a rehearse file's name: its tiny sizes (tests)")
    args = p.parse_args(argv)
    _, cell, config, mix = harness.load_cell(args.workload)
    if mix["kind"] != "train_job_kda":
        raise SystemExit(f"{args.workload} is of kind {mix['kind']!r}: use control.py or control_arch.py")
    rehearse = args.rehearse is not None
    if rehearse:
        with open(os.path.join(HERE, args.rehearse)) as f:
            tiny = json.load(f)
        config, mix = harness.merge_into(config, tiny["config"]), harness.merge_into(mix, tiny["traffic"])
    else:
        harness.check_devices(cell)
        harness.enable_compile_cache()
    ints = lambda s: [int(x) for x in s.split(",") if x]
    seeds, sound_seeds = ints(args.seeds), ints(args.sound)
    precisions = args.precisions.split(",") if args.precisions else [config["precision"]["control"]]
    failed = False
    workdir = tempfile.mkdtemp(prefix="bench_control_")
    try:
        context = lambda seed: harness.Context(cell, config, mix, seed, 0.0, False, rehearse, workdir, quiet=True)
        every = seeds + [seed for seed in sound_seeds if seed not in seeds]
        batches = {seed: loader_batches(context(seed), workdir) for seed in every}
        sound = {}
        if sound_seeds:
            program = Program(context(every[0]), workdir)
            sound = {seed: program.steps(context(seed), batches[seed]) for seed in sound_seeds}
            program.close()
        for seed in every:
            ctx = context(seed)
            t = time.perf_counter()
            want = train_job_arch.reference_steps(ctx, batches[seed], "float32")
            say(f"seed {seed}: reference in float32, {time.perf_counter() - t:.1f} s")
            if seed in sound:
                train_job_arch.say_worst_leaves(sound[seed], want, say)
                verdict = train_job.compare(sound[seed], want, cell["limits"], say)
                if args.dump:
                    dump_leaves(os.path.join(args.dump, f"program_{seed}.txt"), sound[seed], want)
                failed = failed or not verdict["ok"]
                say(json.dumps({"sound": "program", "workload": args.workload, "seed": seed,
                                "correct": bool(verdict["ok"]), "numbers": verdict["numbers"]}))
            for precision in precisions if seed in seeds else ():
                t = time.perf_counter()
                got = train_job_arch.reference_steps(ctx, batches[seed], precision)
                say(f"seed {seed}: reference in {precision}, {time.perf_counter() - t:.1f} s")
                train_job_arch.say_worst_leaves(got, want, say)
                verdict = train_job.compare(got, want, cell["limits"], say)
                if args.dump:
                    dump_leaves(os.path.join(args.dump, f"{precision}_{seed}.txt"), got, want)
                failed = failed or verdict["ok"]
                say(json.dumps({"control": precision, "workload": args.workload, "seed": seed,
                                "control_came_out_correct": bool(verdict["ok"]),
                                "numbers": verdict["numbers"]}))
            del want
            gc.collect()
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
