"""Shipped configs load; graft entry points run on the CPU mesh."""

import pytest

import glob
import os

from mlx_cuda_distributed_pretraining_tpu.config import Config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_all_shipped_configs_load():
    """Every shipped preset (incl. configs/models/ and configs/optimizers/)
    loads, resolves model args, and builds its optimizer."""
    from mlx_cuda_distributed_pretraining_tpu.models.llama import LlamaArgs
    from mlx_cuda_distributed_pretraining_tpu.optim import build_optimizer

    paths = glob.glob(os.path.join(REPO, "configs", "**", "*.yaml"), recursive=True)
    assert len(paths) >= 25
    for p in paths:
        if os.path.basename(p).startswith("serve-"):
            # serving preset: EngineConfig schema, not a training Config
            from mlx_cuda_distributed_pretraining_tpu.serve import EngineConfig

            scfg = EngineConfig.from_yaml(p)
            assert scfg.num_slots > 0 and scfg.max_len > 1
            continue
        if os.path.basename(p) == "alerts.yaml":
            # graftscope alert rules: their own schema, own validator
            from mlx_cuda_distributed_pretraining_tpu.obs.alerts import load_rules

            assert len(load_rules(p)) > 0
            continue
        cfg = Config.from_yaml(p)
        assert cfg.name
        if "tokenizer-config" in p:
            continue  # tokenizer-training preset: no model/training sections
        assert cfg.model.hidden_size > 0
        assert cfg.training.batch_size > 0
        args = LlamaArgs.from_config(cfg.model, vocab_size=259)
        assert args.hidden_size == cfg.model.hidden_size
        assert build_optimizer(cfg.training, 100) is not None


@pytest.mark.slow
def test_dryrun_multichip_8():
    import __graft_entry__

    __graft_entry__.dryrun_multichip(8)


def test_entry_compiles():
    import jax

    import __graft_entry__

    fn, args = __graft_entry__.entry()
    out = jax.jit(fn).lower(*args).compile()
    assert out is not None
