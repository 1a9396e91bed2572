"""Config schema tests: reference YAMLs must load unchanged."""

import textwrap

from mlx_cuda_distributed_pretraining_tpu.config import Config, apply_overrides

SAMPLE_YAML = textwrap.dedent(
    """
    name: "Llama (2M)"
    overwrite: true
    data:
      input_file: "train.jsonl"
      validation_file: "val.jsonl"
      tokenizer_path: null
      preprocessing:
        max_context_size: 1024
        chunk_overlap: 0
      tokenizer:
        normal_vocab_size: 256
        special_tokens:
          pad: "<pad>"
          bos: "<bos>"
          eos: "<eos>"
    model:
      architecture: "llama"
      dimensions:
        hidden_size: 128
        intermediate_size: 256
        num_layers: 4
      attention:
        num_heads: 8
        num_kv_heads: null
        head_dim: null
        max_position_embeddings: null
      normalization:
        rms_norm_eps: 1.0e-5
      rope:
        theta: 10000
        traditional: false
        scaling: null
      misc:
        attention_bias: false
        mlp_bias: false
        tie_word_embeddings: true
    training:
      epochs: 1
      hyperparameters:
        batch_size: 16
        learning_rate: 2.0e-2
        weight_decay: 0.01
      scheduler:
        type: "cosine"
        min_lr_ratio: 0.01
      optimization:
        optimizer: "muon"
    logging:
      log_dir: "logs"
      checkpoint_dir: "checkpoints"
      steps:
        logging_interval: 1
        checkpoint_interval: 10000
        validation_interval: 1000
      metrics:
        log_loss: true
    system:
      seed: 42
      device: "gpu"
      distributed: false
    """
)


def test_reference_yaml_roundtrip(tmp_path):
    p = tmp_path / "cfg.yaml"
    p.write_text(SAMPLE_YAML)
    cfg = Config.from_yaml(str(p))
    assert cfg.name == "Llama (2M)"
    assert cfg.overwrite is True
    assert cfg.model.hidden_size == 128
    assert cfg.model.num_heads == 8
    assert cfg.model.num_kv_heads == 8  # null -> num_heads
    assert cfg.model.head_dim == 16
    assert cfg.training.batch_size == 16
    assert cfg.training.learning_rate == 2.0e-2
    assert cfg.training.optimizer_name == "muon"
    assert cfg.training.epochs == 1
    assert cfg.logging.validation_interval == 1000
    assert cfg.system.seed == 42
    assert cfg.data.max_context_size == 1024

    out = tmp_path / "copy.yaml"
    cfg.to_yaml(str(out))
    cfg2 = Config.from_yaml(str(out))
    assert cfg2.model.hidden_size == cfg.model.hidden_size
    assert cfg2.training.optimizer_name == cfg.training.optimizer_name


def test_missing_name_raises(tmp_path):
    p = tmp_path / "bad.yaml"
    p.write_text("data:\n  input_file: x.jsonl\n")
    try:
        Config.from_yaml(str(p))
        assert False, "expected ValueError"
    except ValueError:
        pass


def test_unknown_keys_tolerated():
    cfg = Config.from_dict(
        {"name": "t", "system": {"seed": 1, "device": "tpu", "future_flag": 7}}
    )
    assert cfg.system.seed == 1
    assert getattr(cfg.system, "_extras")["future_flag"] == 7


def test_dotted_overrides():
    d = {"name": "t", "training": {"hyperparameters": {"batch_size": 16}}}
    d2 = apply_overrides(d, {"training.hyperparameters.batch_size": 4, "system.seed": 9})
    cfg = Config.from_dict(d2)
    assert cfg.training.batch_size == 4
    assert cfg.system.seed == 9
    # original untouched
    assert d["training"]["hyperparameters"]["batch_size"] == 16


def test_mesh_config():
    cfg = Config.from_dict({"name": "t", "system": {"seed": 0, "device": "tpu", "mesh": {"dp": -1, "tp": 2}}})
    assert cfg.system.mesh == {"dp": -1, "tp": 2}
    assert cfg.system.compute_dtype == "float32"
    cfg2 = Config.from_dict(
        {"name": "t", "system": {"seed": 0, "device": "tpu", "mixed_precision": True, "precision": "float16"}}
    )
    assert cfg2.system.compute_dtype == "bfloat16"  # fp16 mapped to bf16 on TPU


def test_system_compute_dtype_explicit_key():
    """system.compute_dtype in YAML is honored; without it the dataclass
    derives it from mixed_precision."""
    from mlx_cuda_distributed_pretraining_tpu.config import Config

    cfg = Config.from_dict({
        "name": "t", "system": {"compute_dtype": "bfloat16"},
    })
    assert cfg.system.compute_dtype == "bfloat16"
    cfg2 = Config.from_dict({"name": "t", "system": {}})
    assert cfg2.system.compute_dtype == "float32"
    cfg3 = Config.from_dict({"name": "t", "system": {"mixed_precision": True}})
    assert cfg3.system.compute_dtype == "bfloat16"
    assert cfg3.system.fused_ce_chunk == -1


def test_pipeline_config_validation():
    """Invalid pp/interleave/microbatch combinations fail at config load with
    errors naming the keys, not as reshape tracer errors inside the step."""
    import pytest

    def mk(**sys_extra):
        d = {
            "name": "t",
            "training": {"hyperparameters": {"batch_size": 32}},
            "model": {"dimensions": {"num_layers": 16}},
            "system": {"seed": 0, "device": "cpu", "mesh": {"pp": 4, "dp": 2},
                       "pipeline_microbatches": 8, **sys_extra},
        }
        return Config.from_dict(d)

    cfg = mk(pipeline_interleave=2, pipeline_compute_skip=False)
    assert cfg.system.pipeline_interleave == 2
    assert cfg.system.pipeline_compute_skip is False
    # defaults: interleave 1, compute-skip on
    assert mk().system.pipeline_interleave == 1
    assert mk().system.pipeline_compute_skip is True

    with pytest.raises(ValueError, match="batch_size=30 must be divisible"):
        d = mk().to_dict()
        d["training"]["hyperparameters"]["batch_size"] = 30
        Config.from_dict(d)
    with pytest.raises(ValueError, match=r"num_layers=14 must be divisible"):
        d = mk(pipeline_interleave=2).to_dict()
        d["model"]["dimensions"]["num_layers"] = 14
        Config.from_dict(d)
    with pytest.raises(ValueError, match="pipeline_microbatches >= mesh.pp"):
        d = mk(pipeline_interleave=2).to_dict()
        d["system"]["pipeline_microbatches"] = 2
        d["training"]["hyperparameters"]["batch_size"] = 4
        Config.from_dict(d)
    with pytest.raises(ValueError, match="pipeline_interleave must be >= 1"):
        mk(pipeline_interleave=0)
    # pp=1 (or no mesh): the divisibility rules don't apply
    d = mk().to_dict()
    d["system"]["mesh"] = {"dp": 2}
    d["training"]["hyperparameters"]["batch_size"] = 30
    assert Config.from_dict(d).system.mesh == {"dp": 2}
