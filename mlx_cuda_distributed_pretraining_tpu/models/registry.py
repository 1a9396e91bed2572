"""Architecture registry.

The reference resolves ``model.architecture`` by importlib against
``models.<arch>`` with an mlx_lm fallback (reference:
core/training.py:1018-1091). Here it's an explicit registry: every
architecture provides ``(args_cls, init_params, forward, loss_fn)``.
"llama_standard" maps to llama with simple attention forced (reference keeps
a separate near-identical file models/llama_standard.py; one model +
config-selected attention is the same capability without the duplication).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple


class Architecture(NamedTuple):
    name: str
    args_cls: Any
    init_params: Callable
    forward: Callable
    loss_fn: Callable
    force_attention: str | None = None
    # (args, seq_len) -> training FLOPs a token, where the llama count
    # (obs/flops.py) does not describe the model
    flops_per_token: Callable | None = None
    # tallies of what the model chose while tracing, beside the trainer's own
    # (train/trainer.py _PLAN_TALLIES): {event key: (log label, counts())}
    plans: Dict[str, Any] | None = None


_REGISTRY: Dict[str, Architecture] = {}
# Architectures in modules of their own, imported (and so registered) when a
# config first names them: a llama run pays nothing for them.
_LAZY_MODULES = {"xing_mla_moe": "xing", "afmoe": "afmoe", "sambay": "sambay", "sdar_moe": "sdar",
                 "kimi_linear": "kimi_linear", "solar_open2": "solar_open2"}


def register(arch: Architecture) -> None:
    _REGISTRY[arch.name] = arch


def resolve_architecture(name: str) -> Architecture:
    key = name.lower()
    if key not in _REGISTRY and key in _LAZY_MODULES:
        import importlib

        importlib.import_module("." + _LAZY_MODULES[key], __package__)
    if key not in _REGISTRY:
        raise ValueError(f"unknown architecture {name!r}; available: "
                         f"{sorted(set(_REGISTRY) | set(_LAZY_MODULES))}")
    return _REGISTRY[key]


def _register_builtin() -> None:
    from . import llama

    base = Architecture("llama", llama.LlamaArgs, llama.init_params, llama.forward, llama.loss_fn)
    register(base)
    register(base._replace(name="llama_standard", force_attention="simple"))
    register(base._replace(name="llama_flash", force_attention="flash"))


_register_builtin()
