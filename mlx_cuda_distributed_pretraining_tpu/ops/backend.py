"""Which implementation of an op a process takes by default: one rule for the
ops that have a TPU kernel and a form for everywhere else."""

from __future__ import annotations

import os

import jax


def backend_from_env(env_name: str, on_tpu: str, elsewhere: str) -> str:
    """``on_tpu`` on a TPU backend, ``elsewhere`` otherwise; the environment
    variable ``env_name`` overrides (the tests force the kernel's name to run
    it under interpret mode)."""
    env = os.environ.get(env_name, "").strip()
    if env:
        return env
    return on_tpu if jax.default_backend() == "tpu" else elsewhere
