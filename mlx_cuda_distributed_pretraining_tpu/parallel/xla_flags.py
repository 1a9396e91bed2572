"""Named XLA flag sets for comm/compute overlap, applied before backend init.

The MFU campaign's first lever is free: XLA's latency-hiding scheduler
and async-collective lowering overlap the fsdp param all-gathers and the
gradient reduce-scatter with surrounding matmuls — but only when the
right backend flags are set *before the backend initializes*, and a
silently dropped flag set is indistinguishable from a scheduling
regression. So flag sets are:

- **named** — configs request ``system.xla.flag_set: latency_hiding``
  rather than carrying raw flag strings;
- **per-backend** — the TPU and GPU spellings differ and XLA hard-errors
  on unknown ``--xla_*`` flags, so the resolver only emits flags the
  current backend understands (CPU resolves to the empty set: XLA:CPU
  has no latency-hiding scheduler and every collective is synchronous);
- **stamped** — :func:`apply_flag_set` returns a JSON-able stamp that the
  trainer writes into the ``run_start`` event, so every run is
  attributable to its flag set; and
- **audited** — analysis/audit_rules.py's dropped-flag-set rule compares
  a program's requested set against the environment it was actually
  lowered under (:func:`missing_flags`), catching the
  set-after-backend-init failure mode.
"""

from __future__ import annotations

import os
import sys
from typing import Any, Dict, List, Optional, Sequence

# flag set name -> backend -> flags. A flag set resolving to () for a
# backend is well-formed (the set exists, the backend has nothing to set).
FLAG_SETS: Dict[str, Dict[str, Sequence[str]]] = {
    "none": {},
    # Latency-hiding scheduler + async collectives + collective matmul
    # (windowed einsum): the overlap trio from the 2x MFU campaign.
    "latency_hiding": {
        "tpu": (
            "--xla_tpu_enable_latency_hiding_scheduler=true",
            "--xla_enable_async_all_gather=true",
            "--xla_enable_async_collective_permute=true",
            "--xla_tpu_enable_async_collective_fusion=true",
            "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true",
            "--xla_tpu_enable_async_collective_fusion_multiple_steps=true",
            "--xla_tpu_overlap_compute_collective_tc=true",
            # Collective matmul: window the fsdp all-gather into the
            # einsum it feeds (0 MiB threshold = always when profitable).
            "--xla_jf_spmd_threshold_for_windowed_einsum_mib=0",
            "--xla_tpu_spmd_threshold_for_allgather_cse=10000",
        ),
        "gpu": (
            "--xla_gpu_enable_latency_hiding_scheduler=true",
            "--xla_gpu_enable_highest_priority_async_stream=true",
            "--xla_gpu_all_reduce_combine_threshold_bytes=134217728",
            "--xla_gpu_all_gather_combine_threshold_bytes=134217728",
            "--xla_gpu_reduce_scatter_combine_threshold_bytes=134217728",
        ),
        # XLA:CPU: no latency-hiding scheduler, collectives are
        # synchronous thread rendezvous — nothing to set. parallel/
        # overlap.py is the CPU-visible half of the campaign.
        "cpu": (),
    },
}

DEFAULT_FLAG_SET = "latency_hiding"


def flag_set_names() -> List[str]:
    return sorted(FLAG_SETS)


def guess_backend() -> str:
    """Backend name WITHOUT initializing one.

    ``jax.default_backend()`` would force initialization — exactly what
    this module must run before — so answer from what jax itself consults
    when it picks a default: the platform env knobs, then the accelerators
    the host exposes (the PCI scan jax runs at import). A wrong guess is
    not silent: the trainer compares the stamp's ``xla_backend`` with the
    platform the backend reports once it is up and logs a warning, and
    ``chip_smoke.py`` fails on a mismatch.
    """
    plats = os.environ.get("JAX_PLATFORMS") or os.environ.get(
        "JAX_PLATFORM_NAME") or ""
    first = plats.split(",")[0].strip().lower()
    if first:
        return {"libtpu": "tpu", "cuda": "gpu", "rocm": "gpu"}.get(first, first)
    if _backend_initialized():
        return sys.modules["jax"].default_backend()
    from jax._src import hardware_utils

    if hardware_utils.num_available_tpu_chips_and_device_id()[0] > 0:
        return "tpu"
    if hardware_utils.has_visible_nvidia_gpu():
        return "gpu"
    return "cpu"


def flags_for(flag_set: Optional[str], backend: Optional[str] = None
              ) -> List[str]:
    """Resolve a named flag set for ``backend`` (default: best guess).

    Unknown set names raise — a typo'd ``system.xla.flag_set`` must not
    silently train without overlap scheduling.
    """
    name = (flag_set or "none").lower()
    if name not in FLAG_SETS:
        raise ValueError(
            f"unknown xla flag_set {flag_set!r} "
            f"(expected one of {flag_set_names()})")
    per_backend = FLAG_SETS[name]
    return list(per_backend.get(backend or guess_backend(), ()))


def missing_flags(flag_set: Optional[str], backend: Optional[str] = None,
                  env: Optional[Dict[str, str]] = None) -> List[str]:
    """Flags of the set NOT present in ``XLA_FLAGS`` — the dropped-flag
    signal the graftaudit rule gates on (empty list = all applied)."""
    current = (env if env is not None else os.environ).get("XLA_FLAGS", "")
    return [f for f in flags_for(flag_set, backend) if f not in current]


def _backend_initialized() -> bool:
    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge as xb

    return bool(xb.backends_are_initialized())


def apply_flag_set(flag_set: Optional[str] = DEFAULT_FLAG_SET,
                   backend: Optional[str] = None,
                   extra: Sequence[str] = ()) -> Dict[str, Any]:
    """Append the set's flags (plus config ``extra_flags``) to XLA_FLAGS.

    Must run before the jax backend initializes (flags are read once, at
    initialization). Returns the attribution stamp::

        {"xla_flag_set": name, "xla_backend": backend,
         "xla_flags": [...], "xla_flags_applied": bool, "reason": ...}

    ``xla_flags_applied`` is False when there was something to set but
    the backend had already initialized — the silent-drop case the audit
    rule exists to catch; the stamp makes it loud in events.jsonl too.
    Idempotent: flags already present in XLA_FLAGS are not re-appended.
    """
    backend = backend or guess_backend()
    flags = flags_for(flag_set, backend) + [str(f) for f in extra]
    stamp: Dict[str, Any] = {
        "xla_flag_set": (flag_set or "none").lower(),
        "xla_backend": backend,
        "xla_flags": flags,
        "xla_flags_applied": True,
    }
    if not flags:
        return stamp
    current = os.environ.get("XLA_FLAGS", "")
    to_add = [f for f in flags if f not in current]
    if not to_add:
        return stamp
    if _backend_initialized():
        stamp["xla_flags_applied"] = False
        stamp["reason"] = ("backend already initialized; flags would be "
                           "silently ignored — apply earlier or set "
                           "XLA_FLAGS in the launcher")
        return stamp
    os.environ["XLA_FLAGS"] = (current + " " + " ".join(to_add)).strip()
    return stamp
