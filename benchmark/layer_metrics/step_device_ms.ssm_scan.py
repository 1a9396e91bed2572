"""Device time a step of the two selective-scan kernels (``ssm_scan_fwd``:
forward and recomputed; ``ssm_scan_bwd``), by their names in the name stack.
Inside ``step_device_ms.ssm``."""

from _named_scopes import step_ms_under


def read(sources):
    parts = [step_ms_under(sources, k) for k in ("ssm_scan_fwd", "ssm_scan_bwd")]
    return None if all(p is None for p in parts) else sum(p or 0.0 for p in parts)
