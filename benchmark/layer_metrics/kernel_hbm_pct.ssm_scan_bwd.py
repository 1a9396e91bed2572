"""Share of the chip's HBM bandwidth that the bytes a selective scan's backward
has to move (``flops/ssm_scan.py``) would need, over ``ssm_scan_bwd``'s self
time, by ``_ssm_scan.py``."""

from _ssm_scan import kernel_hbm_pct


def read(sources):
    return kernel_hbm_pct(sources, "ssm_scan_bwd")
