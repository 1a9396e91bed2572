"""``kv_blocks_used_peak_pct`` in a closed-loop cell, whose end-to-end metric is another."""
from kv_blocks_used_peak_pct import read  # noqa: F401
