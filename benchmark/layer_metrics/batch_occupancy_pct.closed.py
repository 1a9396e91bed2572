"""``batch_occupancy_pct`` in a closed-loop cell, whose end-to-end metric is another."""
from batch_occupancy_pct import read  # noqa: F401
