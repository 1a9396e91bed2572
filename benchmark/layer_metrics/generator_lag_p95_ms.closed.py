"""``generator_lag_p95_ms`` in a closed-loop cell, whose end-to-end metric is another."""
from generator_lag_p95_ms import read  # noqa: F401
