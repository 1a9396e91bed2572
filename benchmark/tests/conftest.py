"""CPU settings for the benchmark's own tests: four host devices (the
four-chip cell's rehearsal), no persistent compile cache, repo on the path.
Run them by hand: ``python -m pytest benchmark/tests -q``. They are not part
of tier-1 (``tests/`` is untouched by the benchmark)."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)
