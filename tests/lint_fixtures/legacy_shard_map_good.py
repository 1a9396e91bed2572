"""GOOD fixture: legacy-shard-map-import — the current API plus nearby
jax.experimental names the rule must not confuse with shard_map."""
import jax
from jax.experimental import mesh_utils


def run(f, mesh, x):
    return jax.shard_map(f, mesh=mesh)(x), mesh_utils
