"""A KDA mixer's q, k, v prologue (ops/short_conv.py): the kernel pair, in
interpret mode, against the XLA form it stands in for (the convolution of
models/stack.py, SiLU, the head norm the mixer had), in value and in every
gradient; what a changed row can move; the plan; the tally.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlx_cuda_distributed_pretraining_tpu.models import kimi_linear as kl
from mlx_cuda_distributed_pretraining_tpu.models import stack
from mlx_cuda_distributed_pretraining_tpu.models.registry import resolve_architecture
from mlx_cuda_distributed_pretraining_tpu.ops import short_conv as sc

pytestmark = pytest.mark.usefixtures("no_mesh_left_behind")   # tests/conftest.py: every test here counts kernel calls
K = 4
# name: (B, S, D, heads, scale, bias, a's dtype, out dtype, block rows, block lanes) -> blocks along the rows
CASES = {
    "one_block_norm": (2, 16, 256, 2, 1.0, False, jnp.float32, jnp.float32, 16, 128),
    "one_block_no_norm": (1, 32, 128, None, 1.0, False, jnp.float32, jnp.float32, 32, 128),
    "several_blocks_norm_scaled": (2, 48, 256, 2, 128 ** -0.5, False, jnp.float32, jnp.float32, 16, 256),
    "several_blocks_bias_no_norm": (2, 64, 256, None, 1.0, True, jnp.float32, jnp.float32, 16, 128),
    "short_last_block_norm": (2, 40, 256, 2, 128 ** -0.5, False, jnp.float32, jnp.float32, 16, 128),
    "short_last_block_bias": (1, 40, 128, None, 1.0, True, jnp.float32, jnp.float32, 16, 128),
    "several_tiles_a_block": (1, 384, 128, 1, 1.0, False, jnp.float32, jnp.float32, 192, 128),
    "four_heads_a_block_norm_scaled": (1, 32, 512, 4, 128 ** -0.5, False, jnp.float32, jnp.float32, 16, 512),
    # a short last block whose later tiles lie wholly past the sequence: what they load is no number
    "short_last_block_several_tiles_norm": (1, 200, 128, 1, 1.0, False, jnp.float32, jnp.float32, 192, 128),
    "bfloat16_short_last_block_several_tiles_bias": (1, 208, 256, None, 1.0, True, jnp.bfloat16, jnp.bfloat16, 192, 256),
    "head_of_two_registers": (1, 32, 512, 2, 256 ** -0.5, False, jnp.float32, jnp.float32, 16, 512),
    "bfloat16_norm_scaled": (2, 96, 256, 2, 128 ** -0.5, False, jnp.bfloat16, jnp.bfloat16, 32, 256),
    "bfloat16_no_norm": (1, 64, 256, None, 1.0, False, jnp.bfloat16, jnp.bfloat16, 32, 128),
    "bfloat16_short_last_block": (1, 80, 256, 2, 1.0, False, jnp.bfloat16, jnp.bfloat16, 32, 256),
    "bfloat16_in_float32_out_short_last_block_bias": (1, 80, 256, None, 1.0, True, jnp.bfloat16, jnp.float32, 32, 256),
}


def _operands(Bt, S, D, bias, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    a = jax.random.normal(ks[0], (Bt, S, D), jnp.float32).astype(dtype)
    w = jax.random.normal(ks[1], (D, K), jnp.float32) * 0.5
    b = jax.random.normal(ks[2], (D,), jnp.float32) * 0.1 if bias else None
    return a, w, b, jax.random.normal(ks[3], (Bt, S, D), jnp.float32)


def _rel(a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernels_match_the_xla_form_in_value_and_every_gradient(case):
    Bt, S, D, heads, scale, bias, adt, odt, rows, lanes = CASES[case]
    a, w, b, g = _operands(Bt, S, D, bias, adt)
    d = D // heads if heads else None
    plan = sc._plan(S, D, d, min(jnp.dtype(adt).itemsize, jnp.dtype(odt).itemsize), "kernel", rows, lanes)
    assert plan.path == "kernel" and plan.rows == rows and plan.lanes == lanes and rows % plan.tile == 0
    assert ("several_tiles" in case) == (rows // plan.tile > 1)
    assert ("one_block" in case) == (S == rows) and ("short_last_block" in case) == (S % rows != 0)

    def both(backend):
        def loss(a, w, b):
            y = sc._short_conv(a, w, b, heads, scale, odt, backend, rows, lanes)
            return jnp.sum(y.astype(jnp.float32) * g), y
        return jax.value_and_grad(loss, argnums=(0, 1, 2) if bias else (0, 1), has_aux=True)(a, w, b)

    before = sc.plan_counts()
    (_, y), grads = both("kernel")
    (_, want), wants = both("xla")
    assert {k: n - before[k] for k, n in sc.plan_counts().items()} == {"conv_kernel": 1, "conv_xla": 1}
    assert y.dtype == want.dtype == jnp.dtype(odt) and y.shape == a.shape
    assert grads[0].dtype == a.dtype and grads[1].shape == (D, K)
    # a bfloat16 result is the same float32 number rounded once: a last place of a few values at most
    assert bool(jnp.all(jnp.isfinite(y.astype(jnp.float32)))) and _rel(y, want) < (1e-4 if odt == jnp.bfloat16 else 5e-7)
    gaps = {name: _rel(x, z) for name, x, z in zip(("a", "w", "bias"), grads, wants)}
    assert max(gaps.values()) < (1e-4 if adt == jnp.bfloat16 else 2e-6), gaps


def _held(eqn):
    """The jaxprs an equation holds (a jitted call's, a loop's body, a kernel's)."""
    return [getattr(j, "jaxpr", j) for v in eqn.params.values() for j in (v if isinstance(v, (list, tuple)) else [v])
            if hasattr(getattr(j, "jaxpr", j), "eqns")]


def _equations(jaxpr):
    """Equations of a jaxpr and of every jaxpr its equations hold (loop bodies, branches)."""
    return sum(1 + sum(_equations(j) for j in _held(e)) for e in jaxpr.eqns)


def _kernel_bodies(jaxpr, found=None):
    """``{kernel's name: its body's jaxpr}`` over every ``pallas_call`` under ``jaxpr``."""
    found = {} if found is None else found
    for e in jaxpr.eqns:
        if e.primitive.name == "pallas_call":
            found[e.params["name"]] = e.params["jaxpr"]
        else:
            for j in _held(e):
                _kernel_bodies(j, found)
    return found


# The bodies at the cell's plan as PR 53 left them (PERF.md section 6: forward 63 with the norm and 56
# without, backward 146 and 132, where PR 52's read 798, 686, 3,155 and 2,819 and took 0.2-0.8 s each to
# trace and lower): a quarter of room, so that a kernel that unrolls its heads or its tiles again fails.
BODY_EQUATIONS = {"short_conv_fwd": 80, "short_conv_bwd": 185}


@pytest.mark.parametrize("heads", [32, None])
def test_the_kernel_bodies_at_the_cells_plan_stay_small(heads):
    """A body holds its tile routine once: the heads of a block are trips of a loop,
    the first tile is the row loop's own, and q and k (a scale apart) are one body."""
    Bt, S, D = 2, 8192, 4096
    a, w = jax.ShapeDtypeStruct((Bt, S, D), jnp.bfloat16), jax.ShapeDtypeStruct((D, K), jnp.bfloat16)

    def traced(scale):
        call = lambda a, w: sc.short_conv(a, w, heads=heads, scale=scale, backend="kernel").astype(jnp.float32).sum()
        return _kernel_bodies(jax.make_jaxpr(jax.value_and_grad(call, argnums=(0, 1)))(a, w).jaxpr)

    bodies = traced(1.0)
    assert sorted(bodies) == ["short_conv_bwd", "short_conv_fwd"]
    counts = {name: _equations(body) for name, body in bodies.items()}
    assert all(0 < counts[name] <= BODY_EQUATIONS[name] for name in counts), counts
    # the scale is an operand: another scale is the same traced body, not a second one
    again = traced(128 ** -0.5)
    assert all(again[name] is bodies[name] for name in bodies)


def test_the_xla_form_is_the_mixers_old_code():
    """``causal_depthwise_conv``, SiLU, the norm over ``[B, S, H, d]``, the cast: letter for letter."""
    a, w, _, _ = _operands(2, 24, 96, False, jnp.float32)
    old = jax.nn.silu(stack.causal_depthwise_conv(a, w)).reshape(2, 24, 3, 32)
    old = (old * (jax.lax.rsqrt(jnp.sum(old * old, axis=-1, keepdims=True) + 1e-6) * 32 ** -0.5)).astype(jnp.bfloat16)
    new = sc.short_conv(a, w, heads=3, scale=32 ** -0.5, out_dtype=jnp.bfloat16, backend="xla")
    assert new.dtype == jnp.bfloat16 and bool(jnp.all(new == old.reshape(2, 24, 96)))
    plain = sc.short_conv(a, w, backend="xla")
    assert plain.dtype == a.dtype and bool(jnp.all(plain == jax.nn.silu(stack.causal_depthwise_conv(a, w))))
    assert sc.L2_EPS == 1e-6


@pytest.mark.parametrize("row", [15, 16, 31, 47])
def test_a_changed_row_moves_no_output_before_it_and_none_more_than_three_after(row):
    """Blocks of 16 rows: row 15 is a block's last (its successors read it through
    the view before their block), 16 a block's first, 47 the sequence's last."""
    S, D = 48, 256
    a, w, _, g = _operands(1, S, D, False, jnp.float32, seed=3)
    call = lambda a: sc._short_conv(a, w, None, 2, 1.0, jnp.float32, "kernel", 16, 128)
    moved = a.at[0, row].add(1.0)
    rows = np.flatnonzero(np.asarray(jnp.any(call(a) != call(moved), axis=(0, 2))))
    assert rows.tolist() == list(range(row, min(row + K, S)))
    # and the transpose: a changed cotangent row moves da at that row and the three before it
    da = lambda g: jax.grad(lambda a: jnp.sum(call(a) * g))(a)
    rows = np.flatnonzero(np.asarray(jnp.any(da(g) != da(g.at[0, row].add(1.0)), axis=(0, 2))))
    assert rows.tolist() == list(range(max(row - K + 1, 0), row + 1))


def test_the_plan_reads_shapes_backend_and_mesh(monkeypatch):
    from jax.sharding import Mesh

    from mlx_cuda_distributed_pretraining_tpu.parallel.context import use_mesh

    monkeypatch.delenv("KDA_BACKEND", raising=False)
    assert sc.short_conv_plan(8192, 4096, 128, 2).path == "xla"          # no TPU here
    monkeypatch.setenv("KDA_BACKEND", "kernel")                          # the mixer's one switch
    assert sc.short_conv_plan(8192, 4096, 128, 2) == ("kernel", sc._BLOCK_ROWS, sc._BLOCK_LANES, sc._TILE_ROWS)
    assert sc.short_conv_plan(8192, 4096, None, 2).path == "kernel"
    assert sc.short_conv_plan(16384, 5120, None, 4) == ("kernel", 512, 1024, 64)   # sambay's call: 5 lane blocks
    assert sc.short_conv_plan(8192, 4096, 512, 2).lanes == 1024 and sc.short_conv_plan(8192, 4096, 2048, 2).lanes == 2048
    assert sc.short_conv_plan(40, 256, 128, 4) == ("kernel", 40, 256, 8)            # rows of whole registers
    assert sc.short_conv_plan(40, 256, 128, 2) == ("kernel", 32, 256, 32)           # a packed tile is 16 rows
    for S, D, d in ((8192, 96, None), (8192, 4096, 64), (8192, 4096, 128 * 3), (8196, 4096, 128), (8, 256, 128)):
        assert sc.short_conv_plan(S, D, d, 2).path == "xla", (S, D, d)
    assert sc.short_conv_plan(8192, 4096, 128, 2, "xla").path == "xla"
    with pytest.raises(ValueError, match="backend"):
        sc.short_conv_plan(64, 128, 128, 2, "mosaic")
    with pytest.raises(ValueError, match="heads"):
        sc.short_conv(jnp.zeros((1, 16, 128)), jnp.zeros((128, K)), heads=3)

    a, w, _, _ = _operands(2, 32, 256, False, jnp.float32)
    tally = sc.plan_counts
    before = tally()
    want = sc.short_conv(a, w, heads=2)                                  # KDA_BACKEND=kernel, no mesh
    assert tally() == {"conv_kernel": before["conv_kernel"] + 1, "conv_xla": before["conv_xla"]}
    with use_mesh(Mesh(np.array(jax.devices()[:2]), ("fsdp",))):
        out = sc.short_conv(a, w, heads=2, backend="kernel")            # GSPMD cannot partition the kernels
    narrow = sc.short_conv(a[..., :96], w[:96], backend="kernel")        # 96 channels fill no register
    assert tally() == {"conv_kernel": before["conv_kernel"] + 1, "conv_xla": before["conv_xla"] + 2}
    assert float(jnp.abs(out - want).max()) < 1e-6 and narrow.shape == (2, 32, 96)
    with use_mesh(Mesh(np.array(jax.devices()[:1]), ("fsdp",))):         # a mesh of one device is no mesh
        sc.short_conv(a, w, heads=2)
    assert tally()["conv_kernel"] == before["conv_kernel"] + 2


def test_the_tallys_keys():
    """The prologue's counts ride on the mixer's one tally, ``kda_plan``; the
    architecture declares no other plan and ``kernel`` / ``xla`` stay the cores'."""
    assert tuple(sc.plan_counts()) == ("conv_kernel", "conv_xla")
    keys = list(kl.kda_plan_counts())
    assert keys[:4] == ["kda_layers", "latent_layers", "kernel", "xla"] and keys[-2:] == ["conv_kernel", "conv_xla"]
    assert set(resolve_architecture("kimi_linear").plans) == {"kda_plan"}
    assert resolve_architecture("kimi_linear").plans["kda_plan"][1]() == kl.kda_plan_counts()
