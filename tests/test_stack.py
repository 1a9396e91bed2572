"""The scaffold the model modules share (models/stack.py) and the one function
that chooses an attention core's kernel (ops/attention.py::attention_core):
each is held here once, to what the model modules' own tests hold them through
a whole model.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlx_cuda_distributed_pretraining_tpu.models import stack
from mlx_cuda_distributed_pretraining_tpu.ops import masks
from mlx_cuda_distributed_pretraining_tpu.ops.attention import (
    attention_core, core_counts, reference_attention)

B, S, H, G, D = 1, 128, 4, 2, 16
MASKS = {"causal": ({}, masks.causal()),
         "window": (dict(mask_type="sliding_window", window_size=32), masks.sliding_window(32))}


def _qkv(dv=D):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    return (jax.random.normal(ks[0], (B, S, H, D)), jax.random.normal(ks[1], (B, S, G, D)),
            jax.random.normal(ks[2], (B, S, G, dv)))


# -- which kernel runs a core ---------------------------------------------------------
@pytest.mark.parametrize("dv", [D, D // 2], ids=["v_as_wide", "v_narrower"])
@pytest.mark.parametrize("mask", sorted(MASKS))
@pytest.mark.parametrize("attention_type", ["simple", "flash"])
def test_attention_core_is_the_reference_under_the_mask_said_once(attention_type, mask, dv):
    """Either kernel (the flash kernels interpreted here), causal and window, ``v``
    as wide as ``q`` and narrower, under a scale of the caller's: the reference's
    output under the ``masks`` closure of the same mask."""
    said, mod = MASKS[mask]
    q, k, v = _qkv(dv)
    got = attention_core(q, k, v, attention_type, scale=0.2, **said)
    want = reference_attention(q, k, v, mask_mod=mod, scale=0.2)
    assert got.shape == (B, S, H, dv)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("kind,scope", [(None, None), ("window", "attn_window"),
                                        ("global", "attn_global"), ("cross", "attn_global")])
@pytest.mark.parametrize("attention_type", ["simple", "flash"])
def test_attention_core_counts_what_its_callers_counted_under_their_scopes(attention_type, kind, scope):
    """A kind's layer and its three kernels' paths (``*_simple`` without the
    kernels) under the keys ``attn_plan`` has always had; no kind, no tally. The
    core's operations sit under ``attn_core``, below the kind's scope where there
    is one: a cross layer's under the global layer's."""
    q, k, v = _qkv(D // 2)
    before = core_counts()
    jaxpr = jax.make_jaxpr(lambda *a: attention_core(*a, attention_type, kind=kind))(q, k, v)
    traced = {key: n - before.get(key, 0) for key, n in core_counts().items() if n - before.get(key, 0)}
    paths = ("simple",) if attention_type == "simple" else (
        "fwd_resident", "bwd_dq_resident", "bwd_dkv_resident")
    assert traced == ({} if kind is None else {f"{kind}_{w}": 1 for w in ("layers",) + paths})
    stacks = {str(e.source_info.name_stack) for e in jaxpr.jaxpr.eqns}
    want = "attn_core" if scope is None else f"{scope}/attn_core"
    assert stacks and all(s == want or s.startswith(want + "/") for s in stacks), stacks


def test_a_mask_is_named_as_the_flash_kernels_name_it():
    from mlx_cuda_distributed_pretraining_tpu.ops.attention import named_mask_mod

    assert named_mask_mod() is masks.causal()
    assert named_mask_mod("sliding_window", 32) is masks.sliding_window(32)
    assert named_mask_mod("prefix_lm", prefix_len=8) is masks.prefix_lm(8)


# -- a layer's cast and remat, and the stack ------------------------------------------
C = 8


def _layers(n, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), n)
    return [{"w": jax.random.normal(k, (C, C)) * 0.3, "b": jnp.full((C,), 0.1 * i)}
            for i, k in enumerate(keys)]


def _block(p, x, flag):
    """A layer whose flag changes what it computes, static (loop) or traced (scan)."""
    y = jnp.tanh(x @ p["w"] + p["b"])
    if flag is None:
        pass
    elif isinstance(flag, bool):
        y = -y if flag else y
    else:
        y = jnp.where(flag, -y, y)
    x = x + y.astype(x.dtype)
    return x, {"sq": jnp.sum(jnp.square(x.astype(jnp.float32))), "n": jnp.ones((), jnp.float32)}


ZERO = lambda: {"sq": jnp.zeros((), jnp.float32), "n": jnp.zeros((), jnp.float32)}


def _run(params, x, scan, flags, remat, dtype=jnp.float32):
    lead, rest = params
    n = len(lead)
    x, dropped = stack.run_layers(_block, x.astype(dtype), lead, dtype, remat,
                                  flags=flags and flags[:n])
    assert dropped is None
    x, outs = stack.run_layers(_block, x, rest, dtype, remat, scan=scan, flags=flags and flags[n:],
                               zero=ZERO())
    return x, outs


@pytest.mark.parametrize("remat", [None, "full", "dots"])
@pytest.mark.parametrize("flags", [None, [True, False, True, False], [False, True, True, True]],
                         ids=["no_flag", "mixed_flags", "one_kind_scanned"])
def test_a_stack_scanned_is_the_stack_looped(flags, remat):
    """Leading layers, then the rest scanned or looped: the same carry, the same
    outputs summed over the rest (the leading layers' dropped), the same gradient
    to every layer and to the input; with a flag a layer, static in a loop and
    traced in a scan (and static there too where the scanned layers agree)."""
    params = (_layers(1, seed=1), _layers(3, seed=2))
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 5, C))

    def loss(params, x, scan):
        y, outs = _run(params, x, scan, flags, remat)
        return jnp.sum(y * y) + outs["sq"], (y, outs)

    (l0, (y0, o0)), g0 = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(params, x, False)
    (l1, (y1, o1)), g1 = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(params, x, True)
    assert float(o0["n"]) == float(o1["n"]) == 3.0
    np.testing.assert_allclose(y1, y0, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(o1["sq"], o0["sq"], rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(g1), jax.tree_util.tree_leaves(g0)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)
    if flags is not None:  # and the flag is read: other flags, another result
        other = [not f for f in flags]
        y2, _ = _run(params, x, True, other, remat)
        assert float(jnp.max(jnp.abs(y2 - y1))) > 1e-3


def test_a_scan_traces_a_flag_only_where_the_layers_differ():
    """One kind alone needs no flag: its block is traced with the static value,
    so a model of two kinds of layer pays no ``cond`` for a stack of one."""
    seen = []

    def block(p, x, flag):
        seen.append(flag)
        return _block(p, x, flag)

    x = jnp.zeros((1, 2, C))
    stack.run_layers(block, x, _layers(2), jnp.float32, None, scan=True, flags=[True, True])
    assert seen == [True]
    seen.clear()
    stack.run_layers(block, x, _layers(2), jnp.float32, None, scan=True, flags=[True, False])
    assert len(seen) == 1 and isinstance(seen[0], jax.core.Tracer) and seen[0].shape == ()


def _eqns(jaxpr):
    for e in jaxpr.eqns:
        yield e
        for v in e.params.values():
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                yield from _eqns(inner)


def test_a_layer_outside_a_scan_casts_its_weights_inside_its_rematerialised_function():
    """The backward pass casts them again, and the step does not hold the bfloat16
    copies in between: in the looped stack's program every float32 → bfloat16
    conversion of a weight is an equation of a checkpointed function, none of the
    function around it; a scanned stack casts what it stacks, outside."""
    params = _layers(2)
    x = jnp.zeros((1, 2, C), jnp.bfloat16)

    def casts(scan):
        jaxpr = jax.make_jaxpr(lambda p, x: stack.run_layers(
            _block, x, p, jnp.bfloat16, "full", scan=scan, zero=ZERO()))(params, x).jaxpr
        weight_cast = lambda e: (e.primitive.name == "convert_element_type"
                                 and e.invars[0].aval.dtype == jnp.float32
                                 and e.params["new_dtype"] == jnp.bfloat16
                                 and e.invars[0].aval.shape in ((C, C), (C,)))
        top = [e for e in jaxpr.eqns if weight_cast(e)]
        inside = [e for outer in jaxpr.eqns if outer.primitive.name in ("checkpoint", "remat2")
                  for e in _eqns(outer.params["jaxpr"]) if weight_cast(e)]
        return len(top), len(inside)

    assert casts(scan=False) == (0, 4)   # two leaves a layer, two layers
    assert casts(scan=True) == (4, 0)
    with pytest.raises(ValueError, match="unknown remat policy"):
        stack.layer_checkpoint("ful")
    f = lambda a: a
    assert stack.layer_checkpoint(None)(f) is f and stack.layer_checkpoint("none")(f) is f


def test_cast_layer_leaves_quantized_leaves_alone():
    tree = {"weight_q": jnp.ones((2, 2), jnp.int8), "weight_s": jnp.ones((2,), jnp.float32)}
    out = stack.cast_layer(tree, jnp.bfloat16)
    assert out["weight_q"].dtype == jnp.int8 and out["weight_s"].dtype == jnp.bfloat16


# -- the head and the loss tail ---------------------------------------------------------
@pytest.mark.parametrize("vocab_axis", [0, 1], ids=["tied_table", "output_matrix"])
@pytest.mark.parametrize("ce_chunk,z", [(-1, 0.0), (3, 1e-3)])
def test_masked_ce_is_the_plain_masked_mean_on_a_tiny_head(vocab_axis, ce_chunk, z):
    V, Cw, Bt, St = 11, 6, 2, 7
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    h = jax.random.normal(ks[0], (Bt, St, Cw))
    w_vc = jax.random.normal(ks[1], (V, Cw))
    weight = w_vc if vocab_axis == 0 else w_vc.T
    targets = jax.random.randint(ks[2], (Bt, St), 0, V)
    mask = jnp.asarray(np.random.default_rng(0).integers(0, 2, (Bt, St)), jnp.int32)
    batch = {"targets": targets, "mask": mask}

    def plain(h, weight):
        logits = stack.head_logits(h, weight, vocab_axis, jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
        m = mask.astype(jnp.float32)
        return jnp.sum(((logz - gold) + z * jnp.square(logz)) * m) / jnp.maximum(m.sum(), 1.0)

    fused = lambda h, weight: stack.masked_ce(h, weight, vocab_axis, batch, V, ce_chunk, z,
                                              jnp.float32)
    np.testing.assert_allclose(stack.head_logits(h, weight, vocab_axis, jnp.float32),
                               jnp.einsum("bsc,vc->bsv", h, w_vc), rtol=1e-5, atol=1e-5)
    (loss, count), grads = jax.value_and_grad(fused, argnums=(0, 1), has_aux=True)(h, weight)
    want, want_grads = jax.value_and_grad(plain, argnums=(0, 1))(h, weight)
    assert float(count) == float(mask.sum())
    np.testing.assert_allclose(loss, want, rtol=1e-5)
    for a, b in zip(grads, want_grads):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)
    # an empty mask is a loss of 0, not a division by 0
    empty = dict(batch, mask=jnp.zeros_like(mask))
    assert float(stack.masked_ce(h, weight, vocab_axis, empty, V, ce_chunk, z, jnp.float32)[0]) == 0.0


def test_one_chunk_rule():
    """Below 0 is automatic: what ``auto_chunk`` says, and 2,048 rows where it
    would not fuse (these losses have no unfused form); anything else is taken."""
    assert stack.ce_chunk_rows(-1, 2, 64, 259) == 2048            # small logits: auto_chunk says 0
    assert stack.ce_chunk_rows(-1, 4, 4096, 32768) == 2048
    assert stack.ce_chunk_rows(512, 4, 4096, 32768) == 512
    assert stack.ce_chunk_rows(0, 4, 4096, 32768) == 0


def test_band_positions_is_a_windows_pairs():
    assert stack.band_positions(16384, 2048) == 2048 * 2049 // 2 + (16384 - 2048) * 2048
    assert stack.band_positions(128, 2048) == 128 * 129 // 2
    assert stack.band_positions(8, 1) == 8


def test_the_scaffold_imports_no_model_module():
    import ast
    import inspect

    tree = ast.parse(inspect.getsource(stack))
    local = [n for n in ast.walk(tree) if isinstance(n, ast.ImportFrom) and n.level == 1]
    assert local == [], [ast.dump(n) for n in local]   # only ..ops, never a sibling under models/
