"""Decoder-hybrid-decoder: Mamba layers, differential attention, and layers
that read an earlier layer's state (architecture ``sambay``; Phi-4-mini-flash,
arXiv:2507.06607).

What differs from ``models/llama.py``, block by block:

- **Five kinds of layer in one stack**, by ``layer_kinds`` (one letter a
  layer): ``M`` a Mamba-1 mixer (``ops/selective_scan.py``), ``S`` / ``F``
  differential attention under a sliding window / causally over everything,
  ``G`` a gated memory unit, ``C`` cross-attention. Every kind is
  ``h = x + Mix(LN1(x))``, ``x' = h + SwiGLU(LN2(h))`` with LayerNorms that
  have a bias; only ``Mix`` and its parameter tree differ, so the stack is a
  Python loop over per-layer trees and ``scan_layers`` has nothing to stack
  (see :func:`hidden_states`).
- **Two tensors cross layers.** The *memory* ``m [B, S, d_inner]`` is the
  scan's output (before the gate, with the ``D`` skip) of the last ``M``
  layer before the ``F`` layer; every ``G`` layer computes
  ``W2(m * SiLU(W1 u))`` from it. ``K, V`` of the ``F`` layer are read,
  unchanged, by every ``C`` layer, which projects only a query. Both are
  outputs of the layer that makes them and inputs of the layers that read
  them, so under remat they are saved once and autodiff sums the readers'
  cotangents into the producer.
- **Differential attention.** 40 query and 20 key heads of 64 pair up into
  20 and 10; the 20 value heads of 64 pair into 10 of 128. A pair computes
  ``A1 - lambda A2`` of two softmax maps over the same 128-wide values,
  normed over 128 and scaled by ``1 - lambda_init``. Both maps are one call
  of the flash kernels over the 40 + 40 stacked query heads (q, k 64 wide,
  v 128): query head ``h`` of the stack reads key/value head ``h // 2``,
  which is the pairing ``j -> j // 2`` for the first softmax's 20 heads and
  the second's.
- **No positions**, no rotary: the scans order the tokens.
- **Tied head**: the logits are ``LN_f(x) E^T`` and the table's gradient is
  the gather's plus the head's.

Training path only: no recurrent-state cache (``forward(cache=...)`` raises).
"""

from __future__ import annotations

import collections
import math
import threading
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops import attention as attention_ops
from ..ops import selective_scan as scan_lib
from . import stack
from .llama import _linear, mlp_block, rms_norm
from .registry import Architecture, register

Params = Dict[str, Any]
KINDS = ("M", "S", "F", "G", "C")


@dataclass(frozen=True)
class SambaYArgs:
    vocab_size: int = 259
    hidden_size: int = 64
    intermediate_size: int = 128
    num_layers: int = 6
    num_heads: int = 4                  # query heads, before pairing
    num_kv_heads: int = 2               # key heads (and value heads), before pairing
    head_dim: int = 16
    layer_kinds: Tuple[str, ...] = ("M", "S", "M", "F", "G", "C")
    sliding_window: int = 16
    layer_norm_eps: float = 1e-5
    d_state: int = 4
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 4
    attention_type: str = "simple"      # simple | flash
    tie_word_embeddings: bool = True
    matmul_precision: Optional[str] = None

    # What the trainer asks of any model's args.
    @property
    def is_moe(self) -> bool:
        return False

    @property
    def d_inner(self) -> int:
        return self.expand * self.hidden_size

    @property
    def memory_layer(self) -> Optional[int]:
        """The layer whose scan output is the memory: the last ``M`` before ``F``."""
        before = self.layer_kinds[:self.kv_layer] if self.kv_layer is not None else ()
        return max((i for i, k in enumerate(before) if k == "M"), default=None)

    @property
    def kv_layer(self) -> Optional[int]:
        return self.layer_kinds.index("F") if "F" in self.layer_kinds else None

    @classmethod
    def from_config(cls, model_cfg: Any, vocab_size: int) -> "SambaYArgs":
        sec = lambda name: dict(getattr(model_cfg, name, None) or {})
        att, norm, misc, ssm = (sec(k) for k in ("attention", "normalization", "misc", "ssm"))
        kinds = tuple(str(k) for k in sec("dimensions").get("layer_kinds") or ())
        if len(kinds) != model_cfg.num_layers or set(kinds) - set(KINDS):
            raise ValueError(f"dimensions.layer_kinds must name {model_cfg.num_layers} layers, each "
                             f"one of {KINDS}; got {kinds}")
        if kinds.count("F") > 1:
            raise ValueError("one F layer makes the keys and values every C layer reads")
        f = kinds.index("F") if "F" in kinds else len(kinds)
        if any(k == "C" for k in kinds[:f + 1]) or ("C" in kinds and "F" not in kinds):
            raise ValueError(f"a C layer reads the F layer's keys and values: {kinds}")
        if "G" in kinds and ("M" not in kinds[:f] or any(k == "G" for k in kinds[:f + 1])):
            raise ValueError(f"a G layer reads the memory of the last M layer before F: {kinds}")
        if model_cfg.num_heads % 2 or model_cfg.num_kv_heads % 2 \
                or (model_cfg.num_heads // 2) % (model_cfg.num_kv_heads // 2):
            raise ValueError("differential attention pairs the query heads and the key heads")
        if not bool(misc.get("tie_word_embeddings", True)):
            raise ValueError("sambay ties its head to the embedding")
        hidden = model_cfg.hidden_size
        return cls(
            vocab_size=vocab_size, hidden_size=hidden,
            intermediate_size=model_cfg.intermediate_size, num_layers=model_cfg.num_layers,
            num_heads=model_cfg.num_heads, num_kv_heads=model_cfg.num_kv_heads,
            head_dim=model_cfg.head_dim, layer_kinds=kinds,
            sliding_window=int(att["sliding_window"]),
            layer_norm_eps=float(norm.get("layer_norm_eps", 1e-5)),
            d_state=int(ssm.get("d_state", 16)), d_conv=int(ssm.get("d_conv", 4)),
            expand=int(ssm.get("expand", 2)),
            dt_rank=int(ssm.get("dt_rank") or math.ceil(hidden / 16)),
            attention_type=model_cfg.attention_type,
            matmul_precision=getattr(model_cfg, "matmul_precision", None),
        )


def lambda_init(layer: int) -> float:
    """A layer's share of the second softmax at initialisation, by its index in
    the stack that is run."""
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


# -- what was traced --------------------------------------------------------------
# Mamba and memory-unit layers, beside what ops/selective_scan.py traced (the
# attention layers by kind: ops/attention.py::core_counts). Counts traces.
_ssm_counts: Dict[str, int] = collections.Counter()
_plan_counts_lock = threading.Lock()
_ATTN_KIND = {"S": "window", "F": "global", "C": "cross"}  # a layer kind's name in the tally


def ssm_plan_counts() -> Dict[str, int]:
    """``mamba_layers`` and ``gmu_layers`` traced, then the scans by path and
    chunk (``ops/selective_scan.plan_counts``)."""
    with _plan_counts_lock:
        own = dict(_ssm_counts)
    return {**own, **{"scan_" + k: n for k, n in scan_lib.plan_counts().items()}}


# -- init ---------------------------------------------------------------------
def init_params(rng: jax.Array, args: SambaYArgs, dtype=jnp.float32) -> Params:
    """normal(0.02) matrices, zero biases, LayerNorm (1, 0); Mamba's own:
    ``A_log = log(1 .. N)``, ``D = 1``, ``b_dt`` the inverse softplus of a
    log-uniform step in [1e-3, 1e-1]; lambda vectors normal(0, 0.1)."""
    counter = iter(range(1 << 30))
    key = lambda: jax.random.fold_in(rng, next(counter))
    C, Di, N, R = args.hidden_size, args.d_inner, args.d_state, args.dt_rank
    H, G, D = args.num_heads, args.num_kv_heads, args.head_dim
    normal = lambda shape, std=0.02: (jax.random.normal(key(), shape, jnp.float32) * std).astype(dtype)
    dense = lambda shape: {"weight": normal(shape)}
    biased = lambda shape: {"weight": normal(shape), "bias": jnp.zeros(shape[-1:], dtype)}
    ln = lambda d: {"weight": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}

    def diff(kind):
        first = {"wq": biased((C, H * D))} if kind == "C" else {"wqkv": biased((C, (H + 2 * G) * D))}
        return {**first, "wo": biased((H * D, C)),
                **{f"lambda_{n}": normal((D,), 0.1) for n in ("q1", "k1", "q2", "k2")},
                "subln": {"weight": jnp.ones((2 * D,), dtype)}}

    def mamba():
        step = jnp.exp(jax.random.uniform(key(), (Di,), jnp.float32)
                       * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
        return {"in_proj": dense((C, 2 * Di)),
                "conv": {"weight": normal((Di, args.d_conv)), "bias": jnp.zeros((Di,), dtype)},
                "x_proj": dense((Di, R + 2 * N)),
                "dt_proj": {"weight": normal((R, Di)),
                            "bias": (step + jnp.log(-jnp.expm1(-step))).astype(dtype)},
                "A_log": jnp.log(jnp.broadcast_to(jnp.arange(1, N + 1, dtype=jnp.float32),
                                                  (Di, N))).astype(dtype),
                "D": jnp.ones((Di,), dtype),
                "out_proj": dense((Di, C))}

    def layer(kind):
        mixer = {"M": lambda: {"ssm": mamba()},
                 "G": lambda: {"gmu": {"w1": dense((C, Di)), "w2": dense((Di, C))}}}.get(
                     kind, lambda: {"attention": diff(kind)})()
        return {"attention_norm": ln(C), **mixer, "ffn_norm": ln(C),
                "feed_forward": {"w_gate": dense((C, args.intermediate_size)),
                                 "w_up": dense((C, args.intermediate_size)),
                                 "w_down": dense((args.intermediate_size, C))}}

    return {"tok_embeddings": dense((args.vocab_size, C)),
            "layers": [layer(k) for k in args.layer_kinds],
            "norm": ln(C)}


# -- sub-layers ---------------------------------------------------------------------
def layer_norm(x: jnp.ndarray, p: Params, eps: float) -> jnp.ndarray:
    """float32-internal LayerNorm with weight and bias."""
    xf = x.astype(jnp.float32)
    xf = xf - jnp.mean(xf, axis=-1, keepdims=True)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * p["weight"].astype(jnp.float32) + p["bias"].astype(jnp.float32)).astype(x.dtype)


def mamba_mixer(p: Params, u: jnp.ndarray, args: SambaYArgs):
    """``u [B, S, C]`` → ``(W_out(y * SiLU(z)), y)``: ``y [B, S, d_inner]`` float32
    is the scan's output, the memory where this is the memory's layer. The
    convolution, ``delta`` and the scan are float32; the four projections take
    operands in ``u``'s dtype."""
    Di, N, R = args.d_inner, args.d_state, args.dt_rank
    with jax.named_scope("ssm"):
        with jax.named_scope("ssm_proj"):
            a, z = jnp.split(u @ p["in_proj"]["weight"], 2, axis=-1)
        with jax.named_scope("ssm_conv"):
            c = jax.nn.silu(stack.causal_depthwise_conv(a, p["conv"]["weight"], p["conv"]["bias"]))
        with jax.named_scope("ssm_proj"):
            rbc = jnp.einsum("bsd,de->bse", c.astype(u.dtype), p["x_proj"]["weight"],
                             preferred_element_type=jnp.float32)
            r, b_t, c_t = jnp.split(rbc, (R, R + N), axis=-1)
            delta = jax.nn.softplus(
                jnp.einsum("bsr,rd->bsd", r.astype(u.dtype), p["dt_proj"]["weight"],
                           preferred_element_type=jnp.float32)
                + p["dt_proj"]["bias"].astype(jnp.float32))
        y = scan_lib.selective_scan(c, delta, -jnp.exp(p["A_log"].astype(jnp.float32)),
                                    b_t, c_t, p["D"])
        with jax.named_scope("ssm_proj"):
            gated = (y * jax.nn.silu(z.astype(jnp.float32))).astype(u.dtype)
            return gated @ p["out_proj"]["weight"], y


def gmu_mixer(p: Params, u: jnp.ndarray, m: jnp.ndarray) -> jnp.ndarray:
    with jax.named_scope("gmu"):
        gate = jax.nn.silu((u @ p["w1"]["weight"]).astype(jnp.float32))
        return (m.astype(jnp.float32) * gate).astype(u.dtype) @ p["w2"]["weight"]


def paired_kv(k: jnp.ndarray, v: jnp.ndarray):
    """``k, v [B, S, G, D]`` → the stacked keys ``[B, S, G, D]`` (the pairs' first
    keys, then their second) and the paired values ``[B, S, G / 2, 2 D]``."""
    B, S, G, D = k.shape
    k = k.reshape(B, S, G // 2, 2, D)
    return jnp.concatenate([k[:, :, :, 0], k[:, :, :, 1]], axis=2), v.reshape(B, S, G // 2, 2 * D)


def diff_attention_core(q, k_st, vbar, args: SambaYArgs, kind: str):
    """``q [B, S, H, D]``, stacked keys and paired values of :func:`paired_kv` →
    the two softmax maps' outputs ``A1, A2 [B, S, H / 2, 2 D]``."""
    B, S, H, D = q.shape
    q = q.reshape(B, S, H // 2, 2, D)
    q_st = jnp.concatenate([q[:, :, :, 0], q[:, :, :, 1]], axis=2)
    v_st = jnp.concatenate([vbar, vbar], axis=2)
    mask = dict(mask_type="sliding_window", window_size=args.sliding_window) if kind == "S" else {}
    out = attention_ops.attention_core(q_st, k_st, v_st, args.attention_type, kind=_ATTN_KIND[kind],
                                       scale=D ** -0.5, precision=args.matmul_precision, **mask)
    return out[:, :, :H // 2], out[:, :, H // 2:]


def diff_combine(p: Params, a1, a2, layer: int, eps: float):
    """``(1 - lambda_init) RMSNorm(A1 - lambda A2)``, float32 inside."""
    with jax.named_scope("attn_diff"):
        f32 = lambda n: p[n].astype(jnp.float32)
        lam = jnp.exp(jnp.sum(f32("lambda_q1") * f32("lambda_k1"))) \
            - jnp.exp(jnp.sum(f32("lambda_q2") * f32("lambda_k2"))) + lambda_init(layer)
        d = a1.astype(jnp.float32) - lam * a2.astype(jnp.float32)
        return (rms_norm(d, p["subln"]["weight"], eps) * (1.0 - lambda_init(layer))).astype(a1.dtype)


def attention_mixer(p: Params, u: jnp.ndarray, args: SambaYArgs, kind: str, layer: int, kv=None):
    """``S`` / ``F``: own q, k, v → ``(out, (k_st, vbar))``; ``C``: own q over
    ``kv``, which the ``F`` layer made."""
    B, S, _ = u.shape
    H, G, D = args.num_heads, args.num_kv_heads, args.head_dim
    with jax.named_scope("attn_qkv"):
        if kind == "C":
            q = _linear(u, p["wq"]).reshape(B, S, H, D)
        else:
            q, k, v = jnp.split(_linear(u, p["wqkv"]), (H * D, (H + G) * D), axis=-1)
            q = q.reshape(B, S, H, D)
            kv = paired_kv(k.reshape(B, S, G, D), v.reshape(B, S, G, D))
    a1, a2 = diff_attention_core(q, *kv, args, kind)
    o = diff_combine(p, a1, a2, layer, args.layer_norm_eps)
    with jax.named_scope("attn_out"):
        return _linear(o.reshape(B, S, H * D), p["wo"]), kv


def block(p: Params, x, args: SambaYArgs, kind: str, layer: int, m=None, kv=None):
    """One layer → ``(x', made)``: ``made`` is the memory (the memory's ``M``
    layer), the stacked keys and paired values (``F``), else None."""
    eps = args.layer_norm_eps
    made = None
    with jax.named_scope("layer"):
        with jax.named_scope("norm"):
            u = layer_norm(x, p["attention_norm"], eps)
        if kind == "M":
            y, mem = mamba_mixer(p["ssm"], u, args)
            if layer == args.memory_layer:
                made = mem.astype(x.dtype)
        elif kind == "G":
            y = gmu_mixer(p["gmu"], u, m)
        else:
            y, own = attention_mixer(p["attention"], u, args, kind, layer, kv)
            if kind == "F":
                made = own
        with jax.named_scope("norm"):
            h = x + y.astype(x.dtype)
            u = layer_norm(h, p["ffn_norm"], eps)
        with jax.named_scope("ffn"):
            y = mlp_block(p["feed_forward"], u)
        with jax.named_scope("norm"):
            return h + y, made


def hidden_states(params: Params, tokens: jnp.ndarray, args: SambaYArgs, compute_dtype=jnp.float32,
                  remat: Optional[str] = None, scan_layers: bool = False):
    """tokens [B, S] → the final-normed state [B, S, C].

    ``scan_layers`` is accepted and changes nothing: a ``lax.scan`` stacks
    layers of one parameter tree, and here a kind has one or two layers
    (published: 9, 8, 1, 7, 7, interleaved two kinds at a time), each kind its
    own tree. Every layer is its own rematerialised function that casts its
    weights inside, so the step holds one layer's bf16 copies at a time."""
    del scan_layers
    with jax.named_scope("embed"):
        x = params["tok_embeddings"]["weight"][tokens].astype(compute_dtype)
    m = kv = None
    for i, (p, kind) in enumerate(zip(params["layers"], args.layer_kinds)):
        if kind in "MG":
            with _plan_counts_lock:
                _ssm_counts["mamba_layers" if kind == "M" else "gmu_layers"] += 1
        fn = stack.own_layer(lambda p, x, m, kv, kind=kind, i=i: block(p, x, args, kind, i, m, kv),
                             compute_dtype, remat)
        x, made = fn(p, x, m if kind == "G" else None, kv if kind == "C" else None)
        if kind == "M" and made is not None:
            m = made
        elif kind == "F":
            kv = made
    with jax.named_scope("final_norm"):
        return layer_norm(x, params["norm"], args.layer_norm_eps)


def forward(params: Params, tokens: jnp.ndarray, args: SambaYArgs, cache=None, start_pos: Any = 0,
            compute_dtype=jnp.float32, remat: Optional[str] = None, scan_layers: bool = False,
            **_unused):
    """tokens [B, S] → (logits [B, S, V] float32, None)."""
    if cache is not None:
        raise NotImplementedError("sambay has no cached decode: no recurrent-state cache")
    h = hidden_states(params, tokens, args, compute_dtype, remat, scan_layers)
    return stack.head_logits(h, params["tok_embeddings"]["weight"], 0, compute_dtype), None


def loss_fn(params: Params, batch: Dict[str, jnp.ndarray], args: SambaYArgs,
            compute_dtype=jnp.float32, remat: Optional[str] = None, remat_ratio: float = 1.0,
            include_aux: bool = True, ce_chunk: int = -1, scan_layers: bool = False,
            z_loss_weight: float = 0.0, overlap: bool = False):
    """``(loss, token_count)``: masked mean cross-entropy through the fused CE
    with the embedding as the head; every layer is rematerialised whole
    (``remat_ratio`` is not split here). The CE's chunk stays the 2,048 rows of
    the other architectures at a vocabulary of 200,064 (1.64 GB of float32
    logits an array): the walk reads and writes its float32 ``dW`` (2.05 GB)
    once a chunk, so 512 rows cost 105 ms a step more on a v5e and saved 1.06
    GiB the step did not need (PERF.md section 6, PR 43)."""
    del remat_ratio, include_aux, overlap  # overlap: the llama stack's fsdp schedule
    h = hidden_states(params, batch["inputs"], args, compute_dtype, remat, scan_layers)
    # 0 (the trainer's "no fused CE" on an sp x tp mesh) has no unfused form here: automatic
    return stack.masked_ce(h, params["tok_embeddings"]["weight"], 0, batch, args.vocab_size,
                           ce_chunk or -1, z_loss_weight, compute_dtype)


def matmul_params_per_token(args: SambaYArgs) -> int:
    """Weights a token is multiplied by: the projections of every mixer, the
    FFNs, and the table once, as the head. Not the lookup, not the depthwise
    convolution, not the scan: those are no MXU work."""
    C, Di, N, R = args.hidden_size, args.d_inner, args.d_state, args.dt_rank
    H, G, D = args.num_heads, args.num_kv_heads, args.head_dim
    mixer = {"M": C * 2 * Di + Di * (R + 2 * N) + R * Di + Di * C,
             "G": 2 * C * Di,
             "S": C * (H + 2 * G) * D + H * D * C,
             "C": 2 * C * H * D}
    mixer["F"] = mixer["S"]
    ffn = 3 * C * args.intermediate_size
    return sum(mixer[k] + ffn for k in args.layer_kinds) + C * args.vocab_size


def flops_per_token(args: SambaYArgs, seq_len: int) -> float:
    """Training FLOPs a token requires on the MXU: 6 a multiplied weight, and
    each attention layer's two softmax maps under its mask: ``H`` head-maps a
    layer (``H / 2`` pairs, two maps each), ``2 (D + 2 D)`` operations a (query,
    key) pair forward, three times that with the backward. The scan's
    elementwise work (``d_inner * d_state`` state updates a token a layer) is
    VPU work and is left out."""
    pairs = sum(stack.band_positions(seq_len, args.sliding_window) if k == "S"
                else seq_len * (seq_len + 1) // 2 for k in args.layer_kinds if k in "SFC")
    return 6.0 * matmul_params_per_token(args) \
        + 3.0 * args.num_heads * 2 * (3 * args.head_dim) * pairs / seq_len


register(Architecture("sambay", SambaYArgs, init_params, forward, loss_fn,
                      flops_per_token=flops_per_token,
                      plans={"attn_plan": ("attention layers (traced, by kind and kernel path)",
                                           attention_ops.core_counts),
                             "ssm_plan": ("state-space layers (traced; scans by path and chunk)",
                                          ssm_plan_counts)}))
