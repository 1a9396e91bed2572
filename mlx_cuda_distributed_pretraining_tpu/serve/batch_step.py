"""Jitted steps for the continuous-batching engine.

Slotted backend — two compiled functions drive the whole engine:

- ``decode_step`` advances EVERY pool slot one token in one dispatch.
  Each row carries its own position (requests join mid-flight at
  different depths), so RoPE and the cache write are per-row: rotation
  tables are computed from a ``[num_slots]`` position vector and the KV
  write is a row-wise scatter ``cache.at[row, pos[row]]``. Free /
  still-prefilling rows ride along masked: the host points them at the
  reserved junk position (``max_len - 1``) with token 0 and discards
  their outputs — the compiled shape never changes with occupancy.

- ``prefill_step`` writes one chunk of one request's prompt into its
  slot. Chunks are fixed-size (compile-once per attend bucket); the last
  chunk is padded and the true-last-token logits row is selected by a
  traced index. Junk written past the true length is overwritten by
  decode before it can ever be attended — the same invariant the
  single-sequence bucketed prefill relies on (infer/generate.py).

Numerics deliberately replicate the locked decode path op-for-op
(llama building blocks, fp32 compute, the same positional validity
mask), so batch-1 greedy output is token-identical to ``generate_text``
(tests/test_serve.py). Sampling is greedy/temperature per slot — the
same per-request rng chain (split-then-sample per token) as
``generate_step``, vmapped over rows.

Paged backend — the same engine driven through block tables
(``paged_prefill_step`` / ``paged_decode_step``): every KV read/write is
routed through a fixed-shape ``[num_seqs, max_blocks]`` table, so the
compiled step is identical regardless of which physical blocks a
sequence holds. ``paged_decode_step`` additionally folds in-batch
speculative decoding into the decode dispatch: with ``draft_len = k``
every row carries ``[last_token, d1..dk]``, ONE forward verifies all
drafts for all rows, and the host commits only accepted prefixes by
advancing row lengths — rejected tail positions are never referenced
by any block table, so there is no rollback copy. ``draft_len = 0`` is
plain paged decode.

Prefix caching needs NO step changes: an admission that adopts cached
blocks simply starts ``paged_prefill_step`` at ``start = adopted
tokens`` with a table whose leading entries point at SHARED physical
blocks — the attention mask (``k_idx <= position``) attends the adopted
prefix through the same table indirection as self-written blocks, and
since writes only ever land at positions ``>= length`` (tail or fresh
blocks), shared full blocks are immutable by construction.

Like infer/generate.py, compiled steps are cached per (args, shape
bucket); attend lengths are power-of-two buckets so a long-serving
engine compiles O(log max_len) variants, not one per position.

Tensor-parallel serving: every factory takes an optional serving
``mesh`` (parallel/mesh.py::build_serve_mesh, tp×dp). Params arrive
pre-placed per the training sharding rules (Megatron-style column/row
splits), the KV buffers are constrained to ``kv_cache_pspec`` (head dim
over ``tp``) on the way in AND out — donation-compatible — and logits
replicate at the single Megatron gather point before sampling. GSPMD
partitions everything in between; host-visible shapes, shape buckets,
and the per-step host-sync count are unchanged, so the scheduler is
oblivious to the mesh.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ..infer.generate import _attend_bucket, _round_up, _spec_accept_one
from ..models import llama
from ..ops.attention import reference_attention
from ..ops.donation import donate_argnums

_STEP_CACHE: Dict[Any, Any] = {}

# Re-exported so the scheduler/engine size buckets the same way the
# single-sequence generator does.
attend_bucket = _attend_bucket
round_up = _round_up


def _rope_rows(x: jnp.ndarray, positions: jnp.ndarray,
               args: llama.LlamaArgs) -> jnp.ndarray:
    """Per-row RoPE: ``x [B, S, H, D]`` rotated by ``positions [B, S]``.

    Elementwise-identical to ``rope_cos_sin`` + ``apply_rope`` (which
    take one shared position vector); only the broadcast differs."""
    pos = positions.astype(jnp.float32)
    if args.rope_scaling_factor:
        pos = pos / args.rope_scaling_factor
    Dh = args.head_dim
    inv_freq = 1.0 / (args.rope_theta
                      ** (jnp.arange(0, Dh, 2, dtype=jnp.float32) / Dh))
    angles = pos[:, :, None] * inv_freq[None, None, :]  # [B, S, Dh//2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    if args.rope_traditional:
        x1 = xf[..., 0::2]
        x2 = xf[..., 1::2]
        out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                        axis=-1).reshape(x.shape)
    else:
        half = x.shape[-1] // 2
        x1 = xf[..., :half]
        x2 = xf[..., half:]
        out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                              axis=-1)
    return out.astype(dtype)


def _write_kv_rows(layer_cache, k, v, rows, pos):
    """Scatter decode K/V ``[B, 1, H, D]`` at per-row positions; returns
    (new_layer_cache, keys_fp, values_fp) with the full-buffer fp views."""
    if "k_q" in layer_cache:
        kq, ks = llama._quantize_kv(k)
        vq, vs = llama._quantize_kv(v)
        new = {
            "k_q": layer_cache["k_q"].at[rows, pos].set(kq[:, 0]),
            "k_s": layer_cache["k_s"].at[rows, pos].set(ks[:, 0]),
            "v_q": layer_cache["v_q"].at[rows, pos].set(vq[:, 0]),
            "v_s": layer_cache["v_s"].at[rows, pos].set(vs[:, 0]),
        }
        keys = new["k_q"].astype(jnp.float32) * new["k_s"]
        values = new["v_q"].astype(jnp.float32) * new["v_s"]
    else:
        dt = layer_cache["k"].dtype
        new = {
            "k": layer_cache["k"].at[rows, pos].set(k[:, 0].astype(dt)),
            "v": layer_cache["v"].at[rows, pos].set(v[:, 0].astype(dt)),
        }
        keys, values = new["k"], new["v"]
    return new, keys, values


def _write_kv_slot(layer_cache, k, v, slot, pos):
    """Write a prefill chunk ``[1, C, H, D]`` into one slot at ``pos``;
    returns (new_layer_cache, keys_fp [1, T, H, D], values_fp)."""
    if "k_q" in layer_cache:
        kq, ks = llama._quantize_kv(k)
        vq, vs = llama._quantize_kv(v)
        dus = jax.lax.dynamic_update_slice
        new = {
            "k_q": dus(layer_cache["k_q"], kq, (slot, pos, 0, 0)),
            "k_s": dus(layer_cache["k_s"], ks, (slot, pos, 0, 0)),
            "v_q": dus(layer_cache["v_q"], vq, (slot, pos, 0, 0)),
            "v_s": dus(layer_cache["v_s"], vs, (slot, pos, 0, 0)),
        }
        T = new["k_q"].shape[1]
        sl = lambda a: jax.lax.dynamic_slice(
            a, (slot, 0, 0, 0), (1,) + a.shape[1:])
        keys = sl(new["k_q"]).astype(jnp.float32) * sl(new["k_s"])
        values = sl(new["v_q"]).astype(jnp.float32) * sl(new["v_s"])
        del T
    else:
        dt = layer_cache["k"].dtype
        dus = jax.lax.dynamic_update_slice
        new = {
            "k": dus(layer_cache["k"], k.astype(dt), (slot, pos, 0, 0)),
            "v": dus(layer_cache["v"], v.astype(dt), (slot, pos, 0, 0)),
        }
        sl = lambda a: jax.lax.dynamic_slice(
            a, (slot, 0, 0, 0), (1,) + a.shape[1:])
        keys, values = sl(new["k"]), sl(new["v"])
    return new, keys, values


def _qkv(p, x, args, rope):
    """First half of a block up to the rotated q/k/v ``[B, S, H, Dh]``,
    under the scope names of the training forward (models/llama.py).
    ``rope`` rotates one ``[B, S, H, Dh]`` tensor by the step's positions."""
    B, S, _ = x.shape
    Hq, Hkv, Dh = args.num_heads, args.num_kv_heads, args.head_dim
    with jax.named_scope("norm"):
        h = llama.rms_norm(x, p["attention_norm"]["weight"], args.rms_norm_eps)
    pa = p["attention"]
    with jax.named_scope("attn_qkv"):
        q = llama._linear(h, pa["wq"]).reshape(B, S, Hq, Dh)
        k = llama._linear(h, pa["wk"]).reshape(B, S, Hkv, Dh)
        v = llama._linear(h, pa["wv"]).reshape(B, S, Hkv, Dh)
        return rope(q), rope(k), v


def _attn_out_ffn(p, x, out, args):
    """Second half of a block: output projection and residual, then the
    dense MLP or MoE (position-free, so shared with the training forward
    as-is) and its residual."""
    B, S, _ = x.shape
    with jax.named_scope("attn_out"):
        x = x + llama._linear(out.reshape(B, S, -1), p["attention"]["wo"])
    with jax.named_scope("norm"):
        h = llama.rms_norm(x, p["ffn_norm"]["weight"], args.rms_norm_eps)
    if args.is_moe:
        from ..models.moe import moe_block

        ff, _aux = moe_block(p["feed_forward"], h, args)
        return x + ff
    with jax.named_scope("ffn"):
        return x + llama.mlp_block(p["feed_forward"], h)


def _project_logits(params, x, args):
    """Final norm and output projection, op-identical to llama.forward's
    logits path (fp32 accumulation; params assumed fp32 — serving compute
    dtype)."""
    with jax.named_scope("final_norm"):
        x = llama.rms_norm(x, params["norm"]["weight"], args.rms_norm_eps)
    with jax.named_scope("lm_head_ce"):
        return _head(params, x, args)


def _head(params, x, args):
    if args.tie_word_embeddings or "output" not in params:
        logits = jax.lax.dot_general(
            x, params["tok_embeddings"]["weight"],
            (((2,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    else:
        logits = jax.lax.dot_general(
            x, params["output"]["weight"],
            (((2,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        if "bias" in params["output"]:
            logits = logits + params["output"]["bias"].astype(jnp.float32)
    if args.logit_scale:
        logits = logits * args.logit_scale
    return logits


def _donate_cache():
    # Donating the pool buffers makes the per-iteration cache update
    # in-place on accelerators; the CPU backend has no donation support,
    # so ops/donation.py gates it off there (and graftaudit forces it
    # back on when lowering these steps for the donation audit).
    return donate_argnums(1)


def kv_cache_pspec(mesh: Optional[Mesh], num_kv_heads: int) -> P:
    """PartitionSpec for a KV buffer ``[rows, T, Hkv, *]``: the head dim
    over ``tp`` when it divides. Both pool layouts put heads at dim 2 —
    slotted ``[slots, max_len, Hkv, Dh]``, paged arena ``[num_blocks+1,
    block_size, Hkv, Dh]`` — and the int8 scale planes ``[.., Hkv, 1]``
    split the same way, so dequantize-after-gather stays local to the
    shard. Ragged head counts fall back to replicated (correct, no win)."""
    if mesh is not None:
        tp = mesh.shape.get("tp", 1)
        if tp > 1 and num_kv_heads % tp == 0:
            return P(None, None, "tp", None)
    return P()


def _c(x, mesh: Optional[Mesh], spec: P):
    """``with_sharding_constraint`` under an explicit NamedSharding (needs
    no ambient mesh context); identity when serving unsharded."""
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def _c_layer(layer_cache, mesh: Optional[Mesh], spec: P):
    """Constrain every buffer of one cache layer (k/v or the int8 quartet
    — all share the head-dim-2 layout) to ``spec``. Pinning BOTH the
    incoming and outgoing cache to the same sharding keeps the update
    alias-compatible, so donation still reuses the pool buffers."""
    if mesh is None:
        return layer_cache
    s = NamedSharding(mesh, spec)
    return {k: jax.lax.with_sharding_constraint(v, s)
            for k, v in layer_cache.items()}


def _batch_pspec(mesh: Optional[Mesh], B: int) -> P:
    """Row-parallel spec for per-slot activations ``[B, S, ...]`` when a
    ``dp`` axis divides the pool size; replicated otherwise."""
    if mesh is not None:
        dp = mesh.shape.get("dp", 1)
        if dp > 1 and B % dp == 0:
            return P("dp")
    return P()


def decode_step(args: llama.LlamaArgs, attend_len: int,
                mesh: Optional[Mesh] = None):
    """Compiled once per (args, attend bucket, mesh) — cached.

    Returns ``step(params, cache, tokens, pos, temps, keys)`` →
    ``(cache, tok, logprob, keys)`` where every array's leading axis is
    the pool's ``num_slots``:

    - ``tokens [B] int32`` — last emitted token per row (0 for masked rows);
    - ``pos [B] int32``    — write position per row (``max_len - 1`` for
      masked rows: the reserved junk target);
    - ``temps [B] f32``    — 0 = greedy, >0 = temperature sample;
    - ``keys [B, 2] u32``  — per-row PRNG keys, split-then-sample per
      token exactly like ``generate_step``.
    """
    key_ = ("decode", args, attend_len, mesh)
    if key_ in _STEP_CACHE:
        return _STEP_CACHE[key_]

    kv_spec = kv_cache_pspec(mesh, args.num_kv_heads)

    @partial(jax.jit, donate_argnums=_donate_cache())
    def decode_step(params, cache, tokens, pos, temps, keys):
        B = tokens.shape[0]
        rows = jnp.arange(B)
        positions = pos[:, None]  # [B, 1]
        with jax.named_scope("embed"):
            x = params["tok_embeddings"]["weight"][tokens][:, None, :]  # [B,1,D]
        x = _c(x, mesh, _batch_pspec(mesh, B))
        rope = lambda t: _rope_rows(t, positions, args)
        k_idx = jnp.arange(attend_len, dtype=jnp.int32)
        # keys at or before each row's own position (junk beyond a row's
        # write head is never attendable — pool invariant)
        mask = (k_idx[None, None, :] <= positions[:, :, None])  # [B,1,L]
        new_cache = []
        for p, layer_cache in zip(params["layers"], cache):
            with jax.named_scope("layer"):
                layer_cache = _c_layer(layer_cache, mesh, kv_spec)
                q, k, v = _qkv(p, x, args, rope)
                with jax.named_scope("kv_gather"):
                    new_layer, ck, cv = _write_kv_rows(layer_cache, k, v, rows, pos)
                new_cache.append(_c_layer(new_layer, mesh, kv_spec))
                with jax.named_scope("attn_core"):
                    out = reference_attention(
                        q, ck[:, :attend_len], cv[:, :attend_len],
                        explicit_mask=mask[:, None, None, :, :])
                x = _attn_out_ffn(p, x, out, args)
        logits = _project_logits(params, x, args)[:, 0, :]  # [B, V]
        # Replicate logits before sampling (vocab-parallel output proj
        # leaves V sharded over tp; the Megatron-style gather point).
        logits = _c(logits, mesh, P())
        with jax.named_scope("sample"):
            lp_all = jax.nn.log_softmax(logits, axis=-1)
            split = jax.vmap(lambda kk: jax.random.split(kk, 2))(keys)  # [B,2,2]
            new_keys, subs = split[:, 0], split[:, 1]
            sampled = jax.vmap(
                lambda kk, lg, t: jax.random.categorical(
                    kk, lg / jnp.maximum(t, 1e-6)))(subs, logits, temps)
            tok = jnp.where(temps > 0.0, sampled.astype(jnp.int32),
                            jnp.argmax(logits, axis=-1).astype(jnp.int32))
            lp = jnp.take_along_axis(lp_all, tok[:, None], axis=-1)[:, 0]
        return new_cache, tok, lp, new_keys

    _STEP_CACHE[key_] = decode_step
    return decode_step


def prefill_step(args: llama.LlamaArgs, chunk: int, attend_len: int,
                 with_logits: bool, mesh: Optional[Mesh] = None):
    """Compiled once per (args, chunk, attend bucket, with_logits, mesh).

    Returns ``step(params, cache, tokens, slot, pos, last_idx)`` →
    ``(cache, last_logits [1, V] | None)``: writes one ``chunk``-sized
    piece of a prompt into ``slot`` starting at ``pos``. Only the FINAL
    chunk needs logits (``with_logits=True``): the full-chunk projection
    is computed and the true-last-token row selected at ``last_idx`` —
    pad junk past the true length is overwritten by decode before it is
    ever attendable."""
    key_ = ("prefill", args, chunk, attend_len, with_logits, mesh)
    if key_ in _STEP_CACHE:
        return _STEP_CACHE[key_]

    kv_spec = kv_cache_pspec(mesh, args.num_kv_heads)

    @partial(jax.jit, donate_argnums=_donate_cache())
    def prefill_step(params, cache, tokens, slot, pos, last_idx):
        with jax.named_scope("embed"):
            x = params["tok_embeddings"]["weight"][tokens][None]  # [1, C, D]
        positions = jnp.arange(chunk, dtype=jnp.int32) + pos  # [C]
        cos, sin = llama.rope_cos_sin(positions, args.head_dim, args.rope_theta,
                                      args.rope_scaling_factor)
        rope = lambda t: llama.apply_rope(t, cos, sin, args.rope_traditional)
        k_idx = jnp.arange(attend_len, dtype=jnp.int32)
        # same positional validity mask as the single-sequence cached
        # decode (llama._cached_attention)
        mask = (k_idx[None, :] <= positions[:, None]) \
            & (k_idx[None, :] < pos + chunk)  # [C, L]
        new_cache = []
        for p, layer_cache in zip(params["layers"], cache):
            with jax.named_scope("layer"):
                layer_cache = _c_layer(layer_cache, mesh, kv_spec)
                q, k, v = _qkv(p, x, args, rope)
                with jax.named_scope("kv_gather"):
                    new_layer, ck, cv = _write_kv_slot(layer_cache, k, v, slot, pos)
                new_cache.append(_c_layer(new_layer, mesh, kv_spec))
                with jax.named_scope("attn_core"):
                    out = reference_attention(q, ck[:, :attend_len],
                                              cv[:, :attend_len], explicit_mask=mask)
                x = _attn_out_ffn(p, x, out, args)
        if not with_logits:
            return new_cache, None
        logits = _project_logits(params, x, args)  # [1, C, V]
        logits = _c(logits, mesh, P())
        last = jax.lax.dynamic_slice_in_dim(logits, last_idx, 1, axis=1)
        return new_cache, last[:, 0, :]  # [1, V]

    _STEP_CACHE[key_] = prefill_step
    return prefill_step


def _paged_write(layer_cache, k, v, blocks, offs):
    """Scatter K/V ``[B, S, H, D]`` into the paged arena at per-position
    block/offset coordinates ``[B, S]``. Real rows own their blocks, so
    their destinations are unique; masked/padded positions all target the
    shared junk block 0 (collisions there are harmless by construction).
    Returns the new layer cache."""
    B, S, H, D = k.shape
    bi = blocks.reshape(-1)
    oi = offs.reshape(-1)
    if "k_q" in layer_cache:
        kq, ks = llama._quantize_kv(k)
        vq, vs = llama._quantize_kv(v)
        return {
            "k_q": layer_cache["k_q"].at[bi, oi].set(kq.reshape(B * S, H, D)),
            "k_s": layer_cache["k_s"].at[bi, oi].set(ks.reshape(B * S, H, 1)),
            "v_q": layer_cache["v_q"].at[bi, oi].set(vq.reshape(B * S, H, D)),
            "v_s": layer_cache["v_s"].at[bi, oi].set(vs.reshape(B * S, H, 1)),
        }
    dt = layer_cache["k"].dtype
    return {
        "k": layer_cache["k"].at[bi, oi].set(k.reshape(B * S, H, D).astype(dt)),
        "v": layer_cache["v"].at[bi, oi].set(v.reshape(B * S, H, D).astype(dt)),
    }


def _paged_gather(layer_cache, tables, nb):
    """Gather each sequence's first ``nb`` blocks as contiguous K/V
    ``[B, nb * block_size, H, D]``. int8 arenas dequantize AFTER the
    gather, so only the attended window is ever expanded to fp — the
    paged analogue of the slotted path's ``[:, :attend_len]`` slice."""
    idx = tables[:, :nb]  # [B, nb]
    if "k_q" in layer_cache:
        keys = layer_cache["k_q"][idx].astype(jnp.float32) \
            * layer_cache["k_s"][idx]
        values = layer_cache["v_q"][idx].astype(jnp.float32) \
            * layer_cache["v_s"][idx]
    else:
        keys = layer_cache["k"][idx]
        values = layer_cache["v"][idx]
    B, _, T, H, D = keys.shape
    return keys.reshape(B, nb * T, H, D), values.reshape(B, nb * T, H, D)


def paged_decode_step(args: llama.LlamaArgs, draft_len: int, attend_len: int,
                      table_width: int, block_size: int,
                      mesh: Optional[Mesh] = None):
    """Compiled once per (args, draft_len, attend bucket, table shape, mesh).

    One dispatch advances every pool row AND verifies its drafts:
    ``step(params, cache, tokens, pos, tables, temps, keys)`` where

    - ``tokens [B, S] int32``, S = draft_len + 1 — per row the last
      emitted (not yet written) token followed by its prompt-lookup
      drafts; masked rows carry zeros.
    - ``pos [B] int32`` — first write position per row (its written
      length); 0 for masked rows, whose table rows map every entry to
      the junk block.
    - ``tables [B, W] int32`` — block tables (W static = table_width).
    - ``temps [B] f32``, ``keys [B, 2] u32`` — as decode_step.

    Returns ``(cache, preds, lp_preds, accept, alts, lp_draft, lp_alt,
    bonus, lp_bonus, new_keys)``: the greedy verify outputs (``preds
    [B, S]`` = argmax at every position, with raw-logits logprobs, the
    same contract as infer/generate._verify_step) plus the point-mass
    sampled-acceptance outputs (the contract of _verify_step_sampled,
    vmapped over rows with per-row temperature). The host picks per row:
    greedy rows use preds, sampled rows use accept/alts/bonus. With
    ``draft_len == 0`` the S axis is 1 and this is plain paged decode.
    """
    key_ = ("paged_decode", args, draft_len, attend_len, table_width,
            block_size, mesh)
    if key_ in _STEP_CACHE:
        return _STEP_CACHE[key_]

    if attend_len % block_size:
        raise ValueError(f"attend_len {attend_len} not a multiple of "
                         f"block_size {block_size}")
    S = draft_len + 1
    nb = attend_len // block_size
    kv_spec = kv_cache_pspec(mesh, args.num_kv_heads)

    def paged_decode_step(params, cache, tokens, pos, tables, temps, keys):
        B = tokens.shape[0]
        positions = pos[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
        # Write coordinates. Positions past the table extent are redirected
        # to the junk block — the engine clamps token budgets so real rows
        # never overflow; this guard keeps an off-by-one from silently
        # corrupting a clamped-index neighbour block.
        safe = positions < table_width * block_size
        pc = jnp.where(safe, positions, 0)
        blocks = jnp.take_along_axis(tables, pc // block_size, axis=1)
        blocks = jnp.where(safe, blocks, 0)
        offs = pc % block_size
        with jax.named_scope("embed"):
            x = params["tok_embeddings"]["weight"][tokens]  # [B, S, D]
        x = _c(x, mesh, _batch_pspec(mesh, B))
        rope = lambda t: _rope_rows(t, positions, args)
        k_idx = jnp.arange(attend_len, dtype=jnp.int32)
        # verify position s attends everything at or before pos + s — its
        # own KV is written first, so drafts see their accepted prefix
        mask = (k_idx[None, None, :] <= positions[:, :, None])  # [B, S, L]
        new_cache = []
        for p, layer_cache in zip(params["layers"], cache):
            with jax.named_scope("layer"):
                layer_cache = _c_layer(layer_cache, mesh, kv_spec)
                q, k, v = _qkv(p, x, args, rope)
                with jax.named_scope("kv_gather"):
                    new_layer = _c_layer(
                        _paged_write(layer_cache, k, v, blocks, offs), mesh, kv_spec)
                    ck, cv = _paged_gather(new_layer, tables, nb)
                new_cache.append(new_layer)
                with jax.named_scope("attn_core"):
                    out = reference_attention(
                        q, ck, cv, explicit_mask=mask[:, None, None, :, :])
                x = _attn_out_ffn(p, x, out, args)
        logits = _project_logits(params, x, args)  # [B, S, V]
        logits = _c(logits, mesh, P())
        with jax.named_scope("sample"):
            return (new_cache,) + _verify_and_sample(logits, tokens, temps, keys)

    def _verify_and_sample(logits, tokens, temps, keys):
        """Greedy verify outputs and the point-mass sampled-acceptance
        outputs for every row (the tail of the returned tuple)."""
        lp_all = jax.nn.log_softmax(logits, axis=-1)
        preds = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, S]
        lp_preds = jnp.take_along_axis(lp_all, preds[..., None],
                                       axis=-1)[..., 0]
        split = jax.vmap(lambda kk: jax.random.split(kk, 2))(keys)
        new_keys, subs = split[:, 0], split[:, 1]

        def row(sub, lg, t, drafts):
            # Point-mass speculative sampling per row (the vmapped analogue
            # of infer/generate._verify_step_sampled, with the row's own
            # temperature). Greedy (t == 0) rows still trace this — their
            # outputs are simply never read host-side.
            probs = jax.nn.softmax(lg / jnp.maximum(t, 1e-6), axis=-1)
            lp = jnp.log(probs + 1e-30)
            ks_ = jax.random.split(sub, S)
            if draft_len:
                accept, alts = jax.vmap(_spec_accept_one)(
                    ks_[:draft_len], probs[:draft_len], drafts)
                gather = lambda rows, i: jnp.take_along_axis(
                    rows, i[:, None], axis=-1)[:, 0]
                lp_draft = gather(lp[:draft_len], drafts)
                lp_alt = gather(lp[:draft_len], alts)
            else:
                accept = jnp.zeros((0,), bool)
                alts = jnp.zeros((0,), jnp.int32)
                lp_draft = jnp.zeros((0,), jnp.float32)
                lp_alt = jnp.zeros((0,), jnp.float32)
            bonus = jax.random.categorical(ks_[draft_len], lp[draft_len])
            return (accept, alts.astype(jnp.int32), lp_draft, lp_alt,
                    bonus.astype(jnp.int32), lp[draft_len, bonus])

        accept, alts, lp_draft, lp_alt, bonus, lp_bonus = jax.vmap(row)(
            subs, logits, temps, tokens[:, 1:])
        return (preds, lp_preds, accept, alts, lp_draft, lp_alt,
                bonus, lp_bonus, new_keys)

    fn = partial(jax.jit, donate_argnums=_donate_cache())(paged_decode_step)
    _STEP_CACHE[key_] = fn
    return fn


def paged_prefill_step(args: llama.LlamaArgs, chunk: int, attend_len: int,
                       table_width: int, block_size: int, with_logits: bool,
                       mesh: Optional[Mesh] = None):
    """Paged analogue of ``prefill_step``: writes one ``chunk`` of one
    request's prompt through its block table.

    Returns ``step(params, cache, tokens, table, pos, last_idx)`` →
    ``(cache, last_logits [1, V] | None)``. ``table [W] int32`` is the
    sequence's block-table row; pad junk past the true prompt length
    lands either in the request's own tail blocks (overwritten by decode
    before it is attendable) or, past the mapped extent, in the shared
    junk block."""
    key_ = ("paged_prefill", args, chunk, attend_len, table_width,
            block_size, with_logits, mesh)
    if key_ in _STEP_CACHE:
        return _STEP_CACHE[key_]

    if attend_len % block_size:
        raise ValueError(f"attend_len {attend_len} not a multiple of "
                         f"block_size {block_size}")
    nb = attend_len // block_size
    kv_spec = kv_cache_pspec(mesh, args.num_kv_heads)

    @partial(jax.jit, donate_argnums=_donate_cache())
    def paged_prefill_step(params, cache, tokens, table, pos, last_idx):
        with jax.named_scope("embed"):
            x = params["tok_embeddings"]["weight"][tokens][None]  # [1, C, D]
        positions = jnp.arange(chunk, dtype=jnp.int32) + pos  # [C]
        cos, sin = llama.rope_cos_sin(positions, args.head_dim, args.rope_theta,
                                      args.rope_scaling_factor)
        rope = lambda t: llama.apply_rope(t, cos, sin, args.rope_traditional)
        safe = positions < table_width * block_size
        pc = jnp.where(safe, positions, 0)
        blocks = jnp.where(safe, table[pc // block_size], 0)[None]  # [1, C]
        offs = (pc % block_size)[None]
        k_idx = jnp.arange(attend_len, dtype=jnp.int32)
        mask = (k_idx[None, :] <= positions[:, None]) \
            & (k_idx[None, :] < pos + chunk)  # [C, L]
        new_cache = []
        for p, layer_cache in zip(params["layers"], cache):
            with jax.named_scope("layer"):
                layer_cache = _c_layer(layer_cache, mesh, kv_spec)
                q, k, v = _qkv(p, x, args, rope)
                with jax.named_scope("kv_gather"):
                    new_layer = _c_layer(
                        _paged_write(layer_cache, k, v, blocks, offs), mesh, kv_spec)
                    ck, cv = _paged_gather(new_layer, table[None], nb)
                new_cache.append(new_layer)
                with jax.named_scope("attn_core"):
                    out = reference_attention(q, ck, cv, explicit_mask=mask)
                x = _attn_out_ffn(p, x, out, args)
        if not with_logits:
            return new_cache, None
        logits = _project_logits(params, x, args)  # [1, C, V]
        logits = _c(logits, mesh, P())
        last = jax.lax.dynamic_slice_in_dim(logits, last_idx, 1, axis=1)
        return new_cache, last[:, 0, :]  # [1, V]

    _STEP_CACHE[key_] = paged_prefill_step
    return paged_prefill_step


def sample_token(logits: jnp.ndarray, temperature: float,
                 key) -> Tuple[int, float, Any]:
    """Sample one token from ``logits [1, V]`` with the request's rng
    chain — the same split-then-sample the locked path applies to the
    prefill logits (generate_step). Returns (token, logprob, new_key)."""
    key, sub = jax.random.split(key)
    if temperature > 0.0:
        tok = jax.random.categorical(sub, logits / max(temperature, 1e-6),
                                     axis=-1)
    else:
        tok = jnp.argmax(logits, axis=-1)
    lp = jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1),
                             tok[:, None], axis=-1)[0, 0]
    return int(tok[0]), float(lp), key
