"""Ring attention: exact causal attention over a sequence-sharded mesh axis.

The reference has no sequence/context parallelism at all (SURVEY.md §2.4 —
longest context 4096, sliding-window masks only). This implements
blockwise ring attention (Liu et al.): the sequence dim is sharded over the
``sp`` mesh axis; each device keeps its Q shard and rotates KV shards
around the ring with ``jax.lax.ppermute`` over ICI, accumulating an online
softmax. The KV transfer overlaps with compute under XLA's async
collective scheduling.

Perf-grade paths (causal AND sliding-window — the training cases): each
rotation chunk runs the
**tiled Pallas flash kernels** (ops/flash_attention.py flash_fwd /
flash_bwd_*), so per-chip attention memory is O(block_q x block_kv), not
O(S_local²), and scores ride the MXU. Chunk-level block sparsity comes
free from the ring structure: the diagonal chunk uses the causal kernel,
fully-visible chunks use the full-mask kernel, invisible chunks are
``lax.cond``-skipped entirely. The whole op is one ``jax.custom_vjp``:
forward saves (o, global lse) per flash-attention-2; backward re-runs the
tiled kernels per chunk with the global statistics and rotates dK/dV
accumulators around the ring alongside K/V, landing them back on their
owner after sp hops.

Sliding-window rings additionally stop rotating once the window is
exhausted (_ring_attention_flash_sw) — a 1024-token window on a 32k
sequence over sp=8 does 1-2 KV hops instead of 8.

Arbitrary mask mods fall back to a pure-jnp chunk path (exact, memory
O(S_local²)) — custom masks are an inference/research surface; causal and
sliding-window are the hot ones.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from .masks import NEG_INF, MaskMod


def ring_live_hops(sp: int, seq_local: int, window: Optional[int]) -> int:
    """Number of live KV rotation chunks for a ring of size ``sp``.

    This is the kernel's own static unroll bound: a full causal ring
    visits all ``sp`` chunks, while a sliding window of ``window`` tokens
    only has visible elements at rotation distances ``i*seq_local <
    window + seq_local - 1`` — so a 1024-window over a 32k sequence on
    sp=8 does 2 hops, not 8. Exposed so callers (dryrun, tests) can
    certify the early stop from outside the kernel."""
    if window is None:
        return sp
    return min(sp, (window + seq_local - 2) // seq_local + 1)


def _ring_perm(sp: int):
    return [(j, (j + 1) % sp) for j in range(sp)]


def _merge_chunk(m, num, den, o_c, lse_c):
    """Online-softmax merge of one chunk's (o, lse) into the running
    (max, numerator, denominator). lse_c: [B, Hq, Sl] (invisible chunks
    carry NEG_INF rows => weight exp(NEG_INF - m_new) == 0)."""
    m_new = jnp.maximum(m, lse_c)
    w_old = jnp.exp(m - m_new)
    w_new = jnp.exp(lse_c - m_new)
    num = num * w_old[..., None] + o_c.astype(jnp.float32) * w_new[..., None]
    den = den * w_old + w_new
    return m_new, num, den


def _gqa_reduce(d_h, B, Hkv, G, Sl, D):
    """Per-query-head dK/dV [B, Hq, Sl, D] -> per-kv-head [B, Sl, Hkv, D]."""
    return d_h.reshape(B, Hkv, G, Sl, D).sum(axis=2).transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# Flash-kernel causal path
# ---------------------------------------------------------------------------
def _ring_attention_flash(q, k, v, axis_name: str, scale: float,
                          block_q: Optional[int], block_kv: Optional[int]):
    """Causal ring attention with Pallas-tiled chunk math. Runs INSIDE
    shard_map; q/k/v are local shards [B, S_local, H, D]."""
    from . import masks as M
    from .flash_attention import flash_bwd_dkv, flash_bwd_dq, flash_fwd

    _causal_mask = M.causal()

    B, Sl, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    sp = jax.lax.axis_size(axis_name)
    kw = dict(block_q=block_q, block_kv=block_kv, scale=scale)

    @jax.custom_vjp
    def attn(q, k, v):
        o, _ = _fwd(q, k, v)
        return o

    def _chunk_fwd(qt, kt, vt, src, my):
        """(o_c, lse_c) for one rotation chunk; lse_c rows of invisible
        chunks are NEG_INF so the merge treats them as weight zero."""

        def causal_case(ops):
            # diag chunk: causality is local (q_global-k_global = r-c)
            return flash_fwd(*ops, mask_type="causal", mask_fn=_causal_mask, **kw)

        def offdiag_case(ops):
            def full_case(ops):
                return flash_fwd(*ops, mask_type="full", mask_fn=None, **kw)

            def skip_case(ops):
                qt = ops[0]
                return (jnp.zeros_like(qt),
                        jnp.full((B, Hq, 1, Sl), NEG_INF, jnp.float32))

            return jax.lax.cond(src < my, full_case, skip_case, ops)

        return jax.lax.cond(src == my, causal_case, offdiag_case, (qt, kt, vt))

    def _fwd(q, k, v):
        # axis_index must be taken fresh in BOTH fwd and bwd: a custom_vjp
        # bwd runs in its own trace, so a closed-over traced index leaks.
        my = jax.lax.axis_index(axis_name)
        qt = q.transpose(0, 2, 1, 3)  # [B, Hq, Sl, D]

        def step(carry, i):
            k_cur, v_cur, m, num, den = carry
            src = (my - i) % sp
            o_c, lse_c = _chunk_fwd(qt, k_cur.transpose(0, 2, 1, 3),
                                    v_cur.transpose(0, 2, 1, 3), src, my)
            m, num, den = _merge_chunk(m, num, den, o_c, lse_c[:, :, 0])
            k_nxt = jax.lax.ppermute(k_cur, axis_name, _ring_perm(sp))
            v_nxt = jax.lax.ppermute(v_cur, axis_name, _ring_perm(sp))
            return (k_nxt, v_nxt, m, num, den), None

        m0 = jnp.full((B, Hq, Sl), NEG_INF, jnp.float32)
        num0 = jnp.zeros((B, Hq, Sl, D), jnp.float32)
        den0 = jnp.zeros((B, Hq, Sl), jnp.float32)
        (k_last, v_last, m, num, den), _ = jax.lax.scan(
            step, (k, v, m0, num0, den0), jnp.arange(sp, dtype=jnp.int32))
        den_safe = jnp.maximum(den, 1e-30)
        ot = (num / den_safe[..., None]).astype(q.dtype)   # [B, Hq, Sl, D]
        lse_g = (m + jnp.log(den_safe))[:, :, None, :]     # [B, Hq, 1, Sl]
        o = ot.transpose(0, 2, 1, 3)
        return o, (q, k, v, o, lse_g)

    def _bwd(res, g):
        q, k, v, o, lse_g = res
        my = jax.lax.axis_index(axis_name)
        qt = q.transpose(0, 2, 1, 3)
        gt = g.transpose(0, 2, 1, 3)
        delta = jnp.sum(gt.astype(jnp.float32) *
                        o.transpose(0, 2, 1, 3).astype(jnp.float32),
                        axis=-1)[:, :, None, :]            # [B, Hq, 1, Sl]

        def chunk_bwd(kt, vt, src, my):
            def causal_case(_):
                dq_c = flash_bwd_dq(qt, kt, vt, gt, lse_g, delta,
                                    mask_type="causal", mask_fn=_causal_mask, **kw)
                dk_h, dv_h = flash_bwd_dkv(qt, kt, vt, gt, lse_g, delta,
                                           mask_type="causal", mask_fn=_causal_mask, **kw)
                return dq_c, dk_h, dv_h

            def offdiag(_):
                def full_case(_):
                    dq_c = flash_bwd_dq(qt, kt, vt, gt, lse_g, delta,
                                        mask_type="full", mask_fn=None, **kw)
                    dk_h, dv_h = flash_bwd_dkv(qt, kt, vt, gt, lse_g, delta,
                                               mask_type="full", mask_fn=None, **kw)
                    return dq_c, dk_h, dv_h

                def skip(_):
                    return (jnp.zeros_like(qt),
                            jnp.zeros((B, Hq, Sl, D), kt.dtype),
                            jnp.zeros((B, Hq, Sl, D), vt.dtype))

                return jax.lax.cond(src < my, full_case, skip, None)

            return jax.lax.cond(src == my, causal_case, offdiag, None)

        def step(carry, i):
            k_cur, v_cur, dk_cur, dv_cur, dq = carry
            src = (my - i) % sp
            dq_c, dk_h, dv_h = chunk_bwd(k_cur.transpose(0, 2, 1, 3),
                                         v_cur.transpose(0, 2, 1, 3), src, my)
            dq = dq + dq_c.astype(jnp.float32)
            # per-query-head -> per-kv-head, back to [B, Sl, Hkv, D]
            dk_cur = dk_cur + _gqa_reduce(dk_h, B, Hkv, G, Sl, D).astype(jnp.float32)
            dv_cur = dv_cur + _gqa_reduce(dv_h, B, Hkv, G, Sl, D).astype(jnp.float32)
            # dK/dV accumulators ride the ring WITH their K/V chunk: after
            # sp hops they are back on the owning device.
            perm = _ring_perm(sp)
            k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
            v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
            dk_nxt = jax.lax.ppermute(dk_cur, axis_name, perm)
            dv_nxt = jax.lax.ppermute(dv_cur, axis_name, perm)
            return (k_nxt, v_nxt, dk_nxt, dv_nxt, dq), None

        dq0 = jnp.zeros((B, Hq, Sl, D), jnp.float32)
        dkv0 = jnp.zeros((B, Sl, Hkv, D), jnp.float32)
        (_, _, dk, dv, dqt), _ = jax.lax.scan(
            step, (k, v, dkv0, dkv0, dq0), jnp.arange(sp, dtype=jnp.int32))
        dq = dqt.transpose(0, 2, 1, 3).astype(q.dtype)
        return dq, dk.astype(k.dtype), dv.astype(v.dtype)

    attn.defvjp(_fwd, _bwd)
    return attn(q, k, v)


# ---------------------------------------------------------------------------
# Flash-kernel sliding-window path
# ---------------------------------------------------------------------------
def _ring_attention_flash_sw(q, k, v, axis_name: str, scale: float,
                             block_q: Optional[int], block_kv: Optional[int], window: int):
    """Sliding-window ring attention with Pallas-tiled chunk math.

    The ring loop is **statically unrolled over the rotation distance** i,
    which makes each chunk's band offset ``window - i*S_local`` a Python
    constant — so every chunk runs a tiled kernel with exact banded block
    sparsity instead of the O(S_local²) jnp fallback:

    - i == 0 (diagonal): canonical sliding_window kernel;
    - 0 < i, chunk fully inside the window: full (unmasked) kernel;
    - band edge: ``band`` kernel, valid iff row-col < window - i*S_local
      (the inter-chunk offset already guarantees causality);
    - i*S_local >= window + S_local - 1: statically skipped — AND the ring
      stops rotating, so a 1024-window over a 32k sequence on sp=8 does 1-2
      hops, not 8.

    Runtime gating on wraparound (src > my ⇒ future tokens) via lax.cond.
    """
    from . import masks as M
    from .flash_attention import flash_bwd_dkv, flash_bwd_dq, flash_fwd

    B, Sl, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    sp = jax.lax.axis_size(axis_name)
    kw = dict(block_q=block_q, block_kv=block_kv, scale=scale)
    # distances with any visible element: i*Sl < window + Sl - 1
    n_live = ring_live_hops(sp, Sl, window)
    perm = _ring_perm(sp)

    def _chunk_kw(i: int) -> dict:
        shift = i * Sl
        if i == 0:
            return dict(mask_type="sliding_window", window=window,
                        mask_fn=M.sliding_window(window), canonical_mask=True)
        if shift + Sl - 1 < window:
            return dict(mask_type="full", mask_fn=None)
        t = window - shift  # may be <= 0: band clipped to the top-right corner
        return dict(mask_type="band", window=t, mask_fn=M.band(t),
                    canonical_mask=True)

    @jax.custom_vjp
    def attn(q, k, v):
        o, _ = _fwd(q, k, v)
        return o

    def _fwd(q, k, v):
        my = jax.lax.axis_index(axis_name)
        qt = q.transpose(0, 2, 1, 3)
        m = jnp.full((B, Hq, Sl), NEG_INF, jnp.float32)
        num = jnp.zeros((B, Hq, Sl, D), jnp.float32)
        den = jnp.zeros((B, Hq, Sl), jnp.float32)
        k_cur, v_cur = k, v
        for i in range(n_live):
            ckw = _chunk_kw(i)

            def live_case(ops, ckw=ckw):
                return flash_fwd(*ops, **ckw, **kw)

            def skip_case(ops):
                return (jnp.zeros_like(qt),
                        jnp.full((B, Hq, 1, Sl), NEG_INF, jnp.float32))

            o_c, lse_c = jax.lax.cond(
                my >= i, live_case, skip_case,
                (qt, k_cur.transpose(0, 2, 1, 3), v_cur.transpose(0, 2, 1, 3)))
            m, num, den = _merge_chunk(m, num, den, o_c, lse_c[:, :, 0])
            if i + 1 < n_live:  # no transfer for chunks that are never used
                k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
                v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
        den_safe = jnp.maximum(den, 1e-30)
        ot = (num / den_safe[..., None]).astype(q.dtype)
        lse_g = (m + jnp.log(den_safe))[:, :, None, :]
        return ot.transpose(0, 2, 1, 3), (q, k, v, ot.transpose(0, 2, 1, 3), lse_g)

    def _bwd(res, g):
        q, k, v, o, lse_g = res
        my = jax.lax.axis_index(axis_name)
        qt = q.transpose(0, 2, 1, 3)
        gt = g.transpose(0, 2, 1, 3)
        delta = jnp.sum(gt.astype(jnp.float32) *
                        o.transpose(0, 2, 1, 3).astype(jnp.float32),
                        axis=-1)[:, :, None, :]

        dq = jnp.zeros((B, Hq, Sl, D), jnp.float32)
        dk_cur = jnp.zeros((B, Sl, Hkv, D), jnp.float32)
        dv_cur = jnp.zeros((B, Sl, Hkv, D), jnp.float32)
        k_cur, v_cur = k, v
        for i in range(n_live):
            ckw = _chunk_kw(i)

            def live_case(ops, ckw=ckw):
                kt, vt = ops
                dq_c = flash_bwd_dq(qt, kt, vt, gt, lse_g, delta, **ckw, **kw)
                dk_h, dv_h = flash_bwd_dkv(qt, kt, vt, gt, lse_g, delta, **ckw, **kw)
                return dq_c, dk_h, dv_h

            def skip_case(ops):
                return (jnp.zeros_like(qt),
                        jnp.zeros((B, Hq, Sl, D), k.dtype),
                        jnp.zeros((B, Hq, Sl, D), v.dtype))

            dq_c, dk_h, dv_h = jax.lax.cond(
                my >= i, live_case, skip_case,
                (k_cur.transpose(0, 2, 1, 3), v_cur.transpose(0, 2, 1, 3)))
            dq = dq + dq_c.astype(jnp.float32)
            dk_cur = dk_cur + _gqa_reduce(dk_h, B, Hkv, G, Sl, D).astype(jnp.float32)
            dv_cur = dv_cur + _gqa_reduce(dv_h, B, Hkv, G, Sl, D).astype(jnp.float32)
            if i + 1 < n_live:
                k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
                v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
                dk_cur = jax.lax.ppermute(dk_cur, axis_name, perm)
                dv_cur = jax.lax.ppermute(dv_cur, axis_name, perm)
        # accumulators sit (n_live-1) hops ahead of their owner; one
        # corrective ppermute lands them home (identity when n_live == sp).
        home = (n_live - 1) % sp
        if home:
            back = [(j, (j + sp - home) % sp) for j in range(sp)]
            dk_cur = jax.lax.ppermute(dk_cur, axis_name, back)
            dv_cur = jax.lax.ppermute(dv_cur, axis_name, back)
        return (dq.transpose(0, 2, 1, 3).astype(q.dtype),
                dk_cur.astype(k.dtype), dv_cur.astype(v.dtype))

    attn.defvjp(_fwd, _bwd)
    return attn(q, k, v)


# ---------------------------------------------------------------------------
# Generic-mask jnp path (exact, O(S_local²) chunk scores)
# ---------------------------------------------------------------------------
def _chunk_scores(q, k, scale):
    """q [B, Sq, Hkv, G, D] x k [B, Skv, Hkv, D] -> [B, Hkv, G, Sq, Skv] f32."""
    return jnp.einsum("bqhgd,bkhd->bhgqk", q, k).astype(jnp.float32) * scale


def _ring_attention_jnp(q, k, v, axis_name, mask_mod, scale):
    B, Sl, Hq, D = q.shape
    _, _, Hkv, _ = k.shape
    G = Hq // Hkv
    sp = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)

    qg = q.reshape(B, Sl, Hkv, G, D)
    q_idx = my * Sl + jnp.arange(Sl, dtype=jnp.int32)

    def accumulate(m, l, acc, k_cur, v_cur, i):
        # chunk i holds the shard originally owned by device (my - i) % sp
        src = (my - i) % sp
        kv_idx = src * Sl + jnp.arange(Sl, dtype=jnp.int32)
        s = _chunk_scores(qg, k_cur, scale)  # [B, Hkv, G, Sl, Sl]
        mask = mask_mod(q_idx[:, None], kv_idx[None, :])
        s = jnp.where(mask[None, None, None], s, NEG_INF)

        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhgqk,bkhd->bhgqd", p, v_cur.astype(jnp.float32))
        return m_new, l_new, acc_new

    def step(carry, i):
        k_cur, v_cur, m, l, acc = carry
        m, l, acc = accumulate(m, l, acc, k_cur, v_cur, i)
        k_nxt = jax.lax.ppermute(k_cur, axis_name, _ring_perm(sp))
        v_nxt = jax.lax.ppermute(v_cur, axis_name, _ring_perm(sp))
        return (k_nxt, v_nxt, m, l, acc), None

    m0 = jnp.full((B, Hkv, G, Sl), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G, Sl), jnp.float32)
    acc0 = jnp.zeros((B, Hkv, G, Sl, D), jnp.float32)
    # Only sp-1 rotations are needed: the last chunk's accumulation happens
    # outside the scan so its (otherwise discarded) ppermute is never issued.
    (k_last, v_last, m, l, acc), _ = jax.lax.scan(
        step, (k, v, m0, l0, acc0), jnp.arange(sp - 1, dtype=jnp.int32))
    m, l, acc = accumulate(m, l, acc, k_last, v_last, sp - 1)

    l_safe = jnp.maximum(l, 1e-30)
    out = acc / l_safe[..., None]  # [B, Hkv, G, Sl, D]
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, Sl, Hq, D)
    return out.astype(q.dtype)


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis_name: str = "sp",
    mask_mod: Optional[MaskMod] = None,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_kv: Optional[int] = None,
) -> jnp.ndarray:
    """Runs INSIDE shard_map. q/k/v: local shards [B, S_local, H, D] with the
    global sequence laid out contiguously across the axis. ``mask_mod``
    takes GLOBAL (q_idx, kv_idx). Default mask is causal (flash-kernel
    path); non-causal mods use the exact jnp chunk path. Blocks left ``None``
    are each chunk kernel's own by ``flash_plan``, as in ``flash_attention``."""
    from .flash_attention import fit_block, flash_plan

    Sl, D = q.shape[1], q.shape[-1]
    scale = (D ** -0.5) if scale is None else scale
    plan = getattr(mask_mod, "_plan", None) if mask_mod is not None else ("causal", 0, 0)
    bq = block_q and fit_block(block_q, Sl)
    bkv = block_kv and fit_block(block_kv, Sl)
    if plan is not None and flash_plan(Sl, Sl, D, k.dtype, bq, bkv).path != "reference":
        if plan[0] == "causal":
            return _ring_attention_flash(q, k, v, axis_name, scale, bq, bkv)
        if plan[0] == "sliding_window":
            return _ring_attention_flash_sw(q, k, v, axis_name, scale, bq, bkv,
                                            window=plan[1])
    from . import masks as M

    return _ring_attention_jnp(q, k, v, axis_name, mask_mod or M.causal(), scale)


def make_ring_attention(mesh, axis_name: str = "sp", mask_mod: Optional[MaskMod] = None,
                        batch_axes=("dp", "fsdp"), block_q: Optional[int] = None,
                        block_kv: Optional[int] = None):
    """shard_map wrapper: [B, S_global, H, D] (sharded batch over dp/fsdp,
    sequence over sp) -> same. Heads/D replicated across sp."""
    from jax.sharding import PartitionSpec as P

    data = tuple(a for a in batch_axes if a in mesh.axis_names and mesh.shape[a] > 1)
    data_spec = data if data else None
    spec = P(data_spec, axis_name, None, None)

    fn = partial(ring_attention, axis_name=axis_name, mask_mod=mask_mod,
                 block_q=block_q, block_kv=block_kv)
    return jax.shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False,
    )
