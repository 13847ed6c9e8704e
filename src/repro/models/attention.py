"""GQA attention: blockwise (flash-style) jnp path + KV-cache decode.

The jnp path mirrors the Pallas kernel (``repro.kernels.flash_attention``)
block for block — online softmax over KV chunks inside a scan over Q chunks —
so activation memory is O(bq·bkv) instead of O(L²).  This is the path the
dry-run lowers (CPU backend can't compile Pallas TPU kernels); on TPU the
``use_pallas`` flag dispatches to the kernel with identical semantics.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .hints import axes_hint, batch_hint, get_model_info

__all__ = ["blockwise_attention", "decode_attention", "KVCache"]

NEG_INF = float("-inf")


class KVCache(NamedTuple):
    """Per-layer-stacked KV cache: (L_layers, B, Hkv, S, hd)."""
    k: jax.Array
    v: jax.Array
    pos: jax.Array        # () int32 — next write position


def _block_body(q, k, v, carry, *, scale, q_start, kv_start, causal, window,
                kv_len):
    """One (q-block, kv-block) online-softmax update.  q (B,H,bq,d)."""
    m_prev, l_prev, acc = carry
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    bq, bkv = q.shape[2], k.shape[2]
    qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
    kpos = kv_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
    mask = kpos < kv_len
    if causal:
        mask = jnp.logical_and(mask, qpos >= kpos)
    if isinstance(window, jax.Array):
        # traced per-layer window (hybrid archs scan over it); <= 0 → full
        mask = jnp.logical_and(mask, jnp.logical_or(window <= 0,
                                                    qpos - kpos < window))
    elif window:
        mask = jnp.logical_and(mask, qpos - kpos < window)
    s = jnp.where(mask[None, None], s, NEG_INF)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.where(mask[None, None], jnp.exp(s - safe_m), 0.0)
    corr = jnp.where(m_prev == NEG_INF, 0.0, jnp.exp(m_prev - safe_m))
    l_new = corr * l_prev + p.sum(axis=-1, keepdims=True)
    acc = jnp.einsum("bhqk,bhkd->bhqd", p, v,
                     preferred_element_type=jnp.float32) + corr * acc
    return m_new, l_new, acc


def blockwise_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, window=0,
                        q_offset: int = 0, bq: int = 512,
                        bkv: int = 1024) -> jax.Array:
    """Dispatch: deterministic shard_map attention on a mesh (q-chunks over
    the model axis, KV gathered at entry — zero collectives inside, and the
    KV gather's AD transpose is a reduce-scatter); GSPMD-auto otherwise.

    Rationale (§Perf it-4/5): letting GSPMD shard these einsums contracted
    over a sharded head_dim emits an all-reduce per (kv-block × q-chunk ×
    layer) in the backward — ~90 GB/layer/device measured on gemma-2b.
    """
    from .hints import get_mesh
    mesh = get_mesh()
    B, H, Lq, d = q.shape
    if mesh is not None and "model" in mesh.axis_names:
        msize = int(mesh.shape["model"])
        baxes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        bsize = 1
        for a in baxes:
            bsize *= int(mesh.shape[a])
        if (msize > 1 and Lq % msize == 0 and (Lq // msize) % 128 == 0
                and B % max(bsize, 1) == 0):
            return _smap_attention(q, k, v, mesh, causal=causal,
                                   window=window, q_offset=q_offset, bkv=bkv)
    return _gspmd_attention(q, k, v, causal=causal, window=window,
                            q_offset=q_offset, bq=bq, bkv=bkv)


def _smap_attention(q, k, v, mesh, *, causal, window, q_offset, bkv):
    """Flash attention under shard_map: (batch → data axes, q-chunks →
    model axis); KV replicated over model inside the body."""
    from jax.sharding import PartitionSpec as P

    B, H, Lq, d = q.shape
    _, Hkv, Lkv, _ = k.shape
    group = H // Hkv
    msize = int(mesh.shape["model"])
    baxes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    bspec = (baxes if len(baxes) > 1 else baxes[0]) if baxes else None
    bq = Lq // msize
    while bq > 512 and bq % 2 == 0:
        bq //= 2
    nq = Lq // bq
    nq_loc = nq // msize
    scale = 1.0 / (d ** 0.5)
    bkv = min(bkv, Lkv)
    pad_kv = (-Lkv) % bkv
    q5 = q.reshape(B, H, nq, bq, d)

    def body(q_loc, k_loc, v_loc, window):
        # q_loc (B_loc, H, nq_loc, bq, d); k_loc/v_loc (B_loc, Hkv, Lkv, d)
        Bl = q_loc.shape[0]
        mi = jax.lax.axis_index("model")
        if pad_kv:
            k_loc = jnp.pad(k_loc, ((0, 0), (0, 0), (0, pad_kv), (0, 0)))
            v_loc = jnp.pad(v_loc, ((0, 0), (0, 0), (0, pad_kv), (0, 0)))
        nkv = k_loc.shape[2] // bkv
        kb = k_loc.reshape(Bl, Hkv, nkv, bkv, d)
        vb = v_loc.reshape(Bl, Hkv, nkv, bkv, d)
        outs = []
        for ci in range(nq_loc):
            qq = q_loc[:, :, ci]                     # (B_loc, H, bq, d)
            q_start = (mi * nq_loc + ci) * bq + q_offset

            @jax.checkpoint
            def kv_step(carry, ki, qq=qq, q_start=q_start):
                kk = kb[:, :, ki][:, :, None].repeat(group, axis=2) \
                    .reshape(Bl, H, bkv, d)
                vv = vb[:, :, ki][:, :, None].repeat(group, axis=2) \
                    .reshape(Bl, H, bkv, d)
                return _block_body(qq, kk, vv, carry, scale=scale,
                                   q_start=q_start, kv_start=ki * bkv,
                                   causal=causal, window=window,
                                   kv_len=Lkv), None

            axes = tuple(mesh.axis_names)
            m0 = jax.lax.pcast(
                jnp.full((Bl, H, bq, 1), NEG_INF, jnp.float32), axes,
                to="varying")
            l0 = jax.lax.pcast(jnp.zeros((Bl, H, bq, 1), jnp.float32), axes,
                               to="varying")
            a0 = jax.lax.pcast(jnp.zeros((Bl, H, bq, d), jnp.float32), axes,
                               to="varying")
            (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0),
                                          jnp.arange(nkv))
            outs.append((acc / jnp.where(l == 0.0, 1.0, l)).astype(q.dtype))
        return jnp.stack(outs, axis=2)               # (B_loc, H, nq_loc, bq, d)

    win_arr = window if isinstance(window, jax.Array) else \
        jnp.asarray(window if window else 0, jnp.int32)
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(bspec, None, "model", None, None),
                  P(bspec, None, None, None),
                  P(bspec, None, None, None), P()),
        out_specs=P(bspec, None, "model", None, None))
    out = fn(q5, k, v, win_arr)
    return out.reshape(B, H, Lq, d)


def _gspmd_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                     causal: bool = True, window=0,
                     q_offset: int = 0, bq: int = 512,
                     bkv: int = 1024) -> jax.Array:
    """q (B, H, Lq, d); k/v (B, Hkv, Lkv, d) → (B, H, Lq, d).

    GQA is folded by reshaping H into (Hkv, group) so no repeat-materialize
    of K/V happens; scores per step are (B, Hkv, group, bq, bkv).
    """
    B, H, Lq, d = q.shape
    _, Hkv, Lkv, _ = k.shape
    group = H // Hkv
    scale = 1.0 / (d ** 0.5)
    bq, bkv = min(bq, Lq), min(bkv, Lkv)
    pad_q = (-Lq) % bq
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    nq = q.shape[2] // bq
    pad_kv = (-Lkv) % bkv
    if pad_kv:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_kv), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_kv), (0, 0)))
    nkv = k.shape[2] // bkv
    qg = q.reshape(B, Hkv, group, nq, bq, d)
    kb = k.reshape(B, Hkv, nkv, bkv, d)
    vb = v.reshape(B, Hkv, nkv, bkv, d)

    # head-parallel when the head count divides the model axis (matches the
    # projections' natural sharding — no resharding copies); otherwise NO
    # model hint: GSPMD factorizes the sharding across (heads × head_dim),
    # which forcing a query-parallel layout was found to fight (measured
    # ~90 GB/layer/device of involuntary-remat copies on gemma — §Perf it-4).
    _, msize = get_model_info()
    attn_model_dim = 1 if (msize > 1 and H % msize == 0) else None

    def q_chunk(qi):
        qq = axes_hint(qg[:, :, :, qi].reshape(B, Hkv * group, bq, d),
                       0, attn_model_dim)
        q_start = qi * bq + q_offset

        # flash semantics under AD: recompute block scores in the backward
        # pass instead of stashing (nq·nkv) score/prob tensors (measured
        # 17 GiB/device without this — EXPERIMENTS.md §Perf).
        @jax.checkpoint
        def kv_step(carry, ki):
            kk = batch_hint(kb[:, :, ki])         # (B, Hkv, bkv, d)
            vv = batch_hint(vb[:, :, ki])
            # broadcast KV across the head group (GQA)
            kk = kk[:, :, None].repeat(group, axis=2).reshape(B, H, bkv, d)
            vv = vv[:, :, None].repeat(group, axis=2).reshape(B, H, bkv, d)
            kk = axes_hint(kk, 0, attn_model_dim if attn_model_dim == 1
                           else None)
            vv = axes_hint(vv, 0, attn_model_dim if attn_model_dim == 1
                           else None)
            carry = _block_body(qq, kk, vv, carry, scale=scale,
                                q_start=q_start, kv_start=ki * bkv,
                                causal=causal, window=window, kv_len=Lkv)
            return tuple(axes_hint(c, 0, attn_model_dim) for c in carry), None

        m0 = axes_hint(jnp.full((B, H, bq, 1), NEG_INF, jnp.float32),
                       0, attn_model_dim)
        l0 = axes_hint(jnp.zeros((B, H, bq, 1), jnp.float32),
                       0, attn_model_dim)
        a0 = axes_hint(jnp.zeros((B, H, bq, d), jnp.float32),
                       0, attn_model_dim)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), jnp.arange(nkv))
        return (acc / jnp.where(l == 0.0, 1.0, l)).astype(q.dtype)

    out = jax.lax.map(q_chunk, jnp.arange(nq))             # (nq, B, H, bq, d)
    out = jnp.moveaxis(out, 0, 2).reshape(B, H, nq * bq, d)
    return out[:, :, :Lq]


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     pos: jax.Array, *, window=0,
                     ring: bool = False) -> jax.Array:
    """Single-token decode.  q (B, H, 1, d); caches (B, Hkv, S, hd).

    Scores are masked to positions < pos (and within the sliding window).
    ``ring=True``: the cache is a ring buffer (window-only archs) — slot s
    holds absolute position ``pos - ((pos - s) mod S)``.
    """
    B, H, _, d = q.shape
    _, Hkv, S, _ = k_cache.shape
    group = H // Hkv
    qg = q.reshape(B, Hkv, group, d)
    s = jnp.einsum("bhgd,bhsd->bhgs", qg.astype(jnp.float32),
                   k_cache.astype(jnp.float32)) / (d ** 0.5)
    kpos = jnp.arange(S)
    if ring:
        abs_pos = pos - jnp.mod(pos - kpos[None, :], S)
        mask = abs_pos >= 0                        # slot ever written
        kdist = pos - abs_pos
    else:
        mask = kpos[None, :] <= pos                # attend incl. current token
        kdist = pos - kpos[None, :]
    if isinstance(window, jax.Array):
        mask = jnp.logical_and(mask, jnp.logical_or(window <= 0,
                                                    kdist < window))
    elif window:
        mask = jnp.logical_and(mask, kdist < window)
    s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgs,bhsd->bhgd", p, v_cache.astype(jnp.float32))
    return out.reshape(B, H, 1, d).astype(q.dtype)
