"""Mixture-of-Experts with sort-based capacity dispatch (kimi-k2, qwen2-moe).

Router → top-k experts per token → tokens are *sorted by expert* and scattered
into a fixed ``(E, C)`` slot buffer (capacity ``C = k·T·cf/E``), expert FFNs
run as one batched einsum over ``(E, C, d)``, results gather back with router
weights.  Compared to the Switch-style one-hot dispatch matmul this keeps the
dispatch FLOPs ~0 (pure gather/scatter) so compiled-FLOPs track *active*
parameters — important for an honest MODEL_FLOPS/HLO_FLOPs ratio (§Roofline).

Overflowed tokens (beyond capacity) are dropped — standard practice; the
smoke tests use capacity_factor high enough to avoid drops, and the
reference implementation (`moe_ref`) is drop-free for comparison.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .layers import init_dense

__all__ = ["init_moe_params", "moe_block", "moe_ref", "router_aux_loss"]


def _hint(x, spec):
    """Best-effort sharding constraint: active under a mesh context (the
    dry-run / production path), silently skipped in single-device tests."""
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except Exception:
        return x


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def init_moe_params(key, cfg, dtype) -> dict:
    d, E, f = cfg.d_model, cfg.n_experts, cfg.d_ff_expert
    ks = jax.random.split(key, 5)
    sg = (2.0 / (d + f)) ** 0.5
    p = {
        "router": init_dense(ks[0], d, E, jnp.float32),
        "w_gate": (sg * jax.random.normal(ks[1], (E, d, f))).astype(dtype),
        "w_up": (sg * jax.random.normal(ks[2], (E, d, f))).astype(dtype),
        "w_down": (sg * jax.random.normal(ks[3], (E, f, d))).astype(dtype),
    }
    if cfg.n_shared_experts:
        fs = f * cfg.n_shared_experts
        k1, k2, k3 = jax.random.split(ks[4], 3)
        p["shared"] = {"w_gate": init_dense(k1, d, fs, dtype),
                       "w_up": init_dense(k2, d, fs, dtype),
                       "w_down": init_dense(k3, fs, d, dtype)}
    return p


def _top_k_gates(logits: jax.Array, k: int):
    """Top-k router probabilities, renormalized.  logits (T, E) f32."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, k)          # (T, k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)
    return gate_vals, expert_ids, probs


def _local_dispatch_ffn(p_loc, x_loc, cfg, C: int, e_lo, E_loc: int):
    """Per-shard MoE: local sort-dispatch into an (E_loc, C, d) buffer, local
    expert FFNs, gather-combine.  ``e_lo`` = first local expert id (traced).

    Runs INSIDE shard_map with zero collectives — dispatch is shard-local
    (the production pattern); the caller psums the (partial) token outputs.
    With expert-TP weight shards (f sharded) the down-projection is a partial
    sum, which the same caller psum completes.
    """
    T, d = x_loc.shape
    E, k = cfg.n_experts, cfg.experts_per_token
    logits = x_loc @ p_loc["router"]
    gate_vals, expert_ids, probs = _top_k_gates(logits, k)

    flat_ids = expert_ids.reshape(-1)                        # (T*k,)
    order = jnp.argsort(flat_ids, stable=True)
    sorted_ids = flat_ids[order]
    first = jnp.searchsorted(sorted_ids, sorted_ids, side="left")
    rank = jnp.arange(T * k) - first
    local_e = sorted_ids - e_lo
    is_local = (local_e >= 0) & (local_e < E_loc)
    valid = (rank < C) & is_local
    slot = jnp.clip(local_e, 0, E_loc - 1) * C + jnp.minimum(rank, C - 1)

    token_of = order // k
    src = jnp.where(valid[:, None], x_loc[token_of], 0)
    buf = jnp.zeros((E_loc * C, d), x_loc.dtype).at[slot].add(src)
    buf = buf.reshape(E_loc, C, d)

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p_loc["w_gate"])) * \
        jnp.einsum("ecd,edf->ecf", buf, p_loc["w_up"])
    out_buf = jnp.einsum("ecf,efd->ecd", h, p_loc["w_down"])
    out_buf = out_buf.reshape(E_loc * C, d)

    # combine by direct scatter-add: one weighted gather-scatter instead of
    # inverse-argsort + (T, k, d) einsum — the latter's AD transposes into
    # ~9 full-size all-gathers + an (T·k, d) psum at the shard_map boundary
    # (measured ~250 GB/layer/device wire on kimi — EXPERIMENTS §Perf it-2).
    w_sorted = gate_vals.reshape(-1)[order]                  # (T*k,)
    contrib = jnp.where(valid[:, None], out_buf[slot], 0)
    contrib = contrib * w_sorted[:, None].astype(contrib.dtype)
    out = jnp.zeros((T, d), contrib.dtype).at[token_of].add(contrib)
    aux = router_aux_loss(logits, expert_ids, E, k)
    return out, aux


def moe_block(p: dict, x: jax.Array, cfg):
    """x (T, d) → ((T, d), aux_loss).

    With a registered mesh (production path) this runs as a shard_map:
    tokens stay on their data shard, dispatch/sort is shard-local, experts
    are EP-sharded over the model axis (or ffn-dim-sharded when the expert
    count doesn't divide it), and the combine is ONE psum over the model
    axis.  Without a mesh (unit tests) it falls back to the same local
    routine on the full array.
    """
    from .hints import get_mesh

    T, d = x.shape
    E, k = cfg.n_experts, cfg.experts_per_token
    mesh = get_mesh()

    if mesh is None or "model" not in mesh.axis_names:
        C = _round_up(max(8, int(cfg.capacity_factor * k * T / E)), 8)
        out, aux = _local_dispatch_ffn(p, x, cfg, C, jnp.zeros((), jnp.int32),
                                       E)
        if cfg.n_shared_experts:
            sp = p["shared"]
            out = out + (jax.nn.silu(x @ sp["w_gate"]) *
                         (x @ sp["w_up"])) @ sp["w_down"]
        return out, aux

    baxes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    bspec = baxes if len(baxes) > 1 else baxes[0]
    dp = 1
    for a in baxes:
        dp *= int(mesh.shape[a])
    msize = int(mesh.shape["model"])
    ep = E % msize == 0
    E_loc = E // msize if ep else E
    T_loc = T // dp if T % dp == 0 else T
    tok_spec = bspec if T % dp == 0 else None
    C = _round_up(max(8, int(cfg.capacity_factor * k * T_loc / E)), 8)

    # in_specs MATCH the parameter shardings (runtime/sharding.py) exactly —
    # including the FSDP d-dim shard over "data" — and the FSDP all-gather
    # happens INSIDE the body.  Its AD transpose is then a reduce-scatter
    # (ZeRO gradient flow); a spec mismatch instead makes shard_map reshard
    # the cotangents, which GSPMD resolves by full replication (measured
    # 9×22.5 GB all-gathers per kimi layer — EXPERIMENTS §Perf it-2/3).
    fsdp = cfg.fsdp and "data" in mesh.axis_names and d % mesh.shape["data"] == 0
    f_ax = "data" if fsdp else None
    w_specs = {
        "router": P(f_ax, None),
        "w_gate": P("model", f_ax, None) if ep else P(None, f_ax, "model"),
        "w_up": P("model", f_ax, None) if ep else P(None, f_ax, "model"),
        "w_down": P("model", None, f_ax) if ep else P(None, "model", f_ax),
    }
    has_shared = bool(cfg.n_shared_experts)
    if has_shared:
        w_specs["shared"] = {"w_gate": P(f_ax, "model"),
                             "w_up": P(f_ax, "model"),
                             "w_down": P("model", f_ax)}

    def gather_d(t, axis):
        if not fsdp:
            return t
        return jax.lax.all_gather(t, "data", axis=axis, tiled=True)

    def body(x_loc, p_loc):
        p_full = {
            "router": gather_d(p_loc["router"], 0),
            "w_gate": gather_d(p_loc["w_gate"], 1),
            "w_up": gather_d(p_loc["w_up"], 1),
            "w_down": gather_d(p_loc["w_down"], 2),
        }
        e_lo = (jax.lax.axis_index("model") * E_loc) if ep else \
            jnp.zeros((), jnp.int32)
        # EP: out holds only the local experts' contributions (partial over
        # model); expert-TP: the down-projection is a partial sum over the
        # f shards (partial over model).  Shared-expert f-shards likewise.
        # → ONE psum over the model axis completes all three.
        out, aux = _local_dispatch_ffn(p_full, x_loc, cfg, C, e_lo, E_loc)
        if has_shared:
            sp = p_loc["shared"]
            wg = gather_d(sp["w_gate"], 0)
            wu = gather_d(sp["w_up"], 0)
            wd = gather_d(sp["w_down"], 1)
            sh = jax.nn.silu(x_loc @ wg) * (x_loc @ wu)
            out = out + sh @ wd
        out = jax.lax.psum(out, "model")
        aux = jax.lax.pmean(aux, baxes) if baxes else aux
        return out, aux

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(tok_spec, None), w_specs),
                       out_specs=(P(tok_spec, None), P()))
    return fn(x, p)


def moe_ref(p: dict, x: jax.Array, cfg) -> jax.Array:
    """Drop-free loop-over-experts oracle (tests only)."""
    T, d = x.shape
    E, k = cfg.n_experts, cfg.experts_per_token
    gate_vals, expert_ids, _ = _top_k_gates(x @ p["router"], k)
    out = jnp.zeros_like(x)
    for e in range(E):
        h = jax.nn.silu(x @ p["w_gate"][e]) * (x @ p["w_up"][e])
        y = h @ p["w_down"][e]
        w = jnp.where(expert_ids == e, gate_vals, 0.0).sum(-1)  # (T,)
        out = out + w[:, None].astype(y.dtype) * y
    if cfg.n_shared_experts:
        sp = p["shared"]
        out = out + (jax.nn.silu(x @ sp["w_gate"]) * (x @ sp["w_up"])) @ sp["w_down"]
    return out


def router_aux_loss(logits: jax.Array, expert_ids: jax.Array, E: int,
                    k: int) -> jax.Array:
    """Switch-style load-balance loss: E · Σ_e f_e · P_e."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    P = probs.mean(axis=0)                                   # (E,)
    counts = jnp.zeros((E,), jnp.float32).at[expert_ids.reshape(-1)].add(1.0)
    f = counts / jnp.maximum(counts.sum(), 1.0)
    return E * jnp.sum(f * P)
