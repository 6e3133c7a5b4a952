"""Mixture-of-Experts FFN with top-k routing and capacity-factor dispatch.

Dispatch strategy (TPU-native, static shapes): tokens are processed in
groups of ``cfg.moe_group``; within a group each (token, k) assignment gets a
slot in a per-expert capacity buffer via a one-hot cumulative-sum position
(the GShard/Switch construction), but materialized through scatter/gather on
an (E, C, d) buffer instead of the (T, E, C) one-hot dispatch tensor — the
latter is O(T*E*C) memory and infeasible at 32k sequence x 128 experts.
Tokens overflowing an expert's capacity are dropped (standard capacity-factor
semantics); the load-balance auxiliary loss (Switch, Eq. 4-6) keeps the
router near-uniform so drops stay rare.

Sharding: groups ride the (pod, data) axes, experts ride the model axis.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.layers import constrain, dense_init


def moe_init(key, cfg, stack: int | None = None):
    d, ff, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    ks = jax.random.split(key, 4)
    lead = (stack,) if stack else ()
    pre = "layers," if stack else ""
    params = {
        # router is REPLICATED ("router_experts" -> None): every expert
        # shard must compute identical routing decisions locally (the
        # expert-parallel path relies on it); it is d x E, i.e. tiny.
        "router": dense_init(ks[0], lead + (d, E), jnp.float32),
        "wi": dense_init(ks[1], lead + (E, d, ff), cfg.activation_dtype),
        "wg": dense_init(ks[2], lead + (E, d, ff), cfg.activation_dtype),
        "wo": dense_init(ks[3], lead + (E, ff, d), cfg.activation_dtype, in_axis=-2),
    }
    axes = {
        "router": pre + "embed,router_experts",
        "wi": pre + "experts,embed,expert_mlp",
        "wg": pre + "experts,embed,expert_mlp",
        "wo": pre + "experts,expert_mlp,embed",
    }
    return params, axes


def _capacity(group: int, top_k: int, num_experts: int, factor: float) -> int:
    c = math.ceil(top_k * group / num_experts * factor)
    return max(8, -(-c // 8) * 8) if group >= 64 else max(1, c)


def moe_apply(p, cfg, x):
    """x: (B, S, d) -> (out, (aux_loss, dropped)).

    Two paths:
      * expert-parallel shard_map (production): each model shard runs ONLY
        its E/shards local experts over its (model-replicated) activations
        and the combine is one token-sized psum over the model axis — no
        all-to-all, no buffer replication.  §Perf hillclimb (b): GSPMD's
        lowering of the scatter/gather dispatch all-reduced the full
        (G,E,C,d) capacity buffer per layer (4.0 TB/chip on dbrx-132b
        train_4k); constraining the buffer made it *worse* (39 TB/chip —
        hypothesis refuted, see EXPERIMENTS.md §Perf); the shard_map
        formulation reduces the MoE collective to ~tokens x d per layer,
        the same order as the dense TP all-reduce.
      * GSPMD scatter/gather fallback for CPU tests / meshes that don't
        divide the expert count.
    """
    from repro.models import layers as L

    mesh = L._CURRENT_MESH
    if mesh is not None and "model" in mesh.axis_names:
        model_size = mesh.shape["model"]
        batch_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
        batch_size = 1
        for a in batch_axes:
            batch_size *= mesh.shape[a]
        # EP pays one FSDP expert-weight re-gather per layer per step; that
        # amortizes over many tokens (train/prefill) but regresses decode
        # (measured 22x on dbrx-132b decode_32k: 1 token/seq can't amortize
        # 400MB of expert gathers).  Gate by tokens-per-step, like
        # production MoE servers that switch dispatch regimes.
        enough_tokens = x.shape[0] * x.shape[1] >= 4 * cfg.moe_group
        if (cfg.num_experts % model_size == 0
                and x.shape[0] % max(batch_size, 1) == 0
                and enough_tokens):
            return _moe_apply_expert_parallel(p, cfg, x, mesh, batch_axes)
    return _moe_apply_gspmd(p, cfg, x)


def _moe_apply_expert_parallel(p, cfg, x, mesh, batch_axes):
    """shard_map expert parallelism (see moe_apply docstring)."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    E = cfg.num_experts
    model_size = mesh.shape["model"]
    E_local = E // model_size

    bspec = batch_axes if len(batch_axes) > 1 else (batch_axes[0] if batch_axes else None)

    def local_moe(xl, router, wi, wg, wo):
        # xl: (B_local, S, d); wi/wg/wo: (E_local, ...) local experts
        shard = jax.lax.axis_index("model")
        e_off = shard * E_local
        Bl, S, d = xl.shape
        N = Bl * S
        group = min(cfg.moe_group, N)
        while N % group:
            group //= 2
        G, T = N // group, group
        k = cfg.top_k
        C = _capacity(T, k, E, cfg.capacity_factor)

        xg = xl.reshape(G, T, d)
        logits = jnp.einsum("gtd,de->gte", xg.astype(jnp.float32), router)
        probs = jax.nn.softmax(logits, axis=-1)                       # (G,T,E)
        gate_vals, expert_idx = jax.lax.top_k(probs, k)
        gate_vals = gate_vals / jnp.sum(gate_vals, -1, keepdims=True)

        onehot = jax.nn.one_hot(expert_idx, E, dtype=jnp.int32)       # (G,T,k,E)
        flat = onehot.reshape(G, T * k, E)
        pos = jnp.cumsum(flat, axis=1) - flat
        slot = jnp.sum(pos * flat, axis=-1)                           # (G,T*k)
        e_flat = expert_idx.reshape(G, T * k)
        e_loc = e_flat - e_off
        mine = (e_loc >= 0) & (e_loc < E_local)
        keep = (slot < C) & mine
        e_loc_c = jnp.clip(e_loc, 0, E_local - 1)
        slot_c = jnp.where(keep, slot, C)

        x_rep = jnp.repeat(xg, k, axis=1)
        g_idx = jnp.arange(G)[:, None]
        buf = jnp.zeros((G, E_local, C + 1, d), xg.dtype)
        buf = buf.at[g_idx, e_loc_c, slot_c].add(
            x_rep * keep[..., None].astype(xg.dtype))
        buf = buf[:, :, :C]

        h = jnp.einsum("gecd,edf->gecf", buf, wi) * jax.nn.silu(
            jnp.einsum("gecd,edf->gecf", buf, wg))
        out_buf = jnp.einsum("gecf,efd->gecd", h, wo)
        out_buf = jnp.concatenate(
            [out_buf, jnp.zeros((G, E_local, 1, d), out_buf.dtype)], axis=2)
        tok = out_buf[g_idx, e_loc_c, slot_c]
        tok = tok * (gate_vals.reshape(G, T * k, 1)
                     * keep[..., None]).astype(tok.dtype)
        out = jnp.sum(tok.reshape(G, T, k, d), axis=2)
        # each shard contributed only its experts' outputs:
        out = jax.lax.psum(out, "model")

        frac = jnp.mean(onehot.sum(2).astype(jnp.float32), axis=(0, 1))
        aux = E * jnp.sum(frac * jnp.mean(probs, axis=(0, 1)))
        # dropped fraction counts capacity overflows of LOCAL experts only;
        # psum over model reassembles the global count.
        dropped = jnp.sum((mine & (slot >= C)).astype(jnp.float32))
        dropped = jax.lax.psum(dropped, "model") / (G * T * k)
        if batch_axes:
            aux = jax.lax.pmean(aux, batch_axes)
            dropped = jax.lax.pmean(dropped, batch_axes)
        return out.reshape(Bl, S, d), aux, dropped

    out, aux, dropped = jax.shard_map(
        local_moe, mesh=mesh,
        in_specs=(P(bspec, None, None), P(None, None),
                  P("model", None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=(P(bspec, None, None), P(), P()),
        check_vma=False,
    )(x, p["router"], p["wi"], p["wg"], p["wo"])
    return out, (aux, dropped)


def _moe_apply_gspmd(p, cfg, x):
    """GSPMD scatter/gather dispatch (test / fallback path)."""
    B, S, d = x.shape
    N = B * S
    group = min(cfg.moe_group, N)
    while N % group:
        group //= 2
    G = N // group
    T = group
    E, k = cfg.num_experts, cfg.top_k
    C = _capacity(T, k, E, cfg.capacity_factor)

    xg = constrain(x.reshape(G, T, d), "expert_group,seq,embed")
    logits = jnp.einsum("gtd,de->gte", xg.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)                               # (G,T,E)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)                       # (G,T,k)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    # Slot assignment: position of each (token, k) within its expert queue,
    # computed per group (the paper-analogous "per-cohort" dispatch).
    onehot = jax.nn.one_hot(expert_idx, E, dtype=jnp.int32)               # (G,T,k,E)
    flat = onehot.reshape(G, T * k, E)                                    # token-major
    pos_in_expert = jnp.cumsum(flat, axis=1) - flat                       # (G,T*k,E)
    slot = jnp.sum(pos_in_expert * flat, axis=-1)                         # (G,T*k)
    e_flat = expert_idx.reshape(G, T * k)
    keep = slot < C
    slot_c = jnp.where(keep, slot, C)  # overflow row C is sliced off

    # Scatter tokens into the (G, E, C+1, d) expert buffer.
    x_rep = jnp.repeat(xg, k, axis=1)                                     # (G,T*k,d)
    g_idx = jnp.arange(G)[:, None]
    buf = jnp.zeros((G, E, C + 1, d), xg.dtype)
    buf = buf.at[g_idx, e_flat, slot_c].add(
        x_rep * keep[..., None].astype(xg.dtype))
    buf = constrain(buf[:, :, :C], "expert_group,experts,cap,embed")

    # Expert FFN (SwiGLU), batched over (group, expert).
    h = jnp.einsum("gecd,edf->gecf", buf, p["wi"]) * jax.nn.silu(
        jnp.einsum("gecd,edf->gecf", buf, p["wg"]))
    h = constrain(h, "expert_group,experts,cap,expert_mlp")
    out_buf = jnp.einsum("gecf,efd->gecd", h, p["wo"])                    # (G,E,C,d)
    out_buf = constrain(out_buf, "expert_group,experts,cap,embed")

    # Gather back and combine with gates.
    out_buf = jnp.concatenate(
        [out_buf, jnp.zeros((G, E, 1, d), out_buf.dtype)], axis=2)
    tok_out = out_buf[g_idx, e_flat, slot_c]                              # (G,T*k,d)
    tok_out = tok_out * (gate_vals.reshape(G, T * k, 1)
                         * keep[..., None]).astype(tok_out.dtype)
    out = jnp.sum(tok_out.reshape(G, T, k, d), axis=2)
    out = constrain(out, "expert_group,seq,embed")

    # Switch load-balance loss: fraction of tokens per expert x mean prob.
    frac = jnp.mean(onehot.sum(axis=2).astype(jnp.float32), axis=(0, 1))  # (E,)
    mean_prob = jnp.mean(probs, axis=(0, 1))                              # (E,)
    aux = E * jnp.sum(frac * mean_prob)
    dropped = jnp.mean(1.0 - keep.astype(jnp.float32))
    return out.reshape(B, S, d), (aux, dropped)
