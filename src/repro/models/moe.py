"""Mixture-of-Experts FFN: top-k routing, capacity dispatch, shared experts.

Dispatch is scatter-based (Switch-style position-in-expert cumsum), not the
GShard one-hot einsum: the (tokens × E × C) dispatch tensor would be
hundreds of MB per device at deepseek-v3 scale, while the scatter form is
O(tokens·k) index arithmetic + two gathers.  Expert weights are stacked
(E, d, ff) and logically sharded on the ``expert`` axis (EP over the model
mesh axis); XLA SPMD emits the token all-to-all from the resharding between
token-sharded activations and expert-sharded buffers.

Router runs in fp32; aux load-balance loss follows Switch (mean fraction ×
mean probability per expert, scaled by E).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, MoEConfig

from .layers import mk


def init_moe(key, cfg: ModelConfig):
    m: MoEConfig = cfg.moe
    d, E, ff = cfg.d_model, m.n_experts, m.d_ff_expert
    ks = jax.random.split(key, 6)
    p = {
        "router": mk(ks[0], (d, E), ("embed", "expert"), scale=0.02),
        "wi": mk(ks[1], (E, d, ff), ("expert", "embed", "ffn")),
        "wg": mk(ks[2], (E, d, ff), ("expert", "embed", "ffn")),
        "wo": mk(ks[3], (E, ff, d), ("expert", "ffn", "embed")),
    }
    if m.n_shared_experts:
        sff = m.d_ff_shared or m.d_ff_expert * m.n_shared_experts
        p["shared_wi"] = mk(ks[4], (d, sff), ("embed", "ffn"))
        p["shared_wg"] = mk(ks[4], (d, sff), ("embed", "ffn"))
        p["shared_wo"] = mk(ks[5], (sff, d), ("ffn", "embed"))
    return p


def apply_moe_sharded(p, x, cfg: ModelConfig):
    """Explicit expert-parallel MoE under shard_map (EXPERIMENTS.md §Perf).

    Layout: tokens batch-sharded over (pod, data) and *replicated* over
    model; experts sharded over model.  Each (data-shard, model-column)
    device routes its local tokens, computes ONLY its own experts'
    contributions with a purely local scatter/gather (per-device capacity),
    and one psum over model combines per-token outputs — the same collective
    shape as a dense row-parallel MLP.  This replaces XLA's auto-partitioned
    dispatch, which replicates full-microbatch activations around the
    data-dependent scatter (measured 18.7 TB/device/step on
    deepseek-v3-671b x train_4k; see EXPERIMENTS.md).
    """

    from jax.sharding import PartitionSpec as P

    m: MoEConfig = cfg.moe
    from repro.dist.sharding import current_mesh

    mesh = current_mesh()
    n_model = mesh.shape["model"]
    baxes = tuple(a for a in ("pod", "data")
                  if a in mesh.shape and mesh.shape[a] > 1
                  and x.shape[0] % mesh.shape[a] == 0)
    bspec = baxes if len(baxes) > 1 else (baxes[0] if baxes else None)
    E_loc = m.n_experts // n_model

    def local(xb, router, wi, wg, wo, shared):
        B_loc, S, d = xb.shape
        T = B_loc * S
        xf = xb.reshape(T, d)
        col = jax.lax.axis_index("model")

        logits = xf.astype(jnp.float32) @ router.astype(jnp.float32)  # (T, E)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_ids = jax.lax.top_k(probs, m.top_k)
        gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

        frac = jnp.zeros(m.n_experts, jnp.float32).at[
            expert_ids.reshape(-1)].add(1.0) / (T * m.top_k)
        aux_l = m.n_experts * jnp.sum(frac * probs.mean(axis=0)) * m.router_aux_weight
        aux_l = jax.lax.pmean(aux_l, "model")

        # my experts: global ids [col*E_loc, (col+1)*E_loc)
        local_ids = expert_ids - col * E_loc                  # (T, k)
        mine = (local_ids >= 0) & (local_ids < E_loc)
        C = int(max(1, round(T * m.top_k * m.capacity_factor / m.n_experts)))
        flat_ids = jnp.where(mine, local_ids, E_loc).reshape(-1)
        onehot = jax.nn.one_hot(flat_ids, E_loc + 1, dtype=jnp.int32)
        pos = ((jnp.cumsum(onehot, axis=0) - 1) * onehot).sum(-1)
        keep = (pos < C) & mine.reshape(-1)
        slot = jnp.where(keep, flat_ids * C + pos, E_loc * C)

        buf = jnp.zeros((E_loc * C + 1, d), xb.dtype)
        tok_idx = jnp.repeat(jnp.arange(T), m.top_k)
        buf = buf.at[slot].add(xf[tok_idx] * keep[:, None].astype(xb.dtype))
        e_in = buf[: E_loc * C].reshape(E_loc, C, d)

        h = jnp.einsum("ecd,edf->ecf", e_in, wi.astype(xb.dtype))
        g = jnp.einsum("ecd,edf->ecf", e_in, wg.astype(xb.dtype))
        e_out = jnp.einsum("ecf,efd->ecd", h * jax.nn.silu(g), wo.astype(xb.dtype))

        flat_out = jnp.concatenate(
            [e_out.reshape(E_loc * C, d), jnp.zeros((1, d), xb.dtype)], axis=0)
        gathered = flat_out[slot].reshape(T, m.top_k, d)
        w = (gate_vals * keep.reshape(T, m.top_k)).astype(xb.dtype)
        out = jnp.einsum("tkd,tk->td", gathered, w)

        if shared is not None:
            swi, swg, swo = shared  # ffn dim sharded over model: row-parallel
            hs = xf @ swi.astype(xb.dtype)
            gs = xf @ swg.astype(xb.dtype)
            out = out + (hs * jax.nn.silu(gs)) @ swo.astype(xb.dtype)
        out = jax.lax.psum(out, "model")
        return out.reshape(B_loc, S, d), aux_l

    sff = (m.d_ff_shared or m.d_ff_expert * m.n_shared_experts)
    shared_ok = m.n_shared_experts and sff % n_model == 0
    shared_in = (
        (p["shared_wi"], p["shared_wg"], p["shared_wo"]) if shared_ok else None
    )
    shared_specs = (
        (P(None, "model"), P(None, "model"), P("model", None)) if shared_ok else None
    )

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(
            P(bspec, None, None),
            P(None, None),
            P("model", None, None),
            P("model", None, None),
            P("model", None, None),
            shared_specs,
        ),
        out_specs=(P(bspec, None, None), P()),
        check_vma=False,
    )
    out, aux = fn(x, p["router"], p["wi"], p["wg"], p["wo"], shared_in)
    if m.n_shared_experts and not shared_ok:
        xf = x.reshape(-1, x.shape[-1])
        h = xf @ p["shared_wi"].astype(x.dtype)
        g = xf @ p["shared_wg"].astype(x.dtype)
        out = out + ((h * jax.nn.silu(g)) @ p["shared_wo"].astype(x.dtype)).reshape(x.shape)
    return out, aux


def moe_sharding_available(cfg: ModelConfig) -> bool:
    from repro.dist.sharding import current_mesh

    mesh = current_mesh()
    try:
        if mesh is None or "model" not in mesh.shape:
            return False
        n_model = mesh.shape["model"]
        return n_model > 1 and cfg.moe.n_experts % n_model == 0
    except Exception:
        return False


def apply_moe(p, x, cfg: ModelConfig):
    """x: (B, S, d) -> (out, aux_loss)."""
    m: MoEConfig = cfg.moe
    B, S, d = x.shape
    E, k = m.n_experts, m.top_k
    T = B * S
    xf = x.reshape(T, d)

    logits = (xf.astype(jnp.float32) @ p["router"].astype(jnp.float32))  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, k)                      # (T, k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    # Switch aux loss: encourages uniform routing.
    frac = jnp.zeros(E, jnp.float32).at[expert_ids.reshape(-1)].add(1.0) / (T * k)
    aux = E * jnp.sum(frac * probs.mean(axis=0)) * m.router_aux_weight

    # capacity & position-in-expert (token-major priority, Switch-style)
    C = int(max(1, round(T * k / E * m.capacity_factor)))
    flat_ids = expert_ids.reshape(-1)                                    # (T*k,)
    onehot = jax.nn.one_hot(flat_ids, E, dtype=jnp.int32)                # (T*k, E)
    pos = (jnp.cumsum(onehot, axis=0) - 1) * onehot                      # pos per assignment
    pos = pos.sum(axis=-1)                                               # (T*k,)
    keep = pos < C
    slot = jnp.where(keep, flat_ids * C + pos, E * C)                    # drop -> overflow row

    # dispatch: scatter token activations into (E*C + 1, d)
    buf = jnp.zeros((E * C + 1, d), x.dtype)
    tok_idx = jnp.repeat(jnp.arange(T), k)
    buf = buf.at[slot].add(xf[tok_idx] * keep[:, None].astype(x.dtype))
    expert_in = buf[: E * C].reshape(E, C, d)

    # expert FFN (stacked weights, EP-sharded on axis 0)
    h = jnp.einsum("ecd,edf->ecf", expert_in, p["wi"].astype(x.dtype))
    g = jnp.einsum("ecd,edf->ecf", expert_in, p["wg"].astype(x.dtype))
    h = h * jax.nn.silu(g)
    expert_out = jnp.einsum("ecf,efd->ecd", h, p["wo"].astype(x.dtype))

    # combine: gather back per assignment, weight, sum over k
    flat_out = jnp.concatenate(
        [expert_out.reshape(E * C, d), jnp.zeros((1, d), x.dtype)], axis=0
    )
    gathered = flat_out[slot].reshape(T, k, d)
    w = (gate_vals * keep.reshape(T, k)).astype(x.dtype)
    out = jnp.einsum("tkd,tk->td", gathered, w)

    if m.n_shared_experts:
        h = xf @ p["shared_wi"].astype(x.dtype)
        g = xf @ p["shared_wg"].astype(x.dtype)
        out = out + (h * jax.nn.silu(g)) @ p["shared_wo"].astype(x.dtype)
    return out.reshape(B, S, d), aux
