"""Top-k routed MoE with capacity-factored index dispatch (GShard-style) and
expert parallelism over the `model` mesh axis.

Dispatch is index-based (gather/scatter), NOT dense one-hot einsum: the
(T, E, C) dispatch tensor of the classic GShard formulation is O(T·E·C) and
does not scale to T=65k tokens per device. We compute each (token, slot)'s
position-in-expert with a cumsum over the one-hot assignment — O(T·k·E) int
work — then gather tokens into the (E, C, D) expert batch.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import MoEConfig
from repro.distributed.act_sharding import constrain
from repro.models.layers import init_swiglu, truncnorm_init


def init_moe(rng, d_model: int, cfg: MoEConfig, dtype, n_pad_experts: int = 0
             ) -> dict:
    """Router + stacked expert FFNs (+ shared expert)."""
    E = cfg.n_experts + n_pad_experts
    k_r, k_e, k_s = jax.random.split(rng, 3)
    ke = jax.random.split(k_e, 3)
    s_in, s_out = d_model ** -0.5, cfg.d_expert ** -0.5
    p = {
        "router": truncnorm_init(k_r, (d_model, E), s_in, jnp.float32),
        "w_gate": truncnorm_init(ke[0], (E, d_model, cfg.d_expert), s_in, dtype),
        "w_up": truncnorm_init(ke[1], (E, d_model, cfg.d_expert), s_in, dtype),
        "w_down": truncnorm_init(ke[2], (E, cfg.d_expert, d_model), s_out, dtype),
    }
    if cfg.d_shared:
        p["shared"] = init_swiglu(k_s, d_model, cfg.d_shared, dtype)
    return p


def moe_apply(p: dict, x: jax.Array, cfg: MoEConfig, *, capacity: int | None
              = None, n_pad_experts: int = 0, deterministic_capacity: bool = True):
    """x: (T, D) token-major. Returns (out (T, D), aux_loss scalar).

    Padding experts (to make E divisible by the EP axis) are masked to
    -inf router logits so they never receive tokens.
    """
    T, D = x.shape
    E = cfg.n_experts + n_pad_experts
    k = cfg.top_k
    if capacity is None:
        capacity = max(8, int(cfg.capacity_factor * T * k / cfg.n_experts))
    logits = (x.astype(jnp.float32) @ p["router"])          # (T, E)
    if n_pad_experts:
        pad_mask = jnp.arange(E) >= cfg.n_experts
        logits = jnp.where(pad_mask[None, :], -1e30, logits)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, top_ids = jax.lax.top_k(probs, k)             # (T, k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)
    # ---- aux load-balancing loss (Switch) --------------------------------
    me = probs.mean(axis=0)                                  # (E,)
    ce = jnp.zeros((E,), jnp.float32).at[top_ids.reshape(-1)].add(1.0) / (T * k)
    aux = cfg.router_aux_weight * cfg.n_experts * jnp.sum(me * ce)
    # ---- position-in-expert via cumsum over one-hot ----------------------
    flat_e = top_ids.reshape(-1)                             # (T*k,)
    oh = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)          # (T*k, E)
    pos = jnp.cumsum(oh, axis=0) - oh                        # entries before me
    pos_in_e = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]
    keep = pos_in_e < capacity
    # ---- dispatch: (E, C) slot -> token row ------------------------------
    # init -1; dropped pairs write -1 (no-op under max); empty slots then
    # point at the zero pad row T.
    tok_of_slot = jnp.full((E, capacity), -1, jnp.int32)
    src_rows = jnp.arange(T * k, dtype=jnp.int32) // k
    tok_of_slot = tok_of_slot.at[
        jnp.where(keep, flat_e, E - 1),
        jnp.where(keep, pos_in_e, capacity - 1)].max(
        jnp.where(keep, src_rows, -1))
    tok_of_slot = jnp.where(tok_of_slot < 0, T, tok_of_slot)
    xpad = constrain(
        jnp.concatenate([x, jnp.zeros((1, D), x.dtype)], axis=0),
        "moe_tokens")
    xe = constrain(xpad[tok_of_slot], "moe_expert")          # (E, C, D)
    # ---- expert FFN (einsum over stacked experts; EP-sharded on E) -------
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, p["w_gate"]))
    u = jnp.einsum("ecd,edf->ecf", xe, p["w_up"])
    y = jnp.einsum("ecf,efd->ecd", g * u, p["w_down"])       # (E, C, D)
    # ---- combine: gather slots back per (token, k) -----------------------
    slot_of_tok = jnp.where(keep, flat_e * capacity + pos_in_e, E * capacity)
    ypad = jnp.concatenate(
        [y.reshape(E * capacity, D), jnp.zeros((1, D), y.dtype)], axis=0)
    yk = ypad[slot_of_tok].reshape(T, k, D)
    out = jnp.einsum("tkd,tk->td", yk.astype(jnp.float32),
                     gate_vals).astype(x.dtype)
    if "shared" in p:
        from repro.models.layers import swiglu
        out = out + swiglu(p["shared"], x)
    return out, aux


# ---------------------------------------------------------------------------
# explicit expert parallelism (§Perf "moe-ep")
# ---------------------------------------------------------------------------


def moe_apply_ep(p: dict, x: jax.Array, cfg: MoEConfig, *,
                 n_pad_experts: int = 0):
    """Replicated-dispatch EP via shard_map (REPRO_MOE=ep).

    The GSPMD global-dispatch formulation gathers the full token tensor per
    expert shard (pathological once the `pod` axis exists — see §Perf
    "moe-disp"). Here tokens stay in their dp shard (replicated across
    `model`), each `model` rank dispatches ONLY its own experts' capacity
    buffers locally, and the single collective is one psum of the (T_loc, D)
    combined output per layer. Bitwise-equal to moe_apply when nothing is
    dropped (same routing, same capacity semantics per dp group).

    Falls back to moe_apply when no mesh policy is installed.
    """
    from repro.distributed import act_sharding
    mesh = act_sharding._MESH
    if mesh is None or "model" not in mesh.axis_names:
        return moe_apply(p, x, cfg, n_pad_experts=n_pad_experts)
    from jax.sharding import PartitionSpec as P
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    mways = mesh.shape["model"]
    E = cfg.n_experts + n_pad_experts
    T, D = x.shape
    k = cfg.top_k
    capacity = max(8, int(cfg.capacity_factor * (T // max(
        1, np.prod([mesh.shape[a] for a in dp]))) * k / cfg.n_experts))

    def local(xl, router, wg, wu, wd, shared):
        # xl (T_loc, D); router (D, E); wg/wu (E_loc, D, F); wd (E_loc, F, D)
        rank = jax.lax.axis_index("model")
        E_loc = wg.shape[0]
        Tl = xl.shape[0]
        logits = xl.astype(jnp.float32) @ router
        if n_pad_experts:
            logits = jnp.where(jnp.arange(E) >= cfg.n_experts, -1e30, logits)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, top_ids = jax.lax.top_k(probs, k)
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)
        me = probs.mean(axis=0)
        ce = jnp.zeros((E,), jnp.float32).at[top_ids.reshape(-1)].add(1.0) \
            / (Tl * k)
        aux = cfg.router_aux_weight * cfg.n_experts * jnp.sum(me * ce)
        aux = jax.lax.pmean(aux, dp) if dp else aux
        # position-in-expert over GLOBAL expert ids (identical on all
        # model ranks — xl is replicated across `model`)
        flat_e = top_ids.reshape(-1)
        oh = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
        pos = jnp.cumsum(oh, axis=0) - oh
        pos_in_e = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]
        mine = (flat_e >= rank * E_loc) & (flat_e < (rank + 1) * E_loc)
        keep = (pos_in_e < capacity) & mine
        e_loc = jnp.where(keep, flat_e - rank * E_loc, E_loc - 1)
        tok_of_slot = jnp.full((E_loc, capacity), -1, jnp.int32)
        src_rows = jnp.arange(Tl * k, dtype=jnp.int32) // k
        tok_of_slot = tok_of_slot.at[
            jnp.where(keep, e_loc, E_loc - 1),
            jnp.where(keep, pos_in_e, capacity - 1)].max(
            jnp.where(keep, src_rows, -1))
        tok_of_slot = jnp.where(tok_of_slot < 0, Tl, tok_of_slot)
        xpad = jnp.concatenate([xl, jnp.zeros((1, D), xl.dtype)], axis=0)
        xe = xpad[tok_of_slot]                           # (E_loc, C, D)
        g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, wg))
        u = jnp.einsum("ecd,edf->ecf", xe, wu)
        y = jnp.einsum("ecf,efd->ecd", g * u, wd)        # (E_loc, C, D)
        slot = jnp.where(keep, e_loc * capacity + pos_in_e, E_loc * capacity)
        ypad = jnp.concatenate(
            [y.reshape(E_loc * capacity, D), jnp.zeros((1, D), y.dtype)], 0)
        yk = ypad[slot].reshape(Tl, k, D)
        out = jnp.einsum("tkd,tk->td", yk.astype(jnp.float32), gate_vals)
        out = jax.lax.psum(out.astype(jnp.float32), "model").astype(xl.dtype)
        if shared is not None:
            from repro.models.layers import swiglu
            out = out + swiglu(shared, xl)
        return out, aux

    shared = p.get("shared")
    sh_specs = jax.tree.map(lambda _: P(), shared) if shared is not None \
        else None
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(dp, None), P(), P("model", None, None),
                  P("model", None, None), P("model", None, None), sh_specs),
        out_specs=(P(dp, None), P()),
        check_vma=False)
    return fn(x, p["router"], p["w_gate"], p["w_up"], p["w_down"], shared)
