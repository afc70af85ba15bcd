"""Expert-parallel MoE via ``shard_map`` + explicit ``all_to_all``.

Why this exists: the pure-GSPMD dispatch (``moe.moe_ffn``) ranks token→
expert pairs with a *global* argsort; XLA cannot partition a global sort,
so it replicates the full [tokens, d_model] tensor on every device — at
deepseek-v3 train shapes that is a 28 GiB f32 array per chip.  Real MoE
systems dispatch with *local* ranking + explicit collectives; this module
does exactly that:

  per device: local top-k routing → rank pairs within destination expert
  shard (local sort, ~1e4 elements) → pack into per-destination capacity
  buffers → ``all_to_all`` over the ``model`` (expert-parallel) axis →
  re-bucket received tokens by local expert → batched expert GEMMs →
  reverse ``all_to_all`` → local gate-weighted combine.

Two layouts, chosen by how tokens are sharded:
  * **a2a path** — tokens sharded over the model axis too (training /
    prefill with sequence parallelism): the full exchange above.
  * **replicated path** — tokens replicated across the model axis (decode;
    seq=1 can't shard): every column computes only its own experts'
    contributions and the combine is a ``psum`` — no all_to_all at all.

Everything inside is differentiable (sorts produce integer indices; data
movement is gather/scatter + collectives whose transposes JAX knows), so
the same code serves train and serve.  Expert weights arrive FSDP-sharded
on d_model and are explicitly ``all_gather``-ed (transpose: reduce-scatter
of expert grads — ZeRO semantics, stated rather than implied).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig


def _shard_map(body, mesh, in_specs, out_specs):
    """``jax.shard_map`` without the varying-axes type check."""
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=False,
    )


def _axes_tuple(rule) -> tuple:
    if rule is None:
        return ()
    if isinstance(rule, str):
        return (rule,)
    return tuple(rule)


def _mesh_size(mesh, axes) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def _local_rank_within(dest: jax.Array, n_dest: int):
    """rank[i] = #{j < i : dest[j] == dest[i]} (stable), via local sort."""
    n = dest.shape[0]
    order = jnp.argsort(dest, stable=True)
    sorted_dest = dest[order]
    arange = jnp.arange(n)
    seg_start = jnp.searchsorted(sorted_dest, sorted_dest, side="left")
    rank_sorted = arange - seg_start
    rank = jnp.zeros((n,), jnp.int32).at[order].set(rank_sorted.astype(jnp.int32))
    return rank


def _expert_ffn(buf, w, activation, dtype):
    act = jax.nn.silu if activation == "silu" else jax.nn.gelu
    gate = jnp.einsum("ecd,edf->ecf", buf, w["w_gate"].astype(dtype))
    up = jnp.einsum("ecd,edf->ecf", buf, w["w_up"].astype(dtype))
    h = act(gate) * up
    return jnp.einsum("ecf,efd->ecd", h, w["w_down"].astype(dtype))


def moe_ffn_sharded(params, x: jax.Array, cfg: ModelConfig, rules: dict, mesh):
    """x: [B, S, D] (globally sharded). Returns (out, aux)."""
    m = cfg.moe
    B, S, D = x.shape
    E, K = m.n_experts, m.top_k
    dtype = x.dtype

    batch_axes = _axes_tuple(rules.get("batch"))
    seq_axes = _axes_tuple(rules.get("residual_seq"))
    ep_axes = _axes_tuple(rules.get("experts"))  # ("model",)
    fsdp_axes = _axes_tuple(rules.get("embed_fsdp"))
    assert ep_axes, "expert axis must be sharded for the sharded MoE path"
    ep = ep_axes[0]
    n_ep = mesh.shape[ep]
    E_loc = E // n_ep
    a2a = ep in seq_axes  # tokens sharded over the EP axis → exchange needed

    x_spec = P(batch_axes or None, seq_axes or None, None)
    w_spec = {
        "w_gate": P(ep_axes, fsdp_axes or None, None),
        "w_up": P(ep_axes, fsdp_axes or None, None),
        "w_down": P(ep_axes, None, fsdp_axes or None),
    }
    router_spec = P(None, None)
    def body(xb, router, w):
        # ---- explicit FSDP all-gather of expert weights (ZeRO-3) ----
        if fsdp_axes:
            for ax in fsdp_axes:
                w = {
                    "w_gate": jax.lax.all_gather(w["w_gate"], ax, axis=1, tiled=True),
                    "w_up": jax.lax.all_gather(w["w_up"], ax, axis=1, tiled=True),
                    "w_down": jax.lax.all_gather(w["w_down"], ax, axis=2, tiled=True),
                }
        Bl, Sl, _ = xb.shape
        T_loc = Bl * Sl
        xf = xb.reshape(T_loc, D)

        # ---- local routing (f32) ----
        logits = xf.astype(jnp.float32) @ router.astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_idx = jax.lax.top_k(probs, K)  # [T_loc, K]
        gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

        # load-balance aux: mean over ALL tokens (psum over token axes)
        density = (
            jnp.zeros((E,), jnp.float32).at[expert_idx.reshape(-1)].add(1.0)
            / (T_loc * K)
        )
        mean_prob = probs.mean(0)
        tok_axes = batch_axes + seq_axes
        if tok_axes:
            density = jax.lax.pmean(density, tok_axes)
            mean_prob = jax.lax.pmean(mean_prob, tok_axes)
        aux = m.router_aux_coef * E * jnp.sum(density * mean_prob)

        flat_expert = expert_idx.reshape(-1).astype(jnp.int32)  # [T_loc*K]
        pair_token = jnp.arange(T_loc * K, dtype=jnp.int32) // K
        flat_gate = gate_vals.reshape(-1)

        if a2a:
            # ---------- full exchange over the EP axis ----------
            dest = flat_expert // E_loc  # destination column [T_loc*K]
            cap_send = max(
                int(math.ceil(T_loc * K * m.capacity_factor / n_ep)),
                min(m.min_capacity, T_loc * K),
            )
            rank = _local_rank_within(dest, n_ep)
            keep = rank < cap_send
            slot = jnp.where(keep, dest * cap_send + rank, n_ep * cap_send)

            send = jnp.zeros((n_ep * cap_send, D), dtype)
            send = send.at[slot].set(xf[pair_token], mode="drop")
            send_meta = jnp.full((n_ep * cap_send,), -1, jnp.int32)
            send_meta = send_meta.at[slot].set(
                flat_expert % E_loc, mode="drop"
            )  # local expert id at destination; -1 = hole
            send = send.reshape(n_ep, cap_send, D)
            send_meta = send_meta.reshape(n_ep, cap_send)

            recv = jax.lax.all_to_all(send, ep, 0, 0, tiled=False)
            recv_meta = jax.lax.all_to_all(
                send_meta[..., None], ep, 0, 0, tiled=False
            )[..., 0]
            # recv: [n_ep(source), cap_send, D] on each destination column
            rn = n_ep * cap_send
            r_expert = recv_meta.reshape(rn)
            r_x = recv.reshape(rn, D)
            valid = r_expert >= 0
            r_expert_v = jnp.where(valid, r_expert, E_loc)  # holes → OOB bucket
            cap_e = max(int(math.ceil(rn / E_loc)), 1)
            r_rank = _local_rank_within(r_expert_v, E_loc + 1)
            r_keep = valid & (r_rank < cap_e)
            r_slot = jnp.where(r_keep, r_expert_v * cap_e + r_rank, E_loc * cap_e)
            buf = jnp.zeros((E_loc * cap_e, D), dtype)
            buf = buf.at[r_slot].set(r_x, mode="drop")
            out_buf = _expert_ffn(
                buf.reshape(E_loc, cap_e, D), w, cfg.activation, dtype
            ).reshape(E_loc * cap_e, D)
            # un-bucket → [rn, D], holes zero
            r_out = jnp.where(
                r_keep[:, None],
                out_buf.at[r_slot].get(mode="fill", fill_value=0),
                0,
            )
            back = jax.lax.all_to_all(
                r_out.reshape(n_ep, cap_send, D), ep, 0, 0, tiled=False
            ).reshape(n_ep * cap_send, D)
            # gather back to pairs
            pair_out = jnp.where(
                keep[:, None], back.at[slot].get(mode="fill", fill_value=0), 0
            )
            out = jnp.einsum(
                "tkd,tk->td",
                pair_out.reshape(T_loc, K, D),
                jnp.where(keep, flat_gate, 0.0).reshape(T_loc, K).astype(dtype),
                preferred_element_type=jnp.float32,
            ).astype(dtype)
        else:
            # ---------- replicated-token path (decode) ----------
            col = jax.lax.axis_index(ep)
            mine = (flat_expert // E_loc) == col
            local_e = jnp.where(mine, flat_expert % E_loc, E_loc)
            rank = _local_rank_within(local_e, E_loc + 1)
            cap_e = max(int(math.ceil(T_loc * K * m.capacity_factor / E)), 1)
            cap_e = min(max(cap_e, m.min_capacity), T_loc * K)
            keep = mine & (rank < cap_e)
            slot = jnp.where(keep, local_e * cap_e + rank, E_loc * cap_e)
            buf = jnp.zeros((E_loc * cap_e, D), dtype)
            buf = buf.at[slot].set(xf[pair_token], mode="drop")
            out_buf = _expert_ffn(
                buf.reshape(E_loc, cap_e, D), w, cfg.activation, dtype
            ).reshape(E_loc * cap_e, D)
            pair_out = jnp.where(
                keep[:, None], out_buf.at[slot].get(mode="fill", fill_value=0), 0
            )
            out = jnp.einsum(
                "tkd,tk->td",
                pair_out.reshape(T_loc, K, D),
                jnp.where(keep, flat_gate, 0.0).reshape(T_loc, K).astype(dtype),
                preferred_element_type=jnp.float32,
            ).astype(dtype)
            out = jax.lax.psum(out, ep)
        return out.reshape(Bl, Sl, D), aux

    mapped = _shard_map(
        body,
        mesh=mesh,
        in_specs=(x_spec, router_spec, w_spec),
        out_specs=(x_spec, P()),
    )
    out, aux = mapped(
        x,
        params["router"],
        {k: params["experts"][k] for k in ("w_gate", "w_up", "w_down")},
    )
    if m.n_shared > 0:
        from repro.models.layers import glu_ffn

        out = out + glu_ffn(params["shared"], x, cfg.activation)
    return out, aux
