"""Mixture-of-experts FFN: shared + routed experts (DeepSeekMoE / OLMoE /
Jamba style) with top-k routing and capacity buffers.

Two execution paths sharing one routing core:
  * ``dense``  — all experts local (CPU smoke tests, single device).
  * ``ep``     — expert-parallel: experts sharded over the `model` mesh axis
                 inside shard_map; activations arrive replicated over
                 `model` (Megatron TP convention), each rank computes its
                 local experts' capacity buffers, and one psum over `model`
                 combines.  No token all-to-all is needed because the
                 dispatch is resolved by the buffer gather (DESIGN.md §6).

The capacity-buffer trick keeps peak memory at O(E_local·C·d_model) by
scattering token *indices* (int32) rather than token vectors, then
gathering rows once into the (E_local, C, D) buffer.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.launch.sharding import shard_map_nocheck
from repro.models import layers
from jax.sharding import PartitionSpec as P


def moe_init(key, cfg: ModelConfig):
    mo = cfg.moe
    D, Fe, E = cfg.d_model, mo.d_ff_expert, mo.num_experts
    ks = jax.random.split(key, 5)
    p = {
        "router": layers.truncated_normal_init(ks[0], (D, E), 1.0),
        "w_gate": layers.truncated_normal_init(ks[1], (E * D, Fe), 1.0)
        .reshape(E, D, Fe),
        "w_up": layers.truncated_normal_init(ks[2], (E * D, Fe), 1.0)
        .reshape(E, D, Fe),
        "w_down": layers.truncated_normal_init(ks[3], (E * Fe, D), 1.0)
        .reshape(E, Fe, D),
    }
    if mo.num_shared:
        p["shared"] = layers.swiglu_init(ks[4], D, mo.num_shared * Fe)
    return p


def _capacity(tokens: int, cfg: ModelConfig) -> int:
    mo = cfg.moe
    return max(1, math.ceil(tokens * mo.top_k * mo.capacity_factor
                            / mo.num_experts))


def _route_local(params, x_flat, cfg: ModelConfig, expert_offset,
                 num_local: int, capacity: int):
    """Route x_flat (T, D) through `num_local` experts starting at
    `expert_offset` (a traced scalar under shard_map). Returns (out, aux)."""
    mo = cfg.moe
    T, D = x_flat.shape
    k, E, C = mo.top_k, mo.num_experts, capacity
    dt = x_flat.dtype

    logits = (x_flat @ params["router"].astype(dt)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                   # (T, E)
    gate, sel = jax.lax.top_k(probs, k)                       # (T, k)
    gate = gate / jnp.clip(gate.sum(-1, keepdims=True), 1e-9, None)

    # Load-balance aux loss (Switch/GShard): E * sum_e f_e * P_e.
    f = jnp.zeros((E,), jnp.float32).at[sel.reshape(-1)].add(1.0) / (T * k)
    aux = mo.num_experts * jnp.sum(f * probs.mean(0))

    flat_sel = sel.reshape(-1)                                # (T*k,)
    tok = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    le = flat_sel - expert_offset
    local = (le >= 0) & (le < num_local)
    le_safe = jnp.where(local, le, num_local)
    # position of each routed copy within its expert's queue
    oh = jax.nn.one_hot(le_safe, num_local, dtype=jnp.int32)  # (T*k, E_loc)
    pos = jnp.cumsum(oh, axis=0) - oh                         # exclusive
    pos_sel = (pos * oh).sum(-1)
    keep = local & (pos_sel < C)
    slot = jnp.where(keep, le_safe * C + pos_sel, num_local * C)

    # scatter token indices (not vectors) into the buffer, then gather once
    sentinel = T
    idx_buf = jnp.full((num_local * C + 1,), sentinel, jnp.int32)
    idx_buf = idx_buf.at[slot].set(tok, mode="drop")
    gate_buf = jnp.zeros((num_local * C + 1,), jnp.float32)
    gate_buf = gate_buf.at[slot].set(
        jnp.where(keep, gate.reshape(-1), 0.0), mode="drop")
    idx_buf, gate_buf = idx_buf[:-1], gate_buf[:-1]           # drop overflow

    x_pad = jnp.concatenate([x_flat, jnp.zeros((1, D), dt)], 0)
    x_buf = jnp.take(x_pad, idx_buf, axis=0)                  # (E_loc*C, D)
    x_buf = x_buf.reshape(num_local, C, D)

    g = jnp.einsum("ecd,edf->ecf", x_buf, params["w_gate"].astype(dt))
    u = jnp.einsum("ecd,edf->ecf", x_buf, params["w_up"].astype(dt))
    y_buf = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u,
                       params["w_down"].astype(dt))
    y_buf = (y_buf.reshape(num_local * C, D)
             * gate_buf[:, None].astype(dt))

    out = jnp.zeros((T + 1, D), dt).at[idx_buf].add(y_buf)[:-1]
    return out, aux


def _moe_core(params, x_flat, cfg: ModelConfig, expert_offset, num_local,
              capacity, axis: Optional[str]):
    out, aux = _route_local(params, x_flat, cfg, expert_offset, num_local,
                            capacity)
    if axis is not None:
        out = jax.lax.psum(out, axis)
        aux = jax.lax.pmean(aux, axis)
    if cfg.moe.num_shared:
        out = out + layers.swiglu(params["shared"], x_flat)
    return out, aux


def moe_apply(params, x, cfg: ModelConfig, dist=None):
    """MoE FFN. x: (B, S, D). Returns (y (B,S,D), aux scalar).

    dist: repro.launch.sharding.DistContext or None.  With a context and
    cfg.moe_impl == "ep", experts run expert-parallel over the `model` axis.
    """
    B, S, D = x.shape
    x_flat = x.reshape(B * S, D)
    mo = cfg.moe

    if dist is not None and cfg.moe_impl == "ep":
        mesh = dist.mesh
        model_ax = dist.model_axis
        n_model = mesh.shape[model_ax]
        assert mo.num_experts % n_model == 0, (
            f"experts {mo.num_experts} must divide model axis {n_model}")
        n_local = mo.num_experts // n_model
        # tokens shard over the batch axes when divisible (train/prefill);
        # tiny decode batches stay replicated (B=1 long-context decode).
        batch_axes = dist.batch_spec_axes(B * S) or ()
        n_batch = 1
        for a in batch_axes:
            n_batch *= mesh.shape[a]
        t_loc = max(1, (B * S) // n_batch)
        cap = _capacity(t_loc, cfg)

        def fn(xf, router, wg, wu, wd, shared):
            prm = {"router": router, "w_gate": wg, "w_up": wu, "w_down": wd}
            if shared is not None:
                prm["shared"] = shared
            off = jax.lax.axis_index(model_ax) * n_local
            out, aux = _moe_core(prm, xf, cfg, off, n_local, cap, model_ax)
            for a in batch_axes:
                aux = jax.lax.pmean(aux, a)
            return out, aux

        shared = params.get("shared")
        xs = P(batch_axes if batch_axes else None, None)
        wspec = P(model_ax, None, None)
        sspec = (None if shared is None
                 else jax.tree.map(lambda _: P(None, None), shared))
        out, aux = shard_map_nocheck(
            fn, mesh,
            in_specs=(xs, P(None, None), wspec, wspec, wspec, sspec),
            out_specs=(xs, P()),
        )(x_flat, params["router"], params["w_gate"], params["w_up"],
          params["w_down"], shared)
        return out.reshape(B, S, D), aux

    cap = _capacity(B * S, cfg)
    out, aux = _moe_core(params, x_flat, cfg, 0, mo.num_experts, cap, None)
    return out.reshape(B, S, D), aux


def moe_apply_reference(params, x, cfg: ModelConfig):
    """Oracle: computes every expert densely for every token (O(E) FLOPs).

    Used only in tests to validate the capacity-buffer path (tokens that
    are not dropped must match exactly).
    """
    B, S, D = x.shape
    mo = cfg.moe
    x_flat = x.reshape(B * S, D)
    dt = x_flat.dtype
    logits = (x_flat @ params["router"].astype(dt)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, -1)
    gate, sel = jax.lax.top_k(probs, mo.top_k)
    gate = gate / jnp.clip(gate.sum(-1, keepdims=True), 1e-9, None)
    g = jnp.einsum("td,edf->tef", x_flat, params["w_gate"].astype(dt))
    u = jnp.einsum("td,edf->tef", x_flat, params["w_up"].astype(dt))
    y = jnp.einsum("tef,efd->ted", jax.nn.silu(g) * u,
                   params["w_down"].astype(dt))         # (T, E, D)
    mask = jax.nn.one_hot(sel, mo.num_experts, dtype=jnp.float32)  # (T,k,E)
    w = (mask * gate[..., None]).sum(1)                 # (T, E)
    out = jnp.einsum("ted,te->td", y, w.astype(dt))
    if mo.num_shared:
        out = out + layers.swiglu(params["shared"], x_flat)
    return out.reshape(B, S, D)
