"""Sharding rules: logical-axis mapping for every parameter/activation/
cache in the zoo (DESIGN.md §6).

Conventions:
  * batch axes  = every mesh axis except `model` (i.e. ("pod","data") on the
    multi-pod mesh) — pure data parallelism;
  * `model` axis = Megatron-style tensor parallelism (heads / d_ff / vocab
    m-dim / experts / mamba d_inner+heads);
  * GQA kv heads replicate when num_kv_heads < |model| (MaxText-style kv
    replication) — the weights are small;
  * decode caches shard batch over the batch axes when divisible, else the
    *sequence* dim shards over `data` (sequence-parallel KV for long_500k).

All rules are path-regex -> PartitionSpec, evaluated on the flattened
parameter tree; stacked scan weights (leading n_super dim under blocks/)
automatically get a leading None.
"""
from __future__ import annotations

import dataclasses
import math
import re
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig

def shard_map_nocheck(fn, mesh, in_specs, out_specs):
    """shard_map with the replication check disabled."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def replicated_specs(tree):
    """Fully-replicated PartitionSpec pytree matching ``tree`` — the
    shard_map operand spec for host-broadcast inputs (prefill caches
    entering the sharded pool, compaction permutations)."""
    return jax.tree.map(lambda leaf: P(*([None] * leaf.ndim)), tree)


@dataclasses.dataclass
class DistContext:
    """Carries the mesh + axis conventions into model code."""

    mesh: Mesh
    model_axis: str = "model"

    @property
    def batch_axes(self) -> Tuple[str, ...]:
        return tuple(a for a in self.mesh.axis_names
                     if a != self.model_axis)

    @property
    def n_batch(self) -> int:
        return math.prod(self.mesh.shape[a] for a in self.batch_axes)

    @property
    def n_model(self) -> int:
        return self.mesh.shape[self.model_axis]

    def sharding(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    def constrain(self, x, spec: P):
        return jax.lax.with_sharding_constraint(x, self.sharding(spec))

    def batch_spec_axes(self, b: int):
        """Batch-dim axes if `b` divides across them, else None (replicate)."""
        return self.batch_axes if b % self.n_batch == 0 else None

    def constrain_tokens(self, x):
        """(B, S, D) activations: DP over batch when divisible."""
        ax = self.batch_spec_axes(x.shape[0])
        spec = P(ax, *([None] * (x.ndim - 1)))
        return self.constrain(x, spec)

    def constrain_logits(self, x):
        """(..., m) logits: DP over batch + TP over the vocab/m dim.

        Without this constraint GSPMD replicates the full m-dim logits on
        every device once the loss touches them (measured 16x temp blowup).
        """
        ax = self.batch_spec_axes(x.shape[0])
        v_ax = "model" if x.shape[-1] % self.n_model == 0 else None
        spec = P(ax, *([None] * (x.ndim - 2)), v_ax)
        return self.constrain(x, spec)


# --------------------------------------------------------------------------
# Parameter partition specs
# --------------------------------------------------------------------------

def _param_rules(cfg: ModelConfig, n_model: int):
    """Ordered (regex, builder) table; builder(leaf_ndim) -> PartitionSpec."""
    kv_shardable = cfg.num_kv_heads % n_model == 0
    heads_shardable = cfg.num_heads % n_model == 0
    kv_ax = "model" if kv_shardable else None
    q_ax = "model" if heads_shardable else None
    mamba_ok = (cfg.mamba is not None
                and (cfg.mamba.expand * cfg.d_model
                     // cfg.mamba.head_dim) % n_model == 0)
    m_ax = "model" if mamba_ok else None
    vocab_ok = cfg.m_vocab % n_model == 0
    v_ax = "model" if vocab_ok else None
    moe_ok = cfg.moe is not None and cfg.moe.num_experts % n_model == 0
    e_ax = "model" if moe_ok else None
    ff_ok = cfg.d_ff % n_model == 0
    f_ax = "model" if ff_ok else None
    fe_ok = cfg.moe is not None and cfg.moe.d_ff_expert % n_model == 0
    fe_ax = "model" if fe_ok else None

    return [
        (r"io/embed$", lambda nd: P(v_ax, None)),
        (r"io/head$", lambda nd: P(None, v_ax)),
        (r"frontend_proj", lambda nd: P(*([None] * nd))),
        (r"attn/wq$", lambda nd: P(None, q_ax, None)),
        (r"(attn|self_attn|cross_attn)/w[kv]$",
         lambda nd: P(None, kv_ax, None)),
        (r"(self_attn|cross_attn)/wq$", lambda nd: P(None, q_ax, None)),
        (r"(attn|self_attn|cross_attn)/wo$",
         lambda nd: P(q_ax, None, None)),
        (r"attn/bq$|(self|cross)_attn/bq$", lambda nd: P(q_ax, None)),
        (r"b[kv]$", lambda nd: P(kv_ax, None)),
        (r"(q|k)_norm/scale$", lambda nd: P(None)),
        # FFN: 2D = dense SwiGLU (shard d_ff); 3D = expert-stacked MoE
        (r"ffn/router$", lambda nd: P(None, None)),
        (r"ffn/(w_gate|w_up)$", lambda nd: P(None, f_ax) if nd == 2
         else P(e_ax, None, None)),
        (r"ffn/w_down$", lambda nd: P(f_ax, None) if nd == 2
         else P(e_ax, None, None)),
        (r"shared/w_(gate|up)$", lambda nd: P(None, fe_ax)),
        (r"shared/w_down$", lambda nd: P(fe_ax, None)),
        # mamba
        (r"mamba/(z|x)_proj$", lambda nd: P(None, m_ax)),
        (r"mamba/dt_proj$", lambda nd: P(None, m_ax)),
        (r"mamba/(b|c)_proj$", lambda nd: P(None, None)),
        (r"mamba/conv_x/w$", lambda nd: P(None, m_ax)),
        (r"mamba/conv_x/b$", lambda nd: P(m_ax)),
        (r"mamba/conv_[bc]/", lambda nd: P(*([None] * nd))),
        (r"mamba/(A_log|D|dt_bias)$", lambda nd: P(m_ax)),
        (r"mamba/norm/scale$", lambda nd: P(m_ax)),
        (r"mamba/out_proj$", lambda nd: P(m_ax, None)),
        # rnn / recommender dense layers
        (r"cell/|in_proj|l\d+/", lambda nd: P(*([None] * nd))),
        # norms & everything residual-dim shaped
        (r"norm", lambda nd: P(*([None] * nd))),
        (r"", lambda nd: P(*([None] * nd))),   # fallback: replicate
    ]


def _path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def param_pspecs(cfg: ModelConfig, params, dist: DistContext):
    """Pytree of PartitionSpec matching `params` (shapes or arrays)."""
    rules = _param_rules(cfg, dist.n_model)

    def spec_for(path, leaf):
        s = _path_str(path)
        nd = len(leaf.shape)
        stacked = s.startswith("blocks/") or s.startswith("encoder/") \
            or s.startswith("decoder/")
        eff_nd = nd - 1 if stacked else nd
        for pat, builder in rules:
            if re.search(pat, s):
                spec = builder(eff_nd)
                break
        if stacked:
            spec = P(None, *spec)
        if len(spec) != nd:  # defensive: pad/truncate
            spec = P(*(list(spec) + [None] * nd)[:nd])
        return spec

    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    return jax.tree_util.tree_unflatten(
        treedef, [spec_for(p, l) for p, l in flat])


# --------------------------------------------------------------------------
# Input / cache partition specs
# --------------------------------------------------------------------------

def batch_pspecs(cfg: ModelConfig, batch, dist: DistContext):
    def spec_for(leaf):
        ax = dist.batch_spec_axes(leaf.shape[0])
        return P(ax, *([None] * (len(leaf.shape) - 1)))

    return jax.tree.map(spec_for, batch)


def cache_pspecs(cfg: ModelConfig, caches, dist: DistContext,
                 global_batch: int):
    """Decode-cache specs.

    Decode is KV-cache-read bound, so the cache must never replicate:
      * kv heads shard over `model` when divisible;
      * otherwise (GQA kv < n_model) the cache SEQUENCE dim shards over
        `model` — decode softmax stats cost one tiny all-reduce while the
        dominant cache reads drop n_model-fold (§Perf decode finding);
      * batch shards over the data axes when divisible, else (long_500k
        B=1) the sequence additionally shards over `data`.
    """
    bx = dist.batch_spec_axes(global_batch)
    kv_ax = "model" if cfg.num_kv_heads % dist.n_model == 0 else None
    mamba_ok = (cfg.mamba is not None
                and (cfg.mamba.expand * cfg.d_model
                     // cfg.mamba.head_dim) % dist.n_model == 0)
    m_ax = "model" if mamba_ok else None

    # seq-shard over `model` ONLY when no head dim can shard at all
    # (e.g. whisper's 12 heads on a 16-way axis).  For GQA archs the
    # right answer is a decode mesh with TP == num_kv_heads (measured:
    # TP=8 beats seq-sharding 15x for qwen3/granite/pixtral decode —
    # XLA's pre-Shardy partitioner reshards seq-sharded caches
    # pathologically around the masked update, see b/433785288).
    heads_shardable = cfg.num_heads % dist.n_model == 0
    allow_seq_model = kv_ax is None and not heads_shardable

    def seq_axes_for(seq_len: int):
        axes = []
        if bx is None and seq_len % dist.n_batch == 0:
            axes.extend(dist.batch_axes)
        if allow_seq_model:
            n = dist.n_model
            total = math.prod(dist.mesh.shape[a] for a in axes) * n
            if seq_len % total == 0:
                axes.append(dist.model_axis)
        if not axes:
            return None
        return tuple(axes) if len(axes) > 1 else axes[0]

    def spec_for(path, leaf):
        s = _path_str(path)
        nd = len(leaf.shape)
        # leading dim is the stacked layer dim (n_super)
        if "attn" in s:                   # (L, B, KV, hd, T)
            return P(None, bx, kv_ax, None, seq_axes_for(leaf.shape[4]))
        if "cross" in s:                  # (L, B, T_enc, KV, hd)
            return P(None, bx, seq_axes_for(leaf.shape[2]), kv_ax, None)
        if "ssm" in s:                    # (L, B, H, N, P)
            return P(None, bx, m_ax, None, None)
        if "conv_x" in s:                 # (L, B, d_conv-1, d_in)
            return P(None, bx, None, m_ax)
        if "conv_" in s:                  # gn channels: replicated
            return P(None, bx, None, None)
        return P(*([None] * nd))

    flat, treedef = jax.tree_util.tree_flatten_with_path(caches)
    return jax.tree_util.tree_unflatten(
        treedef, [spec_for(p, l) for p, l in flat])


def slot_pool_pspecs(cfg: ModelConfig, pool, dist: DistContext,
                     n_slots: int):
    """Serving slot-pool specs (DESIGN.md §8).

    Unlike training-time ``cache_pspecs``, the pool's rules are fixed by
    the serving protocol, not by divisibility heuristics:

      * the SLOT axis (axis 1 of every stacked ``(L, n_slots, ...)`` leaf)
        shards over the data axes — each data shard owns the contiguous
        slot range its host admits into, so a cache insert touches exactly
        one shard and decode reads are all-local;
      * the sequence dim NEVER shards: ``insert_cache_slot`` writes a
        slot-local ``[0, S_p)`` block, and a seq-sharded pool would turn
        every insert into a ragged multi-shard write;
      * kv heads shard over ``model`` when divisible (same as
        cache_pspecs) — orthogonal to the slot axis.

    ``n_slots`` must divide across the data axes: the per-host admission
    shards (serving/scheduler.py ShardedScheduler) assume equal contiguous
    slot ranges.
    """
    if n_slots % dist.n_batch:
        raise ValueError(
            f"n_slots={n_slots} must divide the data axes "
            f"(|data|={dist.n_batch}) — per-host admission shards own "
            "equal contiguous slot ranges")
    bx = dist.batch_axes if dist.n_batch > 1 else None
    kv_ax = ("model" if dist.n_model > 1
             and cfg.num_kv_heads % dist.n_model == 0 else None)
    mamba_ok = (cfg.mamba is not None and dist.n_model > 1
                and (cfg.mamba.expand * cfg.d_model
                     // cfg.mamba.head_dim) % dist.n_model == 0)
    m_ax = "model" if mamba_ok else None

    def spec_for(path, leaf):
        s = _path_str(path)
        nd = len(leaf.shape)
        if "attn" in s:                   # (L, B, KV, hd, T)
            return P(None, bx, kv_ax, None, None)
        if "cross" in s:                  # (L, B, T_enc, KV, hd)
            return P(None, bx, None, kv_ax, None)
        if "ssm" in s:                    # (L, B, H, N, P)
            return P(None, bx, m_ax, None, None)
        if "conv_x" in s:                 # (L, B, d_conv-1, d_in)
            return P(None, bx, None, m_ax)
        return P(None, bx, *([None] * (nd - 2)))

    flat, treedef = jax.tree_util.tree_flatten_with_path(pool)
    return jax.tree_util.tree_unflatten(
        treedef, [spec_for(p, l) for p, l in flat])


def opt_state_pspecs(opt_state, params_specs, zero_dist=None,
                     params_shapes=None):
    """Optimizer-state specs: subtrees that mirror the param tree reuse the
    param specs; scalars/counters replicate.

    ZeRO-1 (`zero_dist` = DistContext + `params_shapes` matching
    params_specs): second-moment/momentum tensors additionally shard over
    the *data* axes on their first still-unsharded divisible dim — the
    moments are only touched at update time, so data-replicating them
    wastes HBM (measured 7.6 GiB/device for qwen3-4b at TP=4).  The update
    all-gather this induces is params-bytes once per step (cheap).
    """
    params_treedef = jax.tree_util.tree_structure(params_specs)

    def zero_extend(spec, shape):
        if zero_dist is None:
            return spec
        n_data = zero_dist.n_batch
        axes = zero_dist.batch_axes
        parts = list(spec)
        for i, (dim, ax) in enumerate(zip(shape, parts)):
            if ax is None and dim % n_data == 0 and dim >= n_data:
                parts[i] = axes if len(axes) > 1 else axes[0]
                return P(*parts)
        return spec

    def _is_factored(x):
        return isinstance(x, dict) and set(x) == {"mu", "nu"}

    def factored_specs(spec):
        """Adafactor per-param state: mu mirrors the param; vr/vc drop the
        last / second-to-last dim of the param spec."""
        parts = tuple(spec)
        if len(parts) >= 2:
            nu = {"vr": P(*parts[:-1]),
                  "vc": P(*(parts[:-2] + parts[-1:]))}
        else:
            nu = {"v": spec}
        return {"mu": spec, "nu": nu}

    def map_state(st):
        if jax.tree_util.tree_structure(st) == params_treedef:
            if zero_dist is None or params_shapes is None:
                return params_specs
            return jax.tree.map(
                lambda spec, sds: zero_extend(spec, sds.shape),
                params_specs, params_shapes,
                is_leaf=lambda x: isinstance(x, P))
        if jax.tree_util.tree_structure(
                st, is_leaf=_is_factored) == params_treedef:
            return jax.tree.map(factored_specs, params_specs,
                                is_leaf=lambda x: isinstance(x, P))
        if isinstance(st, dict):
            return {k: map_state(v) for k, v in st.items()}
        if isinstance(st, tuple):
            return tuple(map_state(v) for v in st)
        # leaf (e.g. count scalar)
        return jax.tree.map(lambda l: P(), st)

    return map_state(opt_state)
