"""Step builders: train / prefill / decode step functions per architecture,
shared by the real drivers (train.py, serve.py) and the dry-run.

The lowered objects are exactly what runs on hardware: the train step
includes the optimizer update (realistic memory picture), the decode step
includes the paper's Eq. 3 top-k vocabulary recovery (the serving path the
paper times in Fig. 3 right).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig, TrainConfig
from repro.core import bloom as bloom_lib
from repro.models import encdec as encdec_lib
from repro.models import io as io_lib
from repro.models import transformer as tf
from repro.train import trainer as trainer_lib


def loss_fn_for(cfg: ModelConfig, dist=None):
    base = (encdec_lib.encdec_loss_fn if cfg.family == "audio"
            else tf.lm_loss_fn)
    return lambda params, batch: base(params, cfg, batch, dist=dist)


def init_fn_for(cfg: ModelConfig):
    base = encdec_lib.encdec_init if cfg.family == "audio" else tf.lm_init
    return lambda key: base(key, cfg)


def apply_fn_for(cfg: ModelConfig):
    if cfg.family == "audio":
        return encdec_lib.encdec_apply
    return tf.lm_apply


def cast_params_for_compute(params, cfg: ModelConfig):
    """One-shot fp32 -> compute-dtype cast of all matrix params.

    §Perf iteration (qwen3-4b train_4k): without this, every weight is
    read as fp32 and converted at every use site — and remat re-executes
    the converts in the backward pass.  Profiling the 1-layer unrolled HLO
    showed `convert` = 202 GB of 230 GB/device accessed.  Casting once at
    the step boundary (outside the remat scope) leaves exactly one
    convert per param per step.  1-D params (norm scales, biases, A_log)
    stay fp32 — their consumers want f32 math and they are tiny.
    """
    dt = jnp.dtype(cfg.dtype)

    def cast(p):
        if p.ndim >= 2 and jnp.issubdtype(p.dtype, jnp.floating):
            return p.astype(dt)
        return p

    return jax.tree.map(cast, params)


def make_train_step(cfg: ModelConfig, tc: TrainConfig, dist=None):
    """(params, opt_state, batch) -> (params, opt_state, metrics)."""
    loss_fn = loss_fn_for(cfg, dist)
    optimizer = trainer_lib.make_optimizer(tc)
    # pallas path: build the per-spec hash matrix before the first trace
    # (the LM loss never differentiates decode, so no decode bins here)
    trainer_lib.warm_bloom_caches(cfg)

    def step(params, opt_state, batch):
        def scalar_loss(p):
            loss, metrics = loss_fn(cast_params_for_compute(p, cfg), batch)
            return loss, metrics

        (loss, metrics), grads = jax.value_and_grad(
            scalar_loss, has_aux=True)(params)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = jax.tree.map(lambda p, u: (p + u).astype(p.dtype),
                              params, updates)
        out_metrics = {"loss": loss, **metrics}
        return params, opt_state, out_metrics

    return step, optimizer


def make_prefill_step(cfg: ModelConfig, dist=None):
    """(params, batch) -> {last_logits, caches} — inference prefill."""
    apply_fn = apply_fn_for(cfg)

    def step(params, batch):
        # serving params arrive already in compute dtype (bf16 serving
        # checkpoint — no fp32 master at inference); no in-step cast.
        out = apply_fn(params, cfg, batch, mode="prefill", dist=dist)
        return {"last_logits": out["logits"][:, -1],
                "caches": out["caches"]}

    return step


def make_decode_step(cfg: ModelConfig, topk: int = 16, dist=None):
    """(params, token, caches, pos) -> {logits, caches, topk ids/scores}.

    One new token against a seq_len KV cache; includes the Bloom Eq. 3
    vocabulary recovery so serving cost is end-to-end.
    """
    apply_fn = apply_fn_for(cfg)

    # Build the whole-vocab (d, k) hash matrix ONCE at step-construction
    # time: recover_topk then picks up the cached device array at trace
    # time instead of rehashing arange(d) inside every compiled step.
    spec = io_lib.vocab_spec(cfg)
    if spec is not None and cfg.io_impl == "pallas":
        bloom_lib.cached_hash_matrix(spec)

    def step(params, token, caches, pos):
        out = apply_fn(params, cfg, {"tokens": token}, mode="decode",
                       caches=caches, pos=pos, dist=dist)
        scores, ids = io_lib.recover_topk(cfg, out["logits"][:, 0],
                                          topk=topk)
        return {"logits": out["logits"], "caches": out["caches"],
                "topk_scores": scores, "topk_ids": ids}

    return step


def insert_cache_slot(pool, caches_small, slot):
    """Write one request's prefill caches into batch slot `slot` of a
    preallocated cache pool.

    Every cache leaf is stacked (n_layers, B, ...) — attention k/v are
    (n_layers, B, KV, hd, T), their sequence dim at axis 4 SHORTER in the
    prefill caches than in the pool (prompt_len < max_len);
    lax.dynamic_update_slice writes the small block at (0, slot, 0, ...)
    and leaves the tail untouched.  Stale
    tail entries from a previous occupant are never read: the kv validity
    mask only admits positions <= the slot's current offset, and decode
    overwrites each position before first attending to it.  SSM caches
    (conv/ssm state) have no sequence dim and are replaced wholesale.

    `slot` may be a traced int32 scalar, so one jitted insert per prompt
    length serves every slot index.
    """
    def put(buf, small):
        starts = (jnp.int32(0), jnp.asarray(slot, jnp.int32)) + \
            (jnp.int32(0),) * (buf.ndim - 2)
        return jax.lax.dynamic_update_slice(buf, small.astype(buf.dtype),
                                            starts)

    return jax.tree.map(put, pool, caches_small)


def make_sharded_insert(pool_specs, dist, slots_per_shard: int):
    """``insert_cache_slot`` lifted to a shard_map device-to-device cache
    insert (DESIGN.md §8).

    The pool's slot axis is sharded over ``data`` (sharding.
    slot_pool_pspecs); the prefill worker's caches arrive replicated —
    that broadcast IS the device-to-device transfer from the prefill mesh
    slice into the decode pool.  Inside the shard_map every data shard
    computes its local view of the global ``slot`` id and only the owning
    shard's dynamic_update_slice survives the ``where``; all other shards
    return their pool block untouched, so the insert writes exactly one
    shard and never gathers the pool.

    Returns a jitted (pool, caches_small, slot) -> pool callable that
    donates the pool (in-place semantics, same as the engine's single-host
    insert); semantically identical to ``insert_cache_slot`` on the
    unsharded tree (asserted by tests/test_serving_multihost.py).
    """
    from repro.launch.sharding import replicated_specs, shard_map_nocheck
    from jax.sharding import PartitionSpec as P

    data_axes = dist.batch_axes

    def _insert(pool_local, small, slot):
        ax = jax.lax.axis_index(data_axes[0]) if data_axes else 0
        local = jnp.asarray(slot, jnp.int32) - ax * slots_per_shard
        owns = (local >= 0) & (local < slots_per_shard)
        idx = jnp.clip(local, 0, slots_per_shard - 1)

        def put(buf, sm):
            starts = (jnp.int32(0), idx) + (jnp.int32(0),) * (buf.ndim - 2)
            upd = jax.lax.dynamic_update_slice(
                buf, sm.astype(buf.dtype), starts)
            return jnp.where(owns, upd, buf)

        return jax.tree.map(put, pool_local, small)

    def insert(pool, caches_small, slot):
        fn = shard_map_nocheck(
            _insert, dist.mesh,
            in_specs=(pool_specs, replicated_specs(caches_small), P()),
            out_specs=pool_specs)
        return fn(pool, caches_small, jnp.asarray(slot, jnp.int32))

    jitted = jax.jit(insert, donate_argnums=(0,))

    def insert_with_transfer(pool, caches_small, slot):
        # the prefill worker's caches are committed to its mesh slice;
        # broadcasting them onto the decode mesh is the explicit
        # device-to-device transfer (jit refuses mixed commitments)
        from jax.sharding import NamedSharding
        caches_small = jax.device_put(
            caches_small, jax.tree.map(
                lambda leaf: NamedSharding(dist.mesh,
                                           P(*([None] * leaf.ndim))),
                caches_small))
        return jitted(pool, caches_small, slot)

    return insert_with_transfer


def make_compact_pool(pool_specs, dist, slots_per_shard: int):
    """Slot-compaction remap of the sharded cache pool (DESIGN.md §9).

    ``perm`` is the control plane's (n_slots,) int32 gather permutation
    (perm[new_slot] = old_slot), guaranteed host-local by
    ``serving.control.plan_compaction`` — no entry crosses a shard
    boundary, so the remap is a pure within-shard move and NEVER gathers
    the pool across the data axis.  Inside the shard_map each data shard
    slices its own window of the replicated permutation, rebases it to
    local slot ids, and gathers its slot rows through it; the donated
    output is the in-place update of the pool (same layout as the input
    — ``out_specs = pool_specs`` — so the single-compiled-decode-step
    invariant survives compaction).

    Returns a jitted (pool, perm) -> pool callable; one executable serves
    every permutation (perm is a traced operand, never a compile-time
    constant).
    """
    from repro.launch.sharding import shard_map_nocheck
    from jax.sharding import NamedSharding, PartitionSpec as P

    data_axes = dist.batch_axes

    def _compact(pool_local, perm):
        ax = jax.lax.axis_index(data_axes[0]) if data_axes else 0
        local = jax.lax.dynamic_slice(
            perm, (ax * slots_per_shard,), (slots_per_shard,)) \
            - ax * slots_per_shard

        def take(buf):
            return jnp.take(buf, local, axis=1, mode="clip")

        return jax.tree.map(take, pool_local)

    def compact(pool, perm):
        fn = shard_map_nocheck(
            _compact, dist.mesh,
            in_specs=(pool_specs, P(None)), out_specs=pool_specs)
        return fn(pool, jnp.asarray(perm, jnp.int32))

    jitted = jax.jit(compact, donate_argnums=(0,))

    def compact_with_commit(pool, perm):
        # the host-built permutation must be committed replicated before
        # entering the jit (same dance as the sharded insert's broadcast)
        perm = jax.device_put(jnp.asarray(perm, jnp.int32),
                              NamedSharding(dist.mesh, P(None)))
        return jitted(pool, perm)

    return compact_with_commit


def make_slot_decode_step(cfg: ModelConfig, topk: int = 16, dist=None):
    """Continuous-batching decode step over a slot pool.

    (params, token (B, 1), caches, pos (B,), active (B,)) ->
        {caches, topk_scores, topk_ids}

    Unlike make_decode_step's scalar `pos`, every slot decodes at its own
    sequence offset — the per-slot position vector is what keeps ONE
    compiled step serving a pool whose requests were admitted at different
    times (no per-offset recompiles, no bucketing).  `active` masks the
    Eq. 3 vocabulary recovery so retired slots can never leak tokens.
    The returned step's ``kv_write`` names how it writes its KV rows:
    ``"inplace"`` on one device (the stacked pool carried through the
    layer loop, one row written per slot and layer; also each shard of
    the Pallas-IO step below), ``"masked"`` under a GSPMD ``dist`` (each
    layer's cache a scan input and output, rewritten by an
    ``iota == pos`` select).
    """
    apply_fn = apply_fn_for(cfg)

    spec = io_lib.vocab_spec(cfg)
    if spec is not None and cfg.io_impl == "pallas":
        bloom_lib.cached_hash_matrix(spec)

    def step(params, token, caches, pos, active):
        out = apply_fn(params, cfg, {"tokens": token}, mode="decode",
                       caches=caches, pos=pos, dist=dist)
        scores, ids = io_lib.recover_topk(cfg, out["logits"][:, 0],
                                          topk=topk, active=active)
        return {"caches": out["caches"], "topk_scores": scores,
                "topk_ids": ids}

    step.kv_write = "inplace" if dist is None else "masked"
    if dist is None or cfg.io_impl != "pallas":
        return step
    # GSPMD cannot partition a Mosaic kernel, so the Pallas-IO pool step
    # runs per data shard: every slot row is independent and the params
    # are replicated, so each shard decodes its own slots with the
    # single-device step.
    from repro.launch.sharding import shard_map_nocheck, slot_pool_pspecs
    from jax.sharding import PartitionSpec as P
    if dist.n_model != 1:
        raise NotImplementedError(
            "io_impl='pallas' pool decode runs per data shard; a model "
            f"axis of {dist.n_model} would have to split the Mosaic kernels")
    local = make_slot_decode_step(cfg, topk=topk)
    rows = P(dist.batch_axes)

    def sharded_step(params, token, caches, pos, active):
        pool = slot_pool_pspecs(cfg, caches, dist, token.shape[0])
        return shard_map_nocheck(
            local, dist.mesh, in_specs=(P(), rows, pool, rows, rows),
            out_specs={"caches": pool, "topk_scores": rows,
                       "topk_ids": rows},
        )(params, token, caches, pos, active)

    sharded_step.kv_write = local.kv_write
    return sharded_step


def make_retrieval_prefill_step(rcfg):
    """One-shot retrieval prefill (DESIGN.md §11).

    (params, items (B, c_max) int32, -1-padded) -> (B, m) tower logits:
    Bloom-encode the item set (core.bloom.encode, Eq. 1 — on-the-fly
    hashing, no (d, k) matrix at 10M-item catalogs) and run the FF tower
    (models/recommender.ff_apply).  No caches, no first token — the
    payload a ``oneshot`` slot holds is this logits row.
    """
    from repro.models import recommender as rec_lib
    spec = rcfg.spec()

    def step(params, items):
        u = bloom_lib.encode(spec, items)            # (B, m) multi-hot
        return rec_lib.ff_apply(params, u)

    return step


def make_retrieval_decode_step(rcfg):
    """The single recover step of a ``oneshot`` slot pool.

    (pool (n_slots, m) logits, active (n_slots,)) -> (scores, ids) of
    shape (n_slots, topk): log_softmax then the occupancy-aware
    streaming Eq. 3 top-k over the d-item catalog
    (io.recover_topk_spec) — never materializing (n_slots, d) scores.
    ``active`` masks retired slots to scores=-inf / ids=0 and, on the
    pallas path, drives the kernel's row-skipping occupancy grid.
    ``rcfg.table_dtype`` rides through to recover_topk_spec: narrow
    pool-logit storage on the pallas path (with in-kernel rehashing — no
    (d, k) stream), fake-quantized ranking on the xla path (DESIGN.md
    §13).
    """
    spec = rcfg.spec()
    impl = rcfg.resolved_impl
    td = rcfg.table_dtype
    td = None if td == "auto" else td
    if impl == "pallas" and td is None:
        # quantized decode rehashes in-kernel; only legacy streams H
        bloom_lib.cached_hash_matrix(spec)

    def step(pool, active):
        return io_lib.recover_topk_spec(spec, pool, topk=rcfg.topk,
                                        impl=impl, chunk=rcfg.chunk,
                                        active=active, table_dtype=td)

    return step


def init_caches_for(cfg: ModelConfig, shape: ShapeConfig):
    if cfg.family == "audio":
        return functools.partial(encdec_lib.init_encdec_cache, cfg,
                                 shape.global_batch, shape.seq_len, 1500)
    return functools.partial(tf.init_lm_cache, cfg, shape.global_batch,
                             shape.seq_len)
