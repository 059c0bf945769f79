"""A Bloom-embedded LM as the program runs it.

Serving: ``LMSlotProgram`` (B=1 exact-length prefill with the Eq. 3
first token, one pool decode step with Eq. 3 top-k recovery over the
whole vocabulary, KV-cache slot inserts) in the program's own
``PrefillPool``, at the program's default Bloom IO implementation.

The comparison that decides ``correct`` for serving: a sample of the
finished requests (drawn from the seed, always holding the longest) is
run through the plain float32 reference (``bench/reference/lm.py``)
over prompt and served tokens; ``token_gap`` is the widest gap by which
a served token's Eq. 3 score lies below the reference's best score at
its position.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import traffic_gen, weights
from bench.reference import lm as ref

# a layer leaf (stacked over layers on axis 0) -> the axes of its fan-in
# for a matrix, else its kind (weights._leaf)
_LEAVES = {
    "attn/wq": (1,), "attn/wk": (1,), "attn/wv": (1,), "attn/wo": (1, 2),
    "ffn/w_gate": (1,), "ffn/w_up": (1,), "ffn/w_down": (1,),
    "attn/bq": "bias", "attn/bk": "bias", "attn/bv": "bias",
    "norm1/scale": "scale", "norm2/scale": "scale",
}


def program_config(cfg: dict):
    """The program's ModelConfig, checked against the configuration."""
    from repro import configs
    get = (configs.get_smoke_config if cfg.get("program_preset") == "smoke"
           else configs.get_config)
    mc = get(cfg["program_arch"])
    want = dict(num_layers=cfg["num_hidden_layers"],
                d_model=cfg["hidden_size"],
                num_heads=cfg["num_attention_heads"],
                num_kv_heads=cfg["num_key_value_heads"],
                resolved_head_dim=cfg["head_dim"],
                d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
                m_vocab=cfg["bloom_m"], rope_theta=cfg["rope_theta"],
                norm_eps=cfg["rms_norm_eps"], qkv_bias=True,
                tie_embeddings=cfg["tie_word_embeddings"],
                dtype=cfg["compute_dtype"])
    got = {f: getattr(mc, f) for f in want}
    got_bloom = (mc.bloom.enabled, mc.bloom.k, mc.bloom.seed)
    want_bloom = (True, cfg["bloom_k"], cfg["bloom_seed"])
    if got != want or got_bloom != want_bloom:
        raise ValueError(f"program config {cfg['program_arch']!r} is "
                         f"{got} {got_bloom}, the configuration file says "
                         f"{want} {want_bloom}")
    return mc


def _leaf_specs(mc) -> tuple[dict, object]:
    """{path: (shape, fan_in, kind)} of the program's parameter tree (its
    structure only), and (treedef, path order) to rebuild the tree."""
    from repro.launch import steps as steps_lib
    shapes = jax.eval_shape(steps_lib.init_fn_for(mc), jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    leaves = {}
    for path, leaf in flat:
        name = "/".join(p.key for p in path)
        shape = leaf.shape
        if name == "io/embed":
            leaves[name] = (shape, None, "embed")
        elif name == "final_norm/scale":
            leaves[name] = (shape, None, "scale")
        else:
            rule = _LEAVES[name.split("/", 2)[2]]
            if isinstance(rule, tuple):
                fan_in = int(np.prod([shape[a] for a in rule]))
                leaves[name] = (shape, fan_in, "matrix")
            else:
                leaves[name] = (shape, None, rule)
    return leaves, (treedef, [("/".join(p.key for p in path))
                              for path, _ in flat])


def _server_cast(cfg: dict):
    # the program serves every array of two or more dimensions in its
    # compute dtype (launch/steps.cast_params_for_compute)
    dt = jnp.dtype(cfg["compute_dtype"])
    return lambda path, a: a.astype(dt) if a.ndim >= 2 else a


def make_params(cfg: dict, mc, seed: int, serve: bool):
    """The program's parameter tree, from the seed: as served (arrays in
    the compute dtype, vectors in float32) or the float32 master."""
    leaves, (treedef, order) = _leaf_specs(mc)
    flat = weights.make(seed, leaves,
                        cast=_server_cast(cfg) if serve else None)
    return jax.tree_util.tree_unflatten(treedef, [flat[p] for p in order])


def reference_params(cfg: dict, mc, seed: int, serve: bool) -> dict:
    """The same weights as a flat float32 {path: array} for the
    reference."""
    leaves, _ = _leaf_specs(mc)
    flat = weights.make(seed, leaves,
                        cast=_server_cast(cfg) if serve else None)
    return {p: a.astype(jnp.float32) for p, a in flat.items()}


class System:
    """One LM serving deployment: weights, program and prefill pool."""


    def __init__(self, cfg: dict, traffic: dict, seed: int):
        from repro.serving.engine import LMSlotProgram, PrefillPool
        self.cfg, self.traffic = cfg, traffic
        self.mc = program_config(cfg)
        self.n_slots = traffic["slots"]
        self.params = make_params(cfg, self.mc, seed, serve=True)
        self.program = LMSlotProgram(
            self.mc, topk=traffic["topk"], n_slots=self.n_slots,
            max_len=traffic["max_len"])
        self.pool = PrefillPool(self.mc, self.params, topk=traffic["topk"],
                                program=self.program)

    def warm(self, state, stats) -> None:
        """Every program the window runs, once: the prefill and the slot
        insert at each prompt length of the mix, the pool decode step and
        the slot-state updates around it."""
        from repro.serving.scheduler import Request
        lens = traffic_gen.prompt_lengths(self.traffic)
        for slot, L in enumerate(lens):
            r = Request(rid=-1 - slot, prompt=np.zeros(L, np.int32),
                        max_gen=2)
            r.slot = slot
            self.program.insert(state, r, self.pool.prefill_all([r])[0],
                                stats)
        out = self.program.step(self.params, state)
        for slot in range(len(lens)):
            self.program.emit(state, r, slot, out, stats)
        jax.block_until_ready(state.active)
        self.program.reset_slots(state)

    @staticmethod
    def step_meta(active: dict) -> dict:
        # each live slot attends its prompt and every token fed so far
        return {"live": len(active),
                "keys": sum(r.prompt_len + len(r.tokens)
                            for r in active.values())}

    def release(self) -> None:
        del self.program, self.pool, self.params


def sample(traffic: dict, seed: int, served: list) -> list:
    """The requests the check reads: the longest served request, then
    others drawn from the seed until ``check_tokens`` tokens are held."""
    longest = max(served, key=lambda r: (len(r.tokens), r.prompt_len))
    rng = traffic_gen.rng_for(seed, 7)
    out, n = [longest], len(longest.tokens)
    for i in rng.permutation(len(served)):
        if n >= traffic["check_tokens"]:
            break
        if served[i] is not longest:
            out.append(served[i])
            n += len(served[i].tokens)
    return out


def token_gaps(cfg: dict, mc, params: dict, reqs: list, max_len: int,
               max_out: int, quant=None) -> tuple[float, float | None]:
    """Widest gap of the served tokens of ``reqs`` below the reference's
    best Eq. 3 score at their positions, and, with ``quant``, the widest
    gap of the tokens that reference in that precision ranks first."""
    fwd = jax.jit(lambda p, t: ref.forward(p, t, cfg))
    fwd_q = (None if quant is None else
             jax.jit(lambda p, t: ref.forward(p, t, cfg, quant=quant)))

    @jax.jit
    def gaps(logits, logits_q, rows, toks):
        lp = jax.nn.log_softmax(jnp.take(logits, rows, axis=0), axis=-1)
        lq = (None if logits_q is None else jax.nn.log_softmax(
            jnp.take(logits_q, rows, axis=0), axis=-1))
        return ref.recovery_gaps(lp, lq, toks, cfg)

    worst, worst_q = 0.0, (None if quant is None else 0.0)
    for r in reqs:
        seq = np.zeros(max_len, np.int32)
        full = np.concatenate([np.asarray(r.prompt, np.int32),
                               np.asarray(r.tokens[:-1], np.int32)])
        seq[:len(full)] = full
        n = len(r.tokens)
        rows = np.zeros(max_out, np.int32)
        rows[:n] = r.prompt_len - 1 + np.arange(n)
        toks = np.zeros(max_out, np.int32)
        toks[:n] = r.tokens
        seq = jnp.asarray(seq)
        g, gq = gaps(fwd(params, seq),
                     None if fwd_q is None else fwd_q(params, seq),
                     jnp.asarray(rows), jnp.asarray(toks))
        worst = max(worst, float(np.asarray(g)[:n].max()))
        if gq is not None:
            worst_q = max(worst_q, float(np.asarray(gq)[:n].max()))
    return worst, worst_q


def check(cfg: dict, traffic: dict, seed: int, served: list) -> list:
    mc = program_config(cfg)
    params = reference_params(cfg, mc, seed, serve=True)
    gap, _ = token_gaps(cfg, mc, params, sample(traffic, seed, served),
                        traffic["max_len"], max(traffic["output_lens"]))
    return [("token_gap", gap)]


def control(cfg: dict, traffic: dict, seed: int, window) -> list:
    """The control: the reference computed with float8 (e4m3) matmul
    inputs, one step below the configuration's bfloat16, read at the
    positions and on the tokens the program served: the gap of the item
    that the float8 model ranks first."""
    mc = program_config(cfg)
    served = window()
    params = reference_params(cfg, mc, seed, serve=True)
    gap, gap_q = token_gaps(cfg, mc, params, sample(traffic, seed, served),
                            traffic["max_len"], max(traffic["output_lens"]),
                            quant=jnp.float8_e4m3fn)
    return [("token_gap", gap_q), ("program_token_gap", gap)]
