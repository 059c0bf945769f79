"""Bloom retrieval as the program serves it: ``RetrievalProgram``
(Bloom encode -> FF tower prefill, one fused Eq. 3 top-k decode step
over the catalog) in the program's own ``PrefillPool``.

The comparison that decides ``correct``: for a sample of the answers
served in the window (drawn from the seed), the plain float32 reference
(``bench/reference/retrieval.py``) scores the served ids and finds the
catalog's true top-k.  Two numbers:

* ``topk_gap``: how far the reference's score of the r-th served id lies
  below the reference's r-th best score, worst over ranks and answers
  (infinite where an answer repeats an id or leaves the catalog);
* ``score_err``: the largest difference between a served score and the
  reference's score of the same id.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from bench import traffic_gen, weights
from bench.reference import retrieval as ref

BAD = 1e9          # a gap that no limit admits: repeated or foreign ids


def program_config(cfg: dict, table_dtype: str | None = None):
    """The program's ``RetrievalConfig`` for the configuration file's
    deployment; the decode path and its tiling are the program's own
    defaults."""
    from repro.configs.retrieval import RetrievalConfig
    rcfg = RetrievalConfig(name="bench", d=cfg["d"], m=cfg["m"], k=cfg["k"],
                           c_max=cfg["c_max"], hidden=tuple(cfg["hidden"]),
                           topk=cfg["topk"], seed=cfg["hash_seed"])
    if table_dtype is not None:
        rcfg = dataclasses.replace(rcfg, table_dtype=table_dtype)
    return rcfg


def tower_leaves(cfg: dict) -> dict:
    dims = [cfg["m"], *cfg["hidden"], cfg["m"]]
    leaves = {}
    for i, (a, b) in enumerate(zip(dims[:-1], dims[1:])):
        leaves[f"l{i}/w"] = ((a, b), a, "matrix")
        leaves[f"l{i}/b"] = ((b,), None, "bias")
    return leaves


def make_tower(cfg: dict, seed: int) -> dict:
    flat = weights.make(seed, tower_leaves(cfg))
    n = len(cfg["hidden"]) + 1
    return {f"l{i}": {"w": flat[f"l{i}/w"], "b": flat[f"l{i}/b"]}
            for i in range(n)}


class System:
    """One retrieval deployment: weights, program and prefill pool."""


    def __init__(self, cfg: dict, traffic: dict, seed: int,
                 table_dtype: str | None = None):
        from repro.serving.engine import PrefillPool
        from repro.serving.retrieval import RetrievalProgram
        self.cfg, self.traffic = cfg, traffic
        self.rcfg = program_config(cfg, table_dtype)
        self.n_slots = traffic["slots"]
        self.params = make_tower(cfg, seed)
        self.program = RetrievalProgram(self.rcfg, n_slots=self.n_slots)
        self.pool = PrefillPool(None, self.params, topk=self.rcfg.topk,
                                program=self.program)

    def warm(self, state, stats) -> None:
        """Every program the window runs, once: the prefill at c_max
        items, the slot insert, the full-pool decode step."""
        from repro.serving.scheduler import Request
        items = np.arange(self.cfg["c_max"], dtype=np.int32)
        for slot in range(self.n_slots):
            r = Request(rid=-1 - slot, prompt=items, max_gen=1,
                        kind="oneshot")
            r.slot = slot
            self.program.insert(state, r, self.pool.prefill_all([r])[0],
                                stats)
        out = self.program.step(self.params, state)
        for slot in range(self.n_slots):
            self.program.emit(state, r, slot, out, stats)
        self.program.reset_slots(state)

    @staticmethod
    def step_meta(active: dict) -> dict:
        return {"live": len(active)}

    def release(self) -> None:
        del self.program, self.pool, self.params


def check(cfg: dict, traffic: dict, seed: int, served: list) -> list:
    """[(name, value)] of the comparison with the reference over a
    sample of ``served`` requests drawn from the seed."""
    n = min(traffic["check_sample"], len(served))
    rng = traffic_gen.rng_for(seed, 7)
    pick = [served[i] for i in sorted(rng.choice(len(served), n,
                                                 replace=False))]
    c = cfg["c_max"]
    items = np.full((n, c), -1, np.int32)
    for i, r in enumerate(pick):
        items[i, :r.prompt_len] = r.prompt
    ids = np.asarray([r.topk_ids for r in pick], np.int64)
    got = np.asarray([r.topk_scores for r in pick], np.float64)
    tower = make_tower(cfg, seed)
    kw = dict(m=cfg["m"], k=cfg["k"], seed=cfg["hash_seed"])
    logp = ref.log_probs([(t["w"], t["b"]) for t in tower.values()],
                         jnp.asarray(items), **kw)
    best = np.asarray(ref.topk_values(logp, d=cfg["d"], topk=cfg["topk"],
                                      **kw), np.float64)
    ok = ((ids >= 0) & (ids < cfg["d"])).all(1) & np.asarray(
        [len(set(row)) == len(row) for row in ids.tolist()])
    mine = np.asarray(ref.scores_of(
        logp, jnp.asarray(np.clip(ids, 0, cfg["d"] - 1), jnp.int32), **kw),
        np.float64)
    gap = np.where(ok[:, None], best - mine, BAD)
    return [("topk_gap", float(gap.max())),
            ("score_err", float(np.abs(got - mine).max()))]


def control(cfg: dict, traffic: dict, seed: int, window) -> list:
    """The control: the program's own narrower path, its (rows, m) pool
    stored in bfloat16 (one step below the float32 the configuration
    states), serving the same window."""
    return check(cfg, traffic, seed, window(table_dtype="bfloat16"))
