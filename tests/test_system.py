"""End-to-end behaviour tests for the paper's system.

These are the integration gates: (1) a Bloom-embedded recommender must
actually learn (beat random by a wide margin) on sparse data, (2) the
Bloom LM path must train, (3) serving must produce recovered-vocab tokens,
(4) the full train driver must be crash-recoverable.
"""
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.base import TrainConfig
from repro.core.alternatives import BloomIO
from repro.data.pipeline import BatchIterator
from repro.data.synthetic import make_recsys
from repro.models import recommender as rec
from repro.train import metrics as M
from repro.train.trainer import Trainer


def _train_recommender(emb, data, steps=150, hidden=(64, 64), lr=2e-3):
    key = jax.random.PRNGKey(0)
    params = rec.recommender_init(key, emb, list(hidden))
    loss_fn = lambda p, b: (rec.recommender_loss(p, emb, b[0], b[1]), {})
    it = BatchIterator(list(data.train()), 64, seed=1)
    tc = TrainConfig(steps=steps, learning_rate=lr, optimizer="adam",
                     warmup_steps=0, checkpoint_every=0,
                     grad_clip_norm=0.0)
    tr = Trainer(loss_fn, params, tc, it,
                 make_batch=lambda a: (jnp.asarray(a[0]),
                                       jnp.asarray(a[1])))
    tr.run(steps=steps)
    return tr.state.params


def test_bloom_recommender_learns():
    data = make_recsys(n=1200, d=500, mean_items=10, seed=0)
    emb = BloomIO.build(d=500, m=150, k=4)
    params = _train_recommender(emb, data)
    p_te, q_te = data.test()
    scores = np.asarray(rec.recommender_scores(params, emb,
                                               jnp.asarray(p_te)))
    mapv = M.mean_average_precision(scores, q_te, p_te)
    random_map = M.mean_average_precision(
        np.random.default_rng(0).normal(size=scores.shape), q_te, p_te)
    assert mapv > 5 * random_map, (mapv, random_map)
    assert mapv > 0.03


def test_lm_smoke_training_reduces_loss():
    from repro.launch.train import run
    params, history = run("qwen1.5-0.5b", steps=40, batch=4, seq=32,
                          ckpt_dir=None, log_every=5)
    losses = [h["loss"] for h in history]
    assert losses[-1] < losses[0] - 0.2, losses


def test_serve_driver_generates_tokens():
    from repro.launch.serve import run
    toks = run("qwen1.5-0.5b", batch=2, prompt_len=12, gen=5)
    assert toks.shape == (2, 5)
    cfg = configs.get_smoke_config("qwen1.5-0.5b")
    assert (toks >= 0).all() and (toks < cfg.vocab).all()


def test_train_driver_crash_and_resume(tmp_path):
    """Kill the driver mid-run via --fault-at, rerun, expect completion."""
    ck = str(tmp_path / "ck")
    from conftest import REPO_ROOT, subprocess_env
    cmd = [sys.executable, "-m", "repro.launch.train", "--arch",
           "qwen1.5-0.5b", "--steps", "16", "--batch", "2", "--seq", "16",
           "--ckpt", ck]
    env = subprocess_env()
    r1 = subprocess.run(cmd + ["--fault-at", "10"], capture_output=True,
                        text=True, env=env, cwd=REPO_ROOT)
    assert r1.returncode != 0 and "induced fault" in r1.stderr
    r2 = subprocess.run(cmd, capture_output=True, text=True, env=env,
                        cwd=REPO_ROOT)
    assert r2.returncode == 0, r2.stderr
    assert "resumed from step" in r2.stdout
    assert "trained" in r2.stdout


def test_grad_accumulation_matches_full_batch():
    """microbatch=2 grad accumulation == one big batch (linear model)."""
    from repro.train.trainer import make_train_step, make_optimizer
    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.normal(size=(16, 4)).astype(np.float32))
    Y = jnp.asarray(rng.normal(size=(16, 1)).astype(np.float32))

    def loss_fn(params, batch):
        x, y = batch
        return jnp.mean((x @ params["w"] - y) ** 2), {}

    tc = TrainConfig(optimizer="sgd", learning_rate=0.1, momentum=0.0,
                     grad_clip_norm=0.0, warmup_steps=0)
    tx = make_optimizer(tc)
    p0 = {"w": jnp.zeros((4, 1))}

    full = make_train_step(loss_fn, tx, microbatch=0, donate=False)
    acc = make_train_step(loss_fn, tx, microbatch=2, donate=False)
    p1, _, _ = full(p0, tx.init(p0), (X, Y))
    p2, _, _ = acc(p0, tx.init(p0), (X, Y))
    np.testing.assert_allclose(np.asarray(p1["w"]), np.asarray(p2["w"]),
                               rtol=1e-5)
