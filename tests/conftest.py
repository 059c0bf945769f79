"""Shared fixtures. NOTE: no XLA_FLAGS here on purpose — smoke tests and
benches must see the real device count (1 CPU); only launch/dryrun.py
forces 512 placeholder devices (in its own process)."""
import os

import jax
import numpy as np
import pytest

# checkout root: the cwd of the subprocess tests (PYTHONPATH=src is relative)
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def subprocess_env():
    """Clean env for driver subprocess tests.

    PATH stays stripped to the system dirs on purpose (drivers must not
    lean on the dev shell), but JAX backend selection has to survive the
    strip: without JAX_PLATFORMS the child process probes for accelerator
    runtimes at import and hangs on CPU-only CI boxes.
    """
    env = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"}
    for var in ("HOME", "TMPDIR", "JAX_PLATFORMS", "XLA_FLAGS",
                "XLA_PYTHON_CLIENT_PREALLOCATE"):
        if var in os.environ:
            env[var] = os.environ[var]
    env.setdefault("JAX_PLATFORMS", jax.default_backend())
    return env


def assert_slot_log_sound(sched, n_slots):
    """Shared invariant check on a serving scheduler's event log — thin
    wrapper over THE replay helper (serving/control.replay_slot_log):
    admissions/releases per slot alternate with matching rids through any
    COMPACT remaps, i.e. no slot ever hosts two live requests and no
    live request is dropped by a compaction.  REJECT (prefill exhausted)
    and RECLAIM (HOST_DOWN) events vacate slots like releases and are
    replayed under the same invariant.  Used by the deterministic sim
    tests, the chaos twins, and the hypothesis property suite."""
    from repro.serving.control import replay_slot_log
    replay_slot_log(sched.admissions, sched.releases,
                    getattr(sched, "compactions", []), n_slots,
                    rejects=getattr(sched, "rejects", []),
                    reclaims=getattr(sched, "reclaims", []))


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture
def key():
    return jax.random.PRNGKey(0)
