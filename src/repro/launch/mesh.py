"""Production meshes (DESIGN.md §6).

A *function*, not a module-level constant, so importing this module never
touches jax device state — critical because smoke tests must see 1 CPU
device while the dry-run forces 512 placeholder devices via XLA_FLAGS.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``.  jax 0.9's
    make_mesh defaults to Explicit axes, under which the model code's
    gathers need sharding annotations it does not carry; Auto leaves the
    partitioning to GSPMD as the sharding rules expect."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 two pods (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_local_mesh(axes=("data", "model")):
    """Whatever devices exist, as a (1, ..., n_devices) mesh — used by
    tests and the CPU train/serve drivers."""
    n = jax.device_count()
    shape = (1,) * (len(axes) - 1) + (n,)
    return auto_mesh(shape, axes)


def make_serving_mesh(n_hosts: int | None = None, model_parallel: int = 1):
    """Serving-pool mesh: one `data` shard per (simulated) host, `model`
    fixed at `model_parallel`.  With
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` this simulates
    an N-way multi-host serving topology on one CPU process (the
    multi-host sim tests and the `--sharded` serve CLI use exactly that).
    """
    total = jax.device_count()
    if n_hosts is None:
        assert total % model_parallel == 0
        n_hosts = total // model_parallel
    assert n_hosts * model_parallel <= total, (
        f"need {n_hosts * model_parallel} devices, have {total}")
    return auto_mesh((n_hosts, model_parallel), ("data", "model"),
                     devices=jax.devices()[:n_hosts * model_parallel])


def make_elastic_mesh(n_devices: int, axes=("data", "model"),
                      model_parallel: int = 1):
    """Rebuild a mesh after a world-size change (node failure / elastic
    scale): keeps `model_parallel` fixed and gives the rest to data."""
    assert n_devices % model_parallel == 0
    shape = (n_devices // model_parallel, model_parallel)
    return auto_mesh(shape, axes[-2:])
