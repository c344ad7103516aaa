"""Production mesh construction.

Single pod: 16×16 = 256 chips, axes ("data", "model").
Multi-pod:  2×16×16 = 512 chips, axes ("pod", "data", "model") — the "pod"
axis is pure data parallelism (no FSDP across pods: cross-pod DCI links are
an order of magnitude slower than intra-pod ICI, so only gradient
all-reduce crosses them).

Functions, not module constants: importing this module never touches jax
device state (required so smoke tests see 1 CPU device while the dry-run
sees 512 forced host devices). Callers enter a mesh with
``jax.set_mesh(mesh)``; the models read it back through
``jax.sharding.get_abstract_mesh()``.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """`jax.make_mesh` with every axis `Auto`: the compiler propagates
    shardings from the `in_shardings` and the models' sharding hints.
    (`jax.make_mesh` otherwise gives `Explicit` axes, under which ops such
    as the embedding gather demand explicitly sharded operands.)"""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh():
    """Whatever devices exist, as a 1×N (data, model) mesh — used by tests
    and the single-host train driver (elastic: adapts to the fleet)."""
    n = len(jax.devices())
    return make_mesh((1, n), ("data", "model"))


def data_axes(mesh) -> tuple:
    """Mesh axes carrying the batch dimension."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)
