"""Production meshes (a function, never module-level state: importing this
module must not touch jax device initialisation)."""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: tuple, axes: tuple):
    """A mesh whose axes GSPMD shards (``Auto``).

    ``jax.make_mesh`` defaults to ``Explicit`` axes, which put shardings in
    the array types: the model code, written for GSPMD propagation, then
    fails to trace (scan carries and gathers disagree on types).
    """
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips with a leading pod axis.

    Axes: ``data`` carries batch + FSDP sharding; ``model`` carries tensor /
    expert parallelism; ``pod`` (multi-pod) extends data parallelism across
    the inter-pod links (DCN-ish: gradient reduction only).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model: int = 2):
    """Tiny mesh over however many (CPU) devices exist -- used by tests."""
    n = len(jax.devices())
    model = min(model, n)
    return _auto_mesh((n // model, model), ("data", "model"))


def make_serving_mesh(model: int | None = None):
    """Mesh over all local devices for sharded serving / chip programming.

    ``model`` sets the tensor-parallel degree (default: every device on the
    ``model`` axis -- serving replicates over ``data`` only when more
    devices than TP degree are available). Serving weights and the PCM
    state of a sharded CiMProgram are sharded over ``model``; the batch
    rides the ``data`` axis.
    """
    n = len(jax.devices())
    model = n if model is None else max(1, min(model, n))
    while n % model:  # e.g. 8 devices, --mesh-model 3
        model -= 1
    return _auto_mesh((n // model, model), ("data", "model"))
