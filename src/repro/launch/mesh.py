"""Production mesh construction (+ ER-Mapping device placement).

``make_production_mesh`` is the canonical entry (16x16 per pod; 2 pods for
multi-pod). ``make_er_mesh`` applies the paper's Entwined Ring Mapping as a
*device-order permutation*: the logical ("data","model") axes are identical,
but TP groups land entwined on the physical torus so the model-axis rings
and the EP all-to-all traffic follow the paper's placement (the hop-distance
model this induces also drives the serving-side balancer — see
docs/serving.md, "Placement & topology").

Functions, not module constants — importing this module never touches jax
device state.
"""

from __future__ import annotations

import jax
import numpy as np


def make_mesh(shape, axes):
    """``jax.make_mesh`` with explicit Auto axis types: the one spelling for
    every mesh this repo builds (launchers + tests)."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_er_mesh(*, multi_pod: bool = False, mapping: str = "er"):
    """Production mesh with baseline/ER/HER physical placement.

    Each pod's 256 devices form a 16x16 grid; the chosen mapping's
    ``device_order()`` (dp=16 groups x tp=16 ranks) permutes them before the
    Mesh is built, so logical coordinates ("data" g, "model" r) sit at the
    physical position the paper's mapping prescribes.
    """
    from repro.core.er_mapping import MAPPINGS
    from repro.core.topology import MeshTopology

    topo = MeshTopology(16, 16)
    m = MAPPINGS[mapping](topo, 16, 16)
    order = m.device_order()                  # (16, 16) device ids in pod
    devices = np.array(jax.devices())
    n_pods = 2 if multi_pod else 1
    if devices.size < n_pods * 256:
        raise ValueError(
            f"need {n_pods * 256} devices, have {devices.size} "
            "(set XLA_FLAGS=--xla_force_host_platform_device_count=512)"
        )
    pods = []
    for p in range(n_pods):
        pod_devs = devices[p * 256 : (p + 1) * 256]
        pods.append(pod_devs[order])
    arr = np.stack(pods) if multi_pod else pods[0]
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.sharding.Mesh(
        arr, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_test_mesh(data: int = 2, model: int = 4):
    """Small mesh for CPU tests (requires forced host device count)."""
    return make_mesh((data, model), ("data", "model"))
