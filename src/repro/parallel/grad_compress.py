"""Cross-pod communication compression (int8 wire format).

The ``pod`` axis rides slow DCI links (the analogue of the paper's
cross-wafer connectors), so cross-pod traffic is the byte budget that
matters at 1000+ node scale. Two facilities:

* :func:`compressed_pod_mean` — average a pytree across pods with int8
  stochastic-rounding wire format (4x fewer DCI bytes than bf16). Used by
  the training loop for DiLoCo-style periodic cross-pod parameter
  synchronization: pods run locally for K steps, then reconcile. This
  replaces per-step cross-pod gradient all-reduce — both a bandwidth
  optimization and a straggler/fault isolation boundary (a slow pod delays
  a sync point, not every step).
* :func:`_quant` / :func:`_pod_psum_int8` — the underlying unbiased int8
  reduce-scatter/all-gather building blocks.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P


def _quant(x: jax.Array, key: jax.Array):
    scale = jnp.max(jnp.abs(x)) / 127.0 + 1e-12
    y = x / scale
    noise = jax.random.uniform(key, y.shape) - 0.5
    q = jnp.clip(jnp.round(y + noise), -127, 127).astype(jnp.int8)
    return q, scale


def _pod_psum_int8(x: jax.Array, axis: str, n_pods: int, key: jax.Array):
    """Unbiased int8-wire psum over ``axis`` for one fp32 tensor."""
    pad = (-x.size) % n_pods
    flat = jnp.pad(x.reshape(-1), (0, pad)).reshape(n_pods, -1)
    q, scale = _quant(flat, key)
    # Reduce-scatter: exchange int8 chunks; chunk i lands on pod i.
    recv = jax.lax.all_to_all(q, axis, split_axis=0, concat_axis=0, tiled=False)
    scales = jax.lax.all_gather(scale, axis)                    # (n_pods,)
    part = jnp.sum(recv.astype(jnp.float32) * scales[:, None], axis=0)
    # All-gather the summed chunk, int8 again.
    q2, scale2 = _quant(part[None], key)
    got = jax.lax.all_gather(q2[0], axis)                       # (n_pods, chunk)
    scales2 = jax.lax.all_gather(scale2, axis)
    full = (got.astype(jnp.float32) * scales2[:, None]).reshape(-1)
    return full[: x.size].reshape(x.shape)


def compressed_pod_mean(tree, mesh: jax.sharding.Mesh, seed: int = 0):
    """Average a pytree over the ``pod`` mesh axis, int8 on the wire.

    Leaves are treated as pod-replicated within each pod's sub-mesh (the
    usual layout: params sharded over "model"/"data", replicated over
    "pod"); the partial shard_map manualizes only the pod axis.
    """
    n_pods = mesh.shape["pod"]
    if n_pods == 1:
        return tree
    leaves, tdef = jax.tree.flatten(tree)

    def body(*flat):
        key = jax.random.PRNGKey(seed)
        out = []
        for i, g in enumerate(flat):
            s = _pod_psum_int8(
                g.astype(jnp.float32), "pod", n_pods, jax.random.fold_in(key, i)
            )
            out.append((s / n_pods).astype(g.dtype))
        return tuple(out)

    specs = tuple(P(*(None,) * l.ndim) for l in leaves)
    out = shard_map(
        body,
        mesh=mesh,
        in_specs=specs,
        out_specs=specs,
        axis_names={"pod"},
        check_vma=False,
    )(*leaves)
    return tdef.unflatten(list(out))
