"""Seeded model weights, made by the benchmark and handed to the program.

One jitted call turns ``--seed`` into every weight of the model, on the
device and in the type it is served in. The tree has the program's layout
(the program takes its weights as an argument), but its values come from
here alone, so the plain reference (``reference.py``) can make the very same
weights again from the seed after the program's state is freed, without
taking anything the program made.

Each leaf is ``scale * N(0, 1)`` drawn from its own key (``fold_in`` of the
leaf's index in a fixed order; ``scale`` is the model's ``init_scale``,
0.02 by default), norms are ones, and the router is float32, as the
program keeps it. Threefry draws the same numbers whatever the
output sharding, so the weights of a cell are the same on one chip and
over a mesh.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

SCALE = 0.02


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative whole number (more than 32 bits)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def shapes(m: dict) -> dict:
    """Leaf shapes and dtypes of the served weights of model ``m`` (the
    ``model`` block of a configuration file)."""
    dt = jnp.dtype(m["dtype"])
    L, d, v = m["n_layers"], m["d_model"], m["vocab"]
    hq, hk = m["n_heads"] * m["head_dim"], m["n_kv_heads"] * m["head_dim"]
    e, f = m["n_experts"], m["expert_d_ff"]
    sds = jax.ShapeDtypeStruct
    return {
        "embed": sds((v, d), dt),
        "final_norm": sds((d,), dt),
        "lm_head": sds((d, v), dt),
        "layers": {
            "ln1": sds((L, d), dt),
            "ln2": sds((L, d), dt),
            "attn": {
                "wq": sds((L, d, hq), dt),
                "wk": sds((L, d, hk), dt),
                "wv": sds((L, d, hk), dt),
                "wo": sds((L, hq, d), dt),
            },
            "moe": {
                "router": sds((L, d, e), jnp.float32),
                "w_gate": sds((L, e, d, f), dt),
                "w_up": sds((L, e, d, f), dt),
                "w_down": sds((L, e, f, d), dt),
            },
        },
    }


def _leaf(key, index: int, name: str, s, scale: float) -> jax.Array:
    if name in ("final_norm", "ln1", "ln2"):
        return jnp.ones(s.shape, s.dtype)
    return scale * jax.random.normal(jax.random.fold_in(key, index), s.shape, s.dtype)


def make(m: dict, seed: int, out_shardings=None):
    """All weights of model ``m`` from ``seed``, made on the device in one
    jitted call (sharded by ``out_shardings`` when given)."""
    tree = shapes(m)
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)

    def build(key):
        leaves = [
            _leaf(key, i, p[-1].key, s, m.get("init_scale", SCALE))
            for i, (p, s) in enumerate(flat)
        ]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    fn = jax.jit(build, out_shardings=out_shardings)
    return fn(seed_key(seed))


def n_bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))
