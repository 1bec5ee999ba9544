"""Named scopes of the served step: op metadata that names each part of
the program (``jax.named_scope``), so a device trace can say which lane,
which MoE stage and which program an op belongs to. They must reach the
HLO's ``op_name`` of the step, the MoE layer on either EP path, and the
migration slice copy."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke
from repro.launch.mesh import make_mesh
from repro.models import moe as M
from repro.models import transformer as T
from repro.parallel.ctx import ParallelCtx
from repro.runtime import migration_driver as md
from repro.runtime.scheduler import RequestScheduler
from repro.runtime.serve import Server, ServeConfig


def _op_names(lowered) -> list[str]:
    hlo = lowered.as_text(dialect="hlo", debug_info=True)
    return sorted(set(re.findall(r'op_name="([^"]*)"', hlo)))


def _has(names, path: str) -> bool:
    """Some op's name stack holds the parts of ``path`` in order, each a
    whole part (``lane/expert_ffn`` matches ``a/lane/b/expert_ffn/c``)."""
    want = path.split("/")
    for n in names:
        parts, i = n.split("/"), 0
        for p in parts:
            if i < len(want) and p == want[i]:
                i += 1
        if i == len(want):
            return True
    return False


def _moe_cfg():
    base = dataclasses.replace(
        smoke(get_config("dbrx-132b")), n_experts=4, experts_per_token=2
    )
    return dataclasses.replace(base, n_layers=1)


@pytest.fixture(scope="module")
def served():
    """A chunked, balanced server mid-flight: one request decoding while
    another rides the prefill lane."""
    cfg = _moe_cfg()
    srv = Server(cfg, ParallelCtx(capacity_factor=8.0),
                 T.init_params(jax.random.PRNGKey(0), cfg),
                 ServeConfig(max_seq=64, batch=2, paged=True, page_size=8,
                             prefill_chunk=8, virtual_ep=2, slots_per_device=2,
                             migration_slices=2))
    sched = RequestScheduler(srv)
    sched.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=4)
    sched.step()
    sched.submit(np.arange(1, 20, dtype=np.int32), max_new_tokens=2)
    sched.step()
    return srv, sched


@pytest.fixture(scope="module")
def step_names(served):
    srv, sched = served
    chunk = srv.chunk_operand(sched._prefilling.slot, np.zeros(8, np.int32), 0, 8)
    low = srv._decode.lower(
        srv.params, jnp.asarray(sched.next_tok), sched.cache,
        placement=srv.table.device_view(), slot_mask=jnp.ones(2, bool), chunk=chunk,
    )
    return _op_names(low)


@pytest.mark.parametrize("path", [
    "decode/router", "decode/dispatch", "decode/expert_ffn", "decode/combine",
    "lane/router", "lane/dispatch", "lane/expert_ffn", "lane/combine",
    "lm_head", "lane/lm_head",
])
def test_step_op_metadata_names_each_part(step_names, path):
    assert _has(step_names, path), path


def test_decode_lm_head_is_outside_the_lane(step_names):
    heads = [n for n in step_names if "lm_head" in n.split("/")]
    assert any("lane" not in n.split("/") for n in heads)


def test_migration_slice_copy_is_scoped(served):
    srv, _ = served
    w = srv._moe()["w_gate"]
    low = md._copy_row_slice.lower(w, md._i32(0), md._i32(1), md._i32(0), rows=4)
    names = [n for n in _op_names(low) if "/" in n]
    assert names and all("migration" in n.split("/") for n in names)


@pytest.mark.parametrize("kernels", [False, True], ids=["einsum", "kernels"])
@pytest.mark.parametrize("mesh", [False, True], ids=["local", "shardmap"])
@pytest.mark.parametrize("stage", ["router", "dispatch", "expert_ffn", "combine"])
def test_moe_layer_stages_are_scoped_on_both_ep_paths(mesh, kernels, stage):
    cfg = _moe_cfg()
    ctx = ParallelCtx(
        mesh=make_mesh((1, 1), ("data", "model")) if mesh else None,
        capacity_factor=8.0, moe_impl="ep", use_kernels=kernels,
    )
    p = jax.tree.map(lambda a: a[0], T.init_params(jax.random.PRNGKey(1), cfg)["layers"]["moe"])
    x = jnp.ones((2, 1, cfg.d_model), jnp.float32)
    low = jax.jit(lambda p, x: M.moe_apply(p, x, cfg, ctx)[0]).lower(p, x)
    assert _has(_op_names(low), stage)
