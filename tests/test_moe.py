"""MoE: dispatch correctness, capacity behavior, EP path vs oracle."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs.base import get_smoke_config
from repro.core.params import init_params
from repro.distributed.sharding import ShardCtx
from repro.models import moe as moe_mod

CFG = get_smoke_config("qwen2-moe-a2.7b").replace(dtype="float32",
                                                  param_dtype="float32")


def _setup(capacity_factor=8.0, key=0):
    cfg = CFG.replace(moe=dataclasses.replace(CFG.moe,
                                              capacity_factor=capacity_factor))
    params = init_params(moe_mod.moe_specs(cfg), jax.random.key(key), "float32")
    x = jax.random.normal(jax.random.key(key + 1), (2, 8, cfg.d_model))
    return cfg, params, x


def test_local_dispatch_matches_ref():
    cfg, params, x = _setup()
    out, aux = moe_mod.moe_apply(params, cfg, x, ctx=ShardCtx())
    ref = moe_mod.moe_ref(params, cfg, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    assert float(aux) > 0.0


def test_capacity_drops_reduce_output():
    """With capacity 1 token/expert some contributions are dropped; output
    must stay finite and differ from the no-drop reference."""
    cfg, params, x = _setup(capacity_factor=0.1)
    out, _ = moe_mod.moe_apply(params, cfg, x, ctx=ShardCtx())
    assert np.isfinite(np.asarray(out)).all()
    ref = moe_mod.moe_ref(params, cfg, x)
    assert np.abs(np.asarray(out) - np.asarray(ref)).max() > 1e-5


def test_padded_experts_never_routed():
    cfg, params, x = _setup()
    E = moe_mod.padded_experts(cfg.moe)
    assert E == 16  # 8 -> padded to 16
    xf = x.reshape(-1, cfg.d_model)
    logits = xf @ params["router"]
    masked = jnp.where(jnp.arange(E)[None] < cfg.moe.num_experts, logits,
                       moe_mod.NEG_INF)
    _, top_i = jax.lax.top_k(jax.nn.softmax(masked, -1), cfg.moe.top_k)
    assert int(top_i.max()) < cfg.moe.num_experts


def test_ep_shard_map_matches_local(multidev):
    multidev("""
import dataclasses, jax, jax.numpy as jnp, numpy as np
from repro import compat
from repro.configs.base import get_smoke_config
from repro.core.params import init_params
from repro.distributed.sharding import ShardCtx
from repro.models import moe as moe_mod
cfg = get_smoke_config("qwen2-moe-a2.7b").replace(dtype="float32", param_dtype="float32")
cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
params = init_params(moe_mod.moe_specs(cfg), jax.random.key(0), "float32")
x = jax.random.normal(jax.random.key(1), (4, 8, cfg.d_model))
mesh = compat.make_mesh((2, 2), ("data", "model"))
out_ep, _ = jax.jit(lambda p, x: moe_mod.moe_apply(p, cfg, x, ctx=ShardCtx(mesh=mesh)))(params, x)
ref = moe_mod.moe_ref(params, cfg, x)
np.testing.assert_allclose(np.asarray(out_ep), np.asarray(ref), rtol=3e-4, atol=3e-4)
print("PASS")
""")


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 1000))
def test_combine_weights_sum(seed):
    """Renormalized top-k routing weights sum to 1 per token."""
    cfg, params, _ = _setup(key=seed % 7)
    cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, norm_topk_prob=True))
    x = jax.random.normal(jax.random.key(seed), (1, 6, cfg.d_model))
    xf = x.reshape(-1, cfg.d_model)
    E = moe_mod.padded_experts(cfg.moe)
    logits = xf @ params["router"]
    logits = jnp.where(jnp.arange(E)[None] < cfg.moe.num_experts, logits,
                       moe_mod.NEG_INF)
    probs = jax.nn.softmax(logits, -1)
    top_p, _ = jax.lax.top_k(probs, cfg.moe.top_k)
    top_p = top_p / top_p.sum(-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(top_p.sum(-1)), 1.0, rtol=1e-5)


def test_moe_grads_flow_through_router():
    cfg, params, x = _setup()

    def loss(p):
        out, aux = moe_mod.moe_apply(p, cfg, x, ctx=ShardCtx())
        return (out ** 2).mean() + aux
    g = jax.grad(loss)(params)
    assert np.abs(np.asarray(g["router"])).sum() > 0
    assert np.abs(np.asarray(g["wg"])).sum() > 0
