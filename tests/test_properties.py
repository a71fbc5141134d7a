"""Hypothesis property tests on system invariants."""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.configs.base import TrainConfig
from repro.models.layers import softmax_xent
from repro.optim import adamw


@settings(max_examples=25, deadline=None)
@given(st.integers(2, 64), st.integers(1, 8), st.integers(0, 10_000))
def test_softmax_xent_matches_naive(V, B, seed):
    logits = jax.random.normal(jax.random.key(seed), (B, V)) * 3
    targets = jax.random.randint(jax.random.key(seed + 1), (B,), 0, V)
    got = float(softmax_xent(logits, targets))
    p = jax.nn.softmax(logits, -1)
    want = float(-jnp.log(jnp.take_along_axis(
        p, targets[:, None], axis=-1))[..., 0].mean())
    np.testing.assert_allclose(got, want, rtol=1e-4)


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 10_000))
def test_xent_lower_bounded_by_zero(seed):
    logits = jax.random.normal(jax.random.key(seed), (4, 16)) * 5
    targets = jnp.argmax(logits, -1)   # best case
    assert float(softmax_xent(logits, targets)) >= 0.0


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 10_000), st.floats(1e-4, 1e-1))
def test_adamw_zero_grad_only_decays(seed, wd):
    cfg = TrainConfig(learning_rate=1e-2, warmup_steps=0, weight_decay=wd,
                      grad_clip=1e9)
    w0 = jax.random.normal(jax.random.key(seed), (8,))
    p = {"w": w0}
    opt = adamw.init_opt_state(p)
    p2, _, _ = adamw.adamw_update(p, {"w": jnp.zeros(8)}, opt, jnp.array(0), cfg)
    lr = float(adamw.lr_schedule(jnp.array(0), cfg))
    np.testing.assert_allclose(np.asarray(p2["w"]),
                               np.asarray(w0) * (1 - lr * wd), rtol=1e-5)


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 10_000))
def test_clip_idempotent(seed):
    g = {"a": jax.random.normal(jax.random.key(seed), (16,)) * 100}
    c1, _ = adamw.clip_by_global_norm(g, 1.0)
    c2, _ = adamw.clip_by_global_norm(c1, 1.0)
    np.testing.assert_allclose(np.asarray(c1["a"]), np.asarray(c2["a"]),
                               rtol=1e-5)


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 64), st.integers(0, 10_000))
def test_int8_quant_error_bounded(n, seed):
    """One int8 quantization step: |err| <= scale/2 elementwise."""
    g = jax.random.normal(jax.random.key(seed), (n,)) * 10 ** (seed % 4 - 2)
    scale = jnp.maximum(jnp.abs(g).max(), 1e-12) / 127.0
    q = jnp.clip(jnp.round(g / scale), -127, 127).astype(jnp.int8)
    err = np.abs(np.asarray(g - q.astype(jnp.float32) * scale))
    assert (err <= float(scale) / 2 + 1e-9).all()


@settings(max_examples=12, deadline=None)
@given(st.integers(2, 40), st.integers(1, 10), st.integers(0, 1000))
def test_rowwise_matvec_property(N, K, seed):
    from repro.core.gru import matvec
    x = jax.random.normal(jax.random.key(seed), (3, K))
    w = jax.random.normal(jax.random.key(seed + 1), (K, N))
    ref = np.asarray(x @ w)
    for mode in ("rowwise", "cascade"):
        np.testing.assert_allclose(np.asarray(matvec(x, w, mode)), ref,
                                   rtol=2e-4, atol=2e-4)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 1000))
def test_checkpoint_roundtrip_property(tmp_seed):
    import tempfile
    from repro.checkpoint.manager import CheckpointManager
    rng = np.random.default_rng(tmp_seed)
    state = {"a": jnp.asarray(rng.normal(size=(3, 5)).astype(np.float32)),
             "n": {"b": jnp.asarray(rng.integers(0, 9, size=(4,)))}}
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d, keep=1)
        mgr.save(state, 1)
        out = mgr.restore(state, step=1)
        for x, y in zip(jax.tree_util.tree_leaves(state),
                        jax.tree_util.tree_leaves(out)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
