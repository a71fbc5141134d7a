"""Core GRU: structural modes vs dense oracle + property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs.base import GRUConfig
from repro.core import gru
from repro.core.params import init_params


def _params(X, H, key=0):
    return init_params(gru.gru_cell_specs(X, H), jax.random.key(key))


@pytest.mark.parametrize("mode", ["dense", "rowwise", "cascade"])
@pytest.mark.parametrize("fused", [True, False])
def test_modes_match_oracle(mode, fused):
    X, H, B, T = 5, 20, 3, 11
    params = _params(X, H)
    xs = jax.random.normal(jax.random.key(1), (B, T, X))
    h0 = jnp.zeros((B, H))
    ref, ref_all = gru.gru_reference(params, h0, xs, return_all=True)
    for dec in [True, False]:
        cfg = GRUConfig(input_dim=X, hidden_dim=H, matvec_mode=mode,
                        fused_gates=fused, decoupled_wx=dec)
        h, alls = gru.gru_sequence(params, h0, xs, cfg=cfg, return_all=True)
        np.testing.assert_allclose(np.asarray(h), np.asarray(ref),
                                   rtol=3e-5, atol=3e-6)
        np.testing.assert_allclose(np.asarray(alls), np.asarray(ref_all),
                                   rtol=3e-5, atol=3e-6)


def test_pallas_backend_matches():
    X, H, B, T = 5, 20, 2, 9
    params = _params(X, H)
    xs = jax.random.normal(jax.random.key(2), (B, T, X))
    h0 = jnp.zeros((B, H))
    ref, _ = gru.gru_reference(params, h0, xs)
    cfg = GRUConfig(input_dim=X, hidden_dim=H, backend="pallas")
    h, _ = gru.gru_sequence(params, h0, xs, cfg=cfg)
    np.testing.assert_allclose(np.asarray(h), np.asarray(ref),
                               rtol=3e-5, atol=3e-6)


def test_unroll_matches_scan():
    X, H, B, T = 4, 16, 2, 8
    params = _params(X, H)
    xs = jax.random.normal(jax.random.key(3), (B, T, X))
    h0 = jnp.zeros((B, H))
    a, _ = gru.gru_sequence(params, h0, xs, cfg=GRUConfig(X, H, unroll=1))
    b, _ = gru.gru_sequence(params, h0, xs, cfg=GRUConfig(X, H, unroll=4))
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


@settings(max_examples=20, deadline=None)
@given(st.integers(2, 48), st.integers(1, 16), st.integers(1, 12),
       st.integers(0, 10_000))
def test_hidden_state_bounded(H, X, T, seed):
    """|h| <= 1 always: h is a convex combo of h_prev and tanh(...)."""
    params = _params(X, H, key=seed % 97)
    xs = 3.0 * jax.random.normal(jax.random.key(seed), (1, T, X))
    h0 = jnp.zeros((1, H))
    for variant in ["v1", "v3"]:
        cfg = GRUConfig(input_dim=X, hidden_dim=H, variant=variant)
        h, alls = gru.gru_sequence(params, h0, xs, cfg=cfg, return_all=True)
        assert np.all(np.abs(np.asarray(alls)) <= 1.0 + 1e-6)
        assert np.isfinite(np.asarray(alls)).all()


@settings(max_examples=15, deadline=None)
@given(st.integers(2, 32), st.integers(1, 8), st.integers(0, 10_000))
def test_rowwise_equals_cascade(H, X, seed):
    params = _params(X, H, key=seed % 89)
    xs = jax.random.normal(jax.random.key(seed), (2, 5, X))
    h0 = jax.random.normal(jax.random.key(seed + 1), (2, H)) * 0.5
    outs = []
    for mode in ["dense", "rowwise", "cascade"]:
        cfg = GRUConfig(input_dim=X, hidden_dim=H, matvec_mode=mode)
        h, _ = gru.gru_sequence(params, h0, xs, cfg=cfg)
        outs.append(np.asarray(h))
    np.testing.assert_allclose(outs[0], outs[1], rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(outs[0], outs[2], rtol=2e-4, atol=2e-5)


def test_zero_update_gate_keeps_state():
    """With b_z -> -inf, z -> 0 and h stays at h0 (gate semantics)."""
    X, H = 3, 8
    params = _params(X, H)
    params = dict(params)
    params["b"] = params["b"].at[:H].set(-30.0)   # z gate bias
    xs = jax.random.normal(jax.random.key(5), (1, 6, X))
    h0 = jax.random.normal(jax.random.key(6), (1, H)) * 0.3
    h, _ = gru.gru_sequence(params, h0, xs, cfg=GRUConfig(X, H))
    np.testing.assert_allclose(np.asarray(h), np.asarray(h0), atol=1e-5)


def test_classifier_shapes_and_grads():
    from repro.configs.gru_jet import CONFIG
    params = init_params(gru.gru_classifier_specs(CONFIG.gru), jax.random.key(0))
    xs = jax.random.normal(jax.random.key(1), (4, 20, 5))
    logits = gru.gru_classify(params, xs, cfg=CONFIG.gru)
    assert logits.shape == (4, 5)

    def loss(p):
        return gru.gru_classify(p, xs, cfg=CONFIG.gru).sum()
    g = jax.grad(loss)(params)
    for leaf in jax.tree_util.tree_leaves(g):
        assert np.isfinite(np.asarray(leaf)).all()
