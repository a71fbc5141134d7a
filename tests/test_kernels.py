"""Per-kernel shape/dtype sweeps vs pure-jnp oracles (interpret=True)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attn import ref as fa_ref
from repro.kernels.flash_attn.kernel import flash_attention
from repro.kernels.gru_cell import ref as gc_ref
from repro.kernels.gru_cell.kernel import gru_step_blocked, gru_step_fused
from repro.kernels.gru_sequence import ref as gs_ref
from repro.kernels.gru_sequence.kernel import gru_sequence_kernel
from repro.kernels.rowwise_matvec import ops as mv_ops, ref as mv_ref


@pytest.mark.parametrize("B,K,N", [(1, 16, 32), (4, 96, 256), (8, 128, 128),
                                   (2, 64, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rowwise_and_cascade_matmul(B, K, N, dtype):
    x = jax.random.normal(jax.random.key(0), (B, K)).astype(dtype)
    w = jax.random.normal(jax.random.key(1), (K, N)).astype(dtype)
    ref = mv_ref.matmul_ref(x, w)
    tol = dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(
        rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(mv_ops.rowwise(x, w), np.float32),
                               np.asarray(ref), **tol)
    np.testing.assert_allclose(np.asarray(mv_ops.cascade(x, w), np.float32),
                               np.asarray(ref), **tol)


@pytest.mark.parametrize("B,H", [(1, 20), (2, 64), (3, 32)])
@pytest.mark.parametrize("variant", ["v1", "v3"])
def test_gru_cell_fused(B, H, variant):
    ks = jax.random.split(jax.random.key(0), 4)
    h = jax.random.normal(ks[0], (B, H))
    xp = jax.random.normal(ks[1], (B, 3 * H))
    u = jax.random.normal(ks[2], (H, 3 * H)) / np.sqrt(H)
    b = jax.random.normal(ks[3], (3 * H,)) * 0.1
    ref = gc_ref.gru_step_ref(h, xp, u, b, variant=variant)
    out = gru_step_fused(h, xp, u, b, variant=variant, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("H,block", [(64, 32), (64, 16), (128, 64)])
def test_gru_cell_blocked(H, block):
    B = 2
    ks = jax.random.split(jax.random.key(1), 4)
    h = jax.random.normal(ks[0], (B, H))
    xp = jax.random.normal(ks[1], (B, 3 * H))
    u = jax.random.normal(ks[2], (H, 3 * H)) / np.sqrt(H)
    b = jax.random.normal(ks[3], (3 * H,)) * 0.1
    ref = gc_ref.gru_step_ref(h, xp, u, b)
    out = gru_step_blocked(h, xp, u, b, block_n=block, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("T,B,H", [(1, 1, 20), (7, 2, 64), (13, 3, 32)])
def test_gru_sequence_kernel(T, B, H):
    ks = jax.random.split(jax.random.key(2), 4)
    h0 = jax.random.normal(ks[0], (B, H))
    xp = jax.random.normal(ks[1], (T, B, 3 * H))
    u = jax.random.normal(ks[2], (H, 3 * H)) / np.sqrt(H)
    b = jax.random.normal(ks[3], (3 * H,)) * 0.1
    ref = gs_ref.gru_sequence_ref(h0, xp, u, b)
    out = gru_sequence_kernel(h0, xp, u, b, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("Hq,Hkv,S,D", [(4, 2, 70, 16), (2, 2, 64, 32),
                                        (8, 2, 33, 16)])
@pytest.mark.parametrize("window", [0, 17])
def test_flash_attention(Hq, Hkv, S, D, window):
    B = 1
    q = jax.random.normal(jax.random.key(3), (B, Hq, S, D))
    k = jax.random.normal(jax.random.key(4), (B, Hkv, S, D))
    v = jax.random.normal(jax.random.key(5), (B, Hkv, S, D))
    out = flash_attention(q, k, v, causal=True, window=window,
                          block_q=32, block_k=32, interpret=True)
    ref = fa_ref.attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-4, atol=3e-5)


def test_flash_attention_bf16():
    B, Hq, Hkv, S, D = 1, 2, 1, 48, 16
    q = jax.random.normal(jax.random.key(6), (B, Hq, S, D), jnp.bfloat16)
    k = jax.random.normal(jax.random.key(7), (B, Hkv, S, D), jnp.bfloat16)
    v = jax.random.normal(jax.random.key(8), (B, Hkv, S, D), jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                          interpret=True)
    ref = fa_ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=5e-2, atol=5e-2)


def _masked_seq_case(family, depth):
    """Raw-array inputs of one fused sequence kernel (depth-L stack, T=6,
    B=3) -> (call(xp_t, mask_t), xp_t)."""
    from repro.kernels.gru_sequence.kernel import gru_stack_sequence_kernel
    from repro.kernels.slstm_cell.kernel import slstm_stack_sequence_kernel
    G = 3 if family == "gru" else 4
    L, B, H, T = depth, 3, 16, 6
    ks = jax.random.split(jax.random.key(depth), 5)
    xp = jax.random.normal(ks[0], (T, B, G * H))
    u = jax.random.normal(ks[1], (L, H, G * H)) / np.sqrt(H)
    wd = jax.random.normal(ks[2], (max(L - 1, 1), H, G * H)) / np.sqrt(H)
    b = jax.random.normal(ks[3], (L, G * H)) * 0.1
    if family == "gru":
        h0 = jax.random.normal(ks[4], (L, B, H)) * 0.5
        return (lambda x, m: gru_stack_sequence_kernel(
            h0, x, u, wd, b, m, interpret=True)[1]), xp
    z = jnp.zeros((L, B, H))
    m0 = jnp.full((L, B, H), -1e30)
    return (lambda x, m: jnp.stack(slstm_stack_sequence_kernel(
        z, z, m0, z, x, u, wd, b, m, interpret=True)[1:])), xp


@pytest.mark.parametrize("family", ["gru", "slstm"])
@pytest.mark.parametrize("depth", [1, 3])
def test_masked_sequence_kernel_layout_bitwise(family, depth):
    """The (T, B, 1) mask stream: an unmasked call, an all-live mask and a
    left-padded mask run one kernel program, so their final states are
    BITWISE equal (the bucketed-prefill contract on the kernels' raw
    interface)."""
    call, xp = _masked_seq_case(family, depth)
    T, B = xp.shape[:2]
    unmasked = call(xp, None)
    live = call(xp, jnp.ones((T, B)))
    P = 3
    padded = call(jnp.pad(xp, ((P, 0), (0, 0), (0, 0))),
                  (jnp.arange(T + P) >= P)[:, None] * jnp.ones((1, B)))
    np.testing.assert_array_equal(np.asarray(unmasked), np.asarray(live))
    np.testing.assert_array_equal(np.asarray(unmasked), np.asarray(padded))


def test_pick_batch_block_is_a_legal_tile():
    """A decode tile is the whole batch or a multiple-of-8 divisor of it:
    the only row counts the chip's kernel compiler accepts."""
    from repro.kernels import pick_batch_block
    for B in range(1, 1200):
        blk = pick_batch_block(B)
        assert B % blk == 0, (B, blk)
        assert blk == B or blk % 8 == 0, (B, blk)
        assert blk == B or blk <= 256, (B, blk)
    assert pick_batch_block(300) == 300          # no multiple-of-8 divisor
    assert pick_batch_block(512) == 256
    assert pick_batch_block(8) == 8
