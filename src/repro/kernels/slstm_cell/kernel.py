"""Fused sLSTM Pallas kernels: grid = time (sequence) / batch (decode),
weights pinned in VMEM, the exponential-gate stabilizer carried per step.

Same structure as :mod:`repro.kernels.gru_sequence.kernel`, adapted to the
sLSTM family's four-leaf state: a depth-L stack runs as ONE ``pallas_call``
whose recurrent state — cell ``c``, normalizer ``n``, stabilizer ``m`` and
hidden ``h`` per layer — lives in four ``(L, B, H)`` VMEM scratch buffers
across grid steps. All layers' recurrent matrices U (``(L, H, 4H)``) and
the deep layers' input projections use constant ``index_map``s, so the
Pallas pipeline fetches them from HBM exactly once; per sequence step only
the ``(1, B, 4H)`` slice of the precomputed layer-0 ``W.x`` streams in.

The stabilizer is the part that makes sLSTM more than a re-gated GRU: the
exponential input/forget gates are only finite because ``m`` tracks their
running log-scale max, and it is genuinely recurrent state — it rides in
VMEM scratch next to ``h``, is frozen by the mask on padded rows, and is
returned per layer so decode can continue a prefilled sequence exactly.

``slstm_stack_decode_kernel`` is the latency path: one grid step of the
same fused structure advancing a whole batch through all L layers for ONE
token, batch-tiled with ``dimension_semantics=("parallel",)`` (megacore
may split independent tiles across cores), weights resident across tiles.

The sequence kernel streams a (T, B) mask (all-ones when the caller
passes none) one (1, B, 1) block per step (``repro.kernels.step_mask``):
False rows keep ALL FOUR state leaves (``where`` selects, it does not
perturb), so bucketed left-padded prefill runs the fused kernel
bitwise-identical to unpadded prompts.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import pick_batch_block, step_mask, step_mask_spec


def _dot(a, b):
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _gate_math(c, n, m, h, xp, u, b):
    """One sLSTM cell update in fp32 (matches
    ``repro.core.slstm.slstm_gate_math`` op for op). c/n/m/h: (B,H);
    xp: (B,4H); u: (H,4H); b: (1,4H). Gate order [z, i, f, o]."""
    H = h.shape[-1]
    g = xp + _dot(h.astype(u.dtype), u) + b              # (B, 4H) fused gates
    z, i = g[:, :H], g[:, H:2 * H]
    f, o = g[:, 2 * H:3 * H], g[:, 3 * H:]
    logf = jax.nn.log_sigmoid(f)
    m_new = jnp.maximum(logf + m, i)
    i_ = jnp.exp(i - m_new)
    f_ = jnp.exp(logf + m - m_new)
    c_new = f_ * c + i_ * jnp.tanh(z)
    n_new = f_ * n + i_
    h_new = jax.nn.sigmoid(o) * c_new / jnp.maximum(n_new, 1e-6)
    return c_new, n_new, m_new, h_new


def _store(refs, l, leaves):
    for ref, leaf in zip(refs, leaves):
        ref[l] = leaf


def _stack_kernel(c0_ref, n0_ref, m0_ref, h0_ref, xp_ref, u_ref, wd_ref,
                  b_ref, k_ref, o_ref, cT_ref, nT_ref, mT_ref, hT_ref,
                  c_s, n_s, m_s, h_s, *, num_layers: int):
    """Fused stack, one time step: ONE shared (1, B, 1) mask block freezes
    every layer's FOUR state leaves on False rows (the stabilizer must
    freeze with the gates, or live steps after padding would see a wrong
    log-scale max). Live rows run exactly the all-live arithmetic."""
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        c_s[...] = c0_ref[...].astype(jnp.float32)
        n_s[...] = n0_ref[...].astype(jnp.float32)
        m_s[...] = m0_ref[...].astype(jnp.float32)
        h_s[...] = h0_ref[...].astype(jnp.float32)

    b = b_ref[...].astype(jnp.float32)                    # (L, 4H)
    xp = xp_ref[...][0].astype(jnp.float32)               # (B, 4H): layer-0 Wx
    keep = k_ref[0] != 0.0                                # (B, 1) this step
    for l in range(num_layers):                           # static unroll
        new = _gate_math(c_s[l], n_s[l], m_s[l], h_s[l], xp, u_ref[l],
                         b[l:l + 1])
        new = tuple(jnp.where(keep, a, s[l])
                    for a, s in zip(new, (c_s, n_s, m_s, h_s)))
        _store((c_s, n_s, m_s, h_s), l, new)
        if l + 1 < num_layers:
            # next layer's input projection, same timestep, stays in VMEM
            xp = _dot(new[3].astype(wd_ref.dtype), wd_ref[l])
    o_ref[...] = new[3][None].astype(o_ref.dtype)
    cT_ref[...] = c_s[...].astype(cT_ref.dtype)
    nT_ref[...] = n_s[...].astype(nT_ref.dtype)
    mT_ref[...] = m_s[...].astype(mT_ref.dtype)
    hT_ref[...] = h_s[...].astype(hT_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def slstm_stack_sequence_kernel(c0: jax.Array, n0: jax.Array, m0: jax.Array,
                                h0: jax.Array, x_proj: jax.Array,
                                u: jax.Array, w_deep: jax.Array, b: jax.Array,
                                mask=None, *, interpret: bool = False):
    """Depth-L fused sLSTM stack (uniform hidden size H across layers).

    c0/n0/m0/h0: (L,B,H) per-layer initial state leaves; x_proj: (T,B,4H)
    time-major precomputed layer-0 Wx; u: (L,H,4H); w_deep: (L-1,H,4H)
    deep-layer input projections ((1,1,4H) zeros for L=1, unused);
    b: (L,4H). Returns (last-layer h states (T,B,H), then the four
    per-layer final leaves cT/nT/mT/hT, each (L,B,H)).

    ``mask`` (T,B) float (nonzero = live step; None = all live): streamed
    one step per grid block; False steps freeze every layer's c/n/m/h
    in-kernel (bucketed prefill runs the fused kernel, no XLA fallback).
    """
    T, B, H4 = x_proj.shape
    H = H4 // 4
    L = h0.shape[0]
    Ld = max(L - 1, 1)
    state_spec = pl.BlockSpec((L, B, H), lambda t: (0, 0, 0))  # resident
    fin = jax.ShapeDtypeStruct((L, B, H), h0.dtype)
    hs, cT, nT, mT, hT = pl.pallas_call(
        functools.partial(_stack_kernel, num_layers=L),
        grid=(T,),
        in_specs=[
            state_spec, state_spec, state_spec, state_spec,
            pl.BlockSpec((1, B, 4 * H), lambda t: (t, 0, 0)),  # stream step t
            pl.BlockSpec((L, H, 4 * H), lambda t: (0, 0, 0)),  # all U: ONCE
            pl.BlockSpec((Ld,) + w_deep.shape[1:], lambda t: (0, 0, 0)),
            pl.BlockSpec((L, 4 * H), lambda t: (0, 0)),
            step_mask_spec(B),                                 # step t's mask
        ],
        out_specs=[pl.BlockSpec((1, B, H), lambda t: (t, 0, 0))]
        + [pl.BlockSpec((L, B, H), lambda t: (0, 0, 0))] * 4,
        out_shape=[jax.ShapeDtypeStruct((T, B, H), h0.dtype),
                   fin, fin, fin, fin],
        scratch_shapes=[pltpu.VMEM((L, B, H), jnp.float32)
                        for _ in range(4)],                # carried c/n/m/h
        interpret=interpret,
    )(c0, n0, m0, h0, x_proj, u, w_deep, b, step_mask(mask, T, B))
    return hs, cT, nT, mT, hT


# ---------------------------------------------------------------------------
# fused decode step (the latency path)
# ---------------------------------------------------------------------------

def _decode_kernel(c_ref, n_ref, m_ref, h_ref, xp_ref, u_ref, wd_ref, b_ref,
                   co_ref, no_ref, mo_ref, ho_ref, *, num_layers: int):
    """One token through all L layers for one batch tile. Weights resident;
    layer l+1 consumes layer l's same-token hidden state straight from
    registers (nothing round-trips through HBM)."""
    b = b_ref[...].astype(jnp.float32)                    # (L, 4H)
    xp = xp_ref[...].astype(jnp.float32)                  # (Bt, 4H)
    for l in range(num_layers):                           # static unroll
        new = _gate_math(c_ref[l].astype(jnp.float32),
                         n_ref[l].astype(jnp.float32),
                         m_ref[l].astype(jnp.float32),
                         h_ref[l].astype(jnp.float32),
                         xp, u_ref[l], b[l:l + 1])
        co_ref[l] = new[0].astype(co_ref.dtype)
        no_ref[l] = new[1].astype(no_ref.dtype)
        mo_ref[l] = new[2].astype(mo_ref.dtype)
        ho_ref[l] = new[3].astype(ho_ref.dtype)
        if l + 1 < num_layers:
            xp = _dot(new[3].astype(wd_ref.dtype), wd_ref[l])


@functools.partial(jax.jit, static_argnames=("batch_block", "interpret"))
def slstm_stack_decode_kernel(c: jax.Array, n: jax.Array, m: jax.Array,
                              h: jax.Array, x_proj: jax.Array, u: jax.Array,
                              w_deep: jax.Array, b: jax.Array, *,
                              batch_block: int = 0, interpret: bool = False):
    """Fused decode step for a depth-L sLSTM stack (uniform hidden size).

    c/n/m/h: (L,B,H) per-layer state leaves; x_proj: (B,4H) precomputed
    layer-0 Wx for the ONE new token; u: (L,H,4H); w_deep: (L-1,H,4H)
    ((1,1,4H) zeros for L=1, unused); b: (L,4H). Returns the four new
    per-layer leaves (L,B,H) each.

    Grid = batch tiles (``batch_block`` rows each, 0 = auto): weights use
    constant index_maps (fetched once regardless of tile count) and the
    tiles carry no cross-tile state, so the axis is ``parallel``.
    """
    L, B, H = h.shape
    Bt = batch_block or pick_batch_block(B)
    assert B % Bt == 0, (B, Bt)
    Ld = max(L - 1, 1)
    tile = pl.BlockSpec((L, Bt, H), lambda i: (0, i, 0))
    out = jax.ShapeDtypeStruct((L, B, H), h.dtype)
    return pl.pallas_call(
        functools.partial(_decode_kernel, num_layers=L),
        grid=(B // Bt,),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        in_specs=[
            tile, tile, tile, tile,                        # this batch tile
            pl.BlockSpec((Bt, 4 * H), lambda i: (i, 0)),   # its Wx slab
            pl.BlockSpec((L, H, 4 * H), lambda i: (0, 0, 0)),  # all U: ONCE
            pl.BlockSpec((Ld,) + w_deep.shape[1:], lambda i: (0, 0, 0)),
            pl.BlockSpec((L, 4 * H), lambda i: (0, 0)),
        ],
        out_specs=[tile, tile, tile, tile],
        out_shape=[out, out, out, out],
        interpret=interpret,
    )(c, n, m, h, x_proj, u, w_deep, b)
