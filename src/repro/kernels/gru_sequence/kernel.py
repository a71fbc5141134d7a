"""Whole-sequence GRU Pallas kernels: grid = time, U pinned in VMEM.

The paper's "row reuse": after the first pass, the vector (and here the
recurrent matrix U) lives in tile-local memory, so subsequent steps are
bounded by local-memory bandwidth, not streaming. TPU translation: the
sequence runs as ONE ``pallas_call`` whose grid axis is time. U's
``index_map`` is constant, so the Pallas pipeline fetches it from HBM
exactly once; the hidden state is carried in a VMEM scratch buffer across
grid steps (TPU grids iterate sequentially). Per step, only the
(1, B, 3H) slice of the precomputed input projection streams in — the
decoupled ``W.x`` path feeding the free-running recurrence.

``gru_stack_sequence_kernel`` extends this to a depth-L stack in ONE
``pallas_call``: ALL layers' U matrices (and the deep layers' input
projections W) are pinned in VMEM via constant index_maps, and the L
per-layer hidden states live in one (L, B, H) scratch buffer. Each grid
step runs the whole depth — layer l consumes layer l-1's same-timestep
output directly from registers/VMEM, so an L-layer stack costs one kernel
launch and one weight fetch total, instead of L sequential pallas_calls
with L hidden-state round-trips through HBM.

``gru_stack_decode_kernel`` is the latency-constrained serve path: ONE
grid step of the same fused-stack structure, advancing a whole batch of
per-layer hidden states through all L layers for ONE token. The grid axis
is the BATCH (tiled), not time — weights stay pinned via constant
index_maps while successive batch tiles stream through, so wave size
scales past a single VMEM block without re-fetching a byte of U/W. The
batch tiles are mutually independent, so the grid axis is declared
``dimension_semantics=("parallel",)``: on a megacore TPU the Mosaic
compiler may split the tiles across both cores instead of iterating them
sequentially (time grids, by contrast, are ``"arbitrary"`` — the hidden
state carried in scratch makes them order-dependent). This is the paper's
figure of merit (single-step latency) with the AIE weight-residency story
intact on TPU.

Every sequence kernel streams a (T, B) length MASK (all-ones when the
caller passes none) through the grid, one (1, B, 1) block per step next to
the input projection (layout: ``repro.kernels.step_mask``): False steps
freeze the hidden state (every layer's, for the stack) with an in-kernel
select, so bucketed left-padded prefill runs the fused kernels — and since
masked and unmasked calls are one kernel program, live rows execute
bit-identical arithmetic to unpadded prompts.

SHARD-SHAPED entry points (``gru_rowwise_shard_*`` / ``gru_cascade_shard_*``
/ ``gru_shard_matvec``) are the ``pallas_sharded`` backend's kernels: each
one computes exactly the per-shard segment of a GRU step that fits BETWEEN
two collectives of the row-parallel / cascade shard_map programs in
``repro.core.rowparallel`` — the AIE4ML pattern of a per-tile kernel nested
under a global dataflow partition. A rowwise v3 step is ONE kernel per
layer (trailing all-gather outside); paper-math v1 splits at the mid-step
``r*h`` aggregation into a z/r kernel and a candidate kernel; cascade
steps split at their psum(s). The kernel bodies mirror the XLA shard-step
expressions op for op (and elementwise phases commute with the local gate
slicing), so on the same shard shapes the ``pallas_sharded`` backend is
bitwise-equal to the XLA ``sharded`` shard bodies.
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


def _gate_math(h, xp, u, b, variant: str):
    """One cell update in fp32. h/xp: (B,H)/(B,3H), u: (H,3H), b: (1,3H)."""
    H = h.shape[-1]
    xz, xr, xh = xp[:, :H], xp[:, H:2 * H], xp[:, 2 * H:]
    if variant == "v3":
        ua = _dot(h.astype(u.dtype), u) + b
        z = jax.nn.sigmoid(xz + ua[:, :H])
        r = jax.nn.sigmoid(xr + ua[:, H:2 * H])
        ht = jnp.tanh(xh + r * ua[:, 2 * H:])
    else:
        zr = _dot(h.astype(u.dtype), u[:, :2 * H]) + b[:, :2 * H]
        z = jax.nn.sigmoid(xz + zr[:, :H])
        r = jax.nn.sigmoid(xr + zr[:, H:])
        ht = jnp.tanh(xh + _dot((r * h).astype(u.dtype), u[:, 2 * H:])
                      + b[:, 2 * H:])
    return (1.0 - z) * h + z * ht


def _seq_kernel(h0_ref, xp_ref, u_ref, b_ref, m_ref, o_ref, h_s, *,
                variant: str):
    """One time step: the (1, B, 1) mask block streams in next to the
    step's input projection; False rows keep their previous hidden state.
    Live rows run EXACTLY the arithmetic of an all-live call (``where``
    selects, it does not perturb), so left-padded bucketed prompts stay
    bitwise-identical to their unpadded originals."""
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        h_s[...] = h0_ref[...].astype(jnp.float32)

    xp = xp_ref[...][0].astype(jnp.float32)               # (B, 3H) this step
    keep = m_ref[0] != 0.0                                # (B, 1) this step
    h_new = _gate_math(h_s[...], xp, u_ref[...],
                       b_ref[...].astype(jnp.float32), variant)
    h_new = jnp.where(keep, h_new, h_s[...])              # freeze masked rows
    h_s[...] = h_new
    o_ref[...] = h_new[None].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("variant", "interpret"))
def gru_sequence_kernel(h0: jax.Array, x_proj: jax.Array, u: jax.Array,
                        b: jax.Array, mask=None, *, variant: str = "v1",
                        interpret: bool = False) -> jax.Array:
    """h0: (B,H), x_proj: (T,B,3H) time-major precomputed Wx, u: (H,3H),
    b: (3H,) -> all hidden states (T,B,H).

    ``mask`` (T,B) float (nonzero = live step; None = all live): streamed
    through the grid one step per block; False steps freeze the hidden
    state in-kernel, so bucketed (left-padded) prefill runs the SAME fused
    kernel as unpadded prompts instead of falling back to the XLA scan."""
    T, B, H3 = x_proj.shape
    H = H3 // 3
    return pl.pallas_call(
        functools.partial(_seq_kernel, variant=variant),
        grid=(T,),
        in_specs=[
            pl.BlockSpec((B, H), lambda t: (0, 0)),        # h0: resident
            pl.BlockSpec((1, B, 3 * H), lambda t: (t, 0, 0)),  # stream step t
            pl.BlockSpec((H, 3 * H), lambda t: (0, 0)),    # U: fetched ONCE
            pl.BlockSpec((1, 3 * H), lambda t: (0, 0)),
            step_mask_spec(B),                             # step t's mask
        ],
        out_specs=pl.BlockSpec((1, B, H), lambda t: (t, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((T, B, H), h0.dtype),
        scratch_shapes=[pltpu.VMEM((B, H), jnp.float32)],  # carried hidden state
        interpret=interpret,
    )(h0, x_proj, u, b[None, :], step_mask(mask, T, B))


# ---------------------------------------------------------------------------
# fused multi-layer stack
# ---------------------------------------------------------------------------

def _stack_kernel(h0_ref, xp_ref, u_ref, wd_ref, b_ref, m_ref, o_ref,
                  hT_ref, h_s, *, variant: str, num_layers: int):
    """Fused stack, one time step: ONE shared (1, B, 1) mask block freezes
    EVERY layer's state on False rows (exact — during frozen steps upper
    layers ignore their input). The next layer consumes the GATED output,
    matching the layer-by-layer masked semantics of the XLA path."""
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        h_s[...] = h0_ref[...].astype(jnp.float32)

    b = b_ref[...].astype(jnp.float32)                    # (L, 3H)
    xp = xp_ref[...][0].astype(jnp.float32)               # (B, 3H): layer 0 Wx
    keep = m_ref[0] != 0.0                                # (B, 1) this step
    for l in range(num_layers):                           # static unroll
        h_new = _gate_math(h_s[l], xp, u_ref[l], b[l:l + 1], variant)
        h_new = jnp.where(keep, h_new, h_s[l])            # freeze masked rows
        h_s[l] = h_new
        if l + 1 < num_layers:
            # next layer's input projection, same timestep, never leaves VMEM
            xp = _dot(h_new.astype(wd_ref.dtype), wd_ref[l]).astype(jnp.float32)
    o_ref[...] = h_new[None].astype(o_ref.dtype)
    hT_ref[...] = h_s[...].astype(hT_ref.dtype)


@functools.partial(jax.jit, static_argnames=("variant", "interpret"))
def gru_stack_sequence_kernel(h0: jax.Array, x_proj: jax.Array, u: jax.Array,
                              w_deep: jax.Array, b: jax.Array, mask=None, *,
                              variant: str = "v1", interpret: bool = False):
    """Depth-L fused stack (uniform hidden size H across layers).

    h0: (L,B,H) per-layer initial states; x_proj: (T,B,3H) time-major
    precomputed layer-0 Wx; u: (L,H,3H) recurrent matrices; w_deep:
    (L-1,H,3H) input projections of layers 1..L-1 (pass (1,1,3H) zeros for
    L=1, unused); b: (L,3H). Returns (last-layer states (T,B,H),
    per-layer final states (L,B,H)).

    ``mask`` (T,B) float (nonzero = live step; None = all live): streamed
    one step per grid block; False steps freeze every layer's hidden state
    in-kernel (bucketed prefill runs the fused kernel, no XLA fallback).
    """
    T, B, H3 = x_proj.shape
    H = H3 // 3
    L = h0.shape[0]
    Ld = max(L - 1, 1)
    hs, hT = pl.pallas_call(
        functools.partial(_stack_kernel, variant=variant, num_layers=L),
        grid=(T,),
        in_specs=[
            pl.BlockSpec((L, B, H), lambda t: (0, 0, 0)),      # h0: resident
            pl.BlockSpec((1, B, 3 * H), lambda t: (t, 0, 0)),  # stream step t
            pl.BlockSpec((L, H, 3 * H), lambda t: (0, 0, 0)),  # all U: ONCE
            pl.BlockSpec((Ld,) + w_deep.shape[1:], lambda t: (0, 0, 0)),
            pl.BlockSpec((L, 3 * H), lambda t: (0, 0)),
            step_mask_spec(B),                                 # step t's mask
        ],
        out_specs=[
            pl.BlockSpec((1, B, H), lambda t: (t, 0, 0)),
            pl.BlockSpec((L, B, H), lambda t: (0, 0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((T, B, H), h0.dtype),
                   jax.ShapeDtypeStruct((L, B, H), h0.dtype)],
        scratch_shapes=[pltpu.VMEM((L, B, H), jnp.float32)],  # per-layer h
        interpret=interpret,
    )(h0, x_proj, u, w_deep, b, step_mask(mask, T, B))
    return hs, hT


# ---------------------------------------------------------------------------
# fused multi-layer decode step (the latency path)
# ---------------------------------------------------------------------------

def _decode_kernel(h_ref, xp_ref, u_ref, wd_ref, b_ref, o_ref, *,
                   variant: str, num_layers: int):
    """One token through all L layers for one batch tile. Weights resident;
    layer l+1 consumes layer l's same-token output straight from registers
    (nothing round-trips through HBM)."""
    b = b_ref[...].astype(jnp.float32)                    # (L, 3H)
    xp = xp_ref[...].astype(jnp.float32)                  # (Bt, 3H): layer-0 Wx
    for l in range(num_layers):                           # static unroll
        h_new = _gate_math(h_ref[l].astype(jnp.float32), xp, u_ref[l],
                           b[l:l + 1], variant)
        o_ref[l] = h_new.astype(o_ref.dtype)
        if l + 1 < num_layers:
            xp = _dot(h_new.astype(wd_ref.dtype), wd_ref[l]).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("variant", "batch_block",
                                             "interpret"))
def gru_stack_decode_kernel(h: jax.Array, x_proj: jax.Array, u: jax.Array,
                            w_deep: jax.Array, b: jax.Array, *,
                            variant: str = "v1", batch_block: int = 0,
                            interpret: bool = False) -> jax.Array:
    """Fused decode step for a depth-L stack (uniform hidden size H).

    h: (L,B,H) per-layer hidden states; x_proj: (B,3H) precomputed layer-0
    Wx for the ONE new token; u: (L,H,3H); w_deep: (L-1,H,3H) deep-layer
    input projections ((1,1,3H) zeros for L=1, unused); b: (L,3H).
    Returns the new per-layer states (L,B,H).

    Grid = batch tiles (``batch_block`` rows each, 0 = auto): all weights
    use constant index_maps so the Pallas pipeline fetches them from HBM
    once regardless of how many tiles stream through. The tiles carry no
    cross-tile state, so the axis is marked ``parallel`` (megacore: big
    waves may run tiles on both TPU cores per chip).
    """
    L, B, H = h.shape
    Bt = batch_block or pick_batch_block(B)
    assert B % Bt == 0, (B, Bt)
    Ld = max(L - 1, 1)
    return pl.pallas_call(
        functools.partial(_decode_kernel, variant=variant, num_layers=L),
        grid=(B // Bt,),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        in_specs=[
            pl.BlockSpec((L, Bt, H), lambda i: (0, i, 0)),     # this batch tile
            pl.BlockSpec((Bt, 3 * H), lambda i: (i, 0)),       # its Wx slab
            pl.BlockSpec((L, H, 3 * H), lambda i: (0, 0, 0)),  # all U: ONCE
            pl.BlockSpec((Ld,) + w_deep.shape[1:], lambda i: (0, 0, 0)),
            pl.BlockSpec((L, 3 * H), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((L, Bt, H), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((L, B, H), h.dtype),
        interpret=interpret,
    )(h, x_proj, u, w_deep, b)


# ---------------------------------------------------------------------------
# q8 datapath: int8 weight rows, int32 accumulation, dequant at the bias add
# ---------------------------------------------------------------------------
#
# The paper's AIE lanes MAC int8 weight ROWS against the activation vector;
# these kernels keep that layout literally. U (and the deep layers' W) are
# stored TRANSPOSED, (3H, H) int8 — one contiguous row per output element,
# quantized per row (``repro.core.params.quantize_rows_int8``), so the int8
# reduction runs over contiguous memory and the VMEM-resident weight
# footprint is a quarter of f32 (the depth x H range that stays resident
# roughly quadruples — the AIE local-memory story). Activations use the
# FIXED scale 127 (h and r*h live in (-1,1) — see params.py): quantization
# inside the kernel is one round+clip, no dynamic range scan, and the
# per-row dequant is one multiply folded into the bias add
# (``acc * eff + b`` with ``eff = scale_row / 127`` precomputed at
# prepare() time).


def _doti(a, b):
    """int8 x int8 -> int32, contracting the CONTIGUOUS last axes:
    a (B, K) against row-major weights (N, K)."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.int32)


def _q8_act(a):
    """Fixed-scale activation quantization: f32 in [-1, 1] -> int8."""
    return jnp.clip(jnp.round(a * 127.0), -127.0, 127.0).astype(jnp.int8)


def _gate_math_q8(h, xp, uq, eff, b, variant: str):
    """One q8 cell update. h: (B,H) f32 state, xp: (B,3H) f32 input
    projection, uq: (3H,H) int8 weight rows, eff/b: (1,3H) f32 per-row
    dequant scales (activation scale folded) and bias."""
    H = h.shape[-1]
    xz, xr, xh = xp[:, :H], xp[:, H:2 * H], xp[:, 2 * H:]
    hq = _q8_act(h)
    if variant == "v3":
        ua = _doti(hq, uq).astype(jnp.float32) * eff + b
        z = jax.nn.sigmoid(xz + ua[:, :H])
        r = jax.nn.sigmoid(xr + ua[:, H:2 * H])
        ht = jnp.tanh(xh + r * ua[:, 2 * H:])
    else:
        zr = (_doti(hq, uq[:2 * H]).astype(jnp.float32) * eff[:, :2 * H]
              + b[:, :2 * H])
        z = jax.nn.sigmoid(xz + zr[:, :H])
        r = jax.nn.sigmoid(xr + zr[:, H:])
        cand = (_doti(_q8_act(r * h), uq[2 * H:]).astype(jnp.float32)
                * eff[:, 2 * H:] + b[:, 2 * H:])
        ht = jnp.tanh(xh + cand)
    return (1.0 - z) * h + z * ht


def _seq_kernel_q8(h0_ref, xp_ref, uq_ref, eff_ref, b_ref, m_ref, o_ref,
                   h_s, *, variant: str):
    """q8 sequence step: identical freeze semantics to the f32 kernel
    (``where`` selects, it does not perturb — and the quantized arithmetic
    of live rows is independent of dead rows), so bucketed left-padded
    prompts stay bitwise-identical to their unpadded q8 originals."""
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        h_s[...] = h0_ref[...].astype(jnp.float32)

    xp = xp_ref[...][0].astype(jnp.float32)               # (B, 3H) this step
    keep = m_ref[0] != 0.0                                # (B, 1) this step
    h_new = _gate_math_q8(h_s[...], xp, uq_ref[...], eff_ref[...],
                          b_ref[...].astype(jnp.float32), variant)
    h_new = jnp.where(keep, h_new, h_s[...])              # freeze masked rows
    h_s[...] = h_new
    o_ref[...] = h_new[None].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("variant", "interpret"))
def gru_sequence_q8_kernel(h0: jax.Array, x_proj: jax.Array, u_q: jax.Array,
                           u_eff: jax.Array, b: jax.Array, mask=None, *,
                           variant: str = "v1",
                           interpret: bool = False) -> jax.Array:
    """q8 twin of :func:`gru_sequence_kernel`. h0: (B,H), x_proj: (T,B,3H)
    f32 time-major Wx, u_q: (3H,H) int8 weight rows (pinned in VMEM at a
    quarter of the f32 footprint), u_eff: (3H,) f32 per-row dequant
    scales, b: (3H,), mask: (T,B) or None -> all hidden states (T,B,H)
    f32."""
    T, B, H3 = x_proj.shape
    H = H3 // 3
    return pl.pallas_call(
        functools.partial(_seq_kernel_q8, variant=variant),
        grid=(T,),
        in_specs=[
            pl.BlockSpec((B, H), lambda t: (0, 0)),            # h0: resident
            pl.BlockSpec((1, B, 3 * H), lambda t: (t, 0, 0)),  # stream step t
            pl.BlockSpec((3 * H, H), lambda t: (0, 0)),        # int8 U: ONCE
            pl.BlockSpec((1, 3 * H), lambda t: (0, 0)),
            pl.BlockSpec((1, 3 * H), lambda t: (0, 0)),
            step_mask_spec(B),                                 # step t's mask
        ],
        out_specs=pl.BlockSpec((1, B, H), lambda t: (t, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((T, B, H), h0.dtype),
        scratch_shapes=[pltpu.VMEM((B, H), jnp.float32)],
        interpret=interpret,
    )(h0, x_proj, u_q, u_eff[None, :], b[None, :], step_mask(mask, T, B))


def _stack_kernel_q8(h0_ref, xp_ref, uq_ref, eff_ref, wdq_ref, wde_ref,
                     b_ref, m_ref, o_ref, hT_ref, h_s, *, variant: str,
                     num_layers: int):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        h_s[...] = h0_ref[...].astype(jnp.float32)

    b = b_ref[...].astype(jnp.float32)                    # (L, 3H)
    eff = eff_ref[...]                                    # (L, 3H)
    xp = xp_ref[...][0].astype(jnp.float32)               # (B, 3H): layer 0 Wx
    keep = m_ref[0] != 0.0                                # (B, 1) this step
    for l in range(num_layers):                           # static unroll
        h_new = _gate_math_q8(h_s[l], xp, uq_ref[l], eff[l:l + 1],
                              b[l:l + 1], variant)
        h_new = jnp.where(keep, h_new, h_s[l])            # freeze masked rows
        h_s[l] = h_new
        if l + 1 < num_layers:
            # deep input projection: int8 rows too (h_new is in (-1,1))
            xp = (_doti(_q8_act(h_new), wdq_ref[l]).astype(jnp.float32)
                  * wde_ref[l][None])
    o_ref[...] = h_new[None].astype(o_ref.dtype)
    hT_ref[...] = h_s[...].astype(hT_ref.dtype)


@functools.partial(jax.jit, static_argnames=("variant", "interpret"))
def gru_stack_sequence_q8_kernel(h0: jax.Array, x_proj: jax.Array,
                                 u_q: jax.Array, u_eff: jax.Array,
                                 wd_q: jax.Array, wd_eff: jax.Array,
                                 b: jax.Array, mask=None, *,
                                 variant: str = "v1",
                                 interpret: bool = False):
    """q8 twin of :func:`gru_stack_sequence_kernel` (uniform hidden size).

    h0: (L,B,H); x_proj: (T,B,3H) f32 layer-0 Wx; u_q: (L,3H,H) int8
    weight rows with u_eff: (L,3H) dequant scales; wd_q: (L-1,3H,H) int8
    deep-layer input projections with wd_eff: (L-1,3H) (pass the
    ``quantize_gru_cells`` placeholders for L=1, unused); b: (L,3H);
    mask: (T,B) or None. Returns (last-layer states (T,B,H), per-layer
    finals (L,B,H))."""
    T, B, H3 = x_proj.shape
    H = H3 // 3
    L = h0.shape[0]
    Ld = max(L - 1, 1)
    hs, hT = pl.pallas_call(
        functools.partial(_stack_kernel_q8, variant=variant, num_layers=L),
        grid=(T,),
        in_specs=[
            pl.BlockSpec((L, B, H), lambda t: (0, 0, 0)),      # h0: resident
            pl.BlockSpec((1, B, 3 * H), lambda t: (t, 0, 0)),  # stream step t
            pl.BlockSpec((L, 3 * H, H), lambda t: (0, 0, 0)),  # int8 U: ONCE
            pl.BlockSpec((L, 3 * H), lambda t: (0, 0)),
            pl.BlockSpec((Ld,) + wd_q.shape[1:], lambda t: (0, 0, 0)),
            pl.BlockSpec((Ld, 3 * H), lambda t: (0, 0)),
            pl.BlockSpec((L, 3 * H), lambda t: (0, 0)),
            step_mask_spec(B),                                 # step t's mask
        ],
        out_specs=[
            pl.BlockSpec((1, B, H), lambda t: (t, 0, 0)),
            pl.BlockSpec((L, B, H), lambda t: (0, 0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((T, B, H), h0.dtype),
                   jax.ShapeDtypeStruct((L, B, H), h0.dtype)],
        scratch_shapes=[pltpu.VMEM((L, B, H), jnp.float32)],
        interpret=interpret,
    )(h0, x_proj, u_q, u_eff, wd_q, wd_eff, b, step_mask(mask, T, B))
    return hs, hT


def _decode_kernel_q8(h_ref, xp_ref, uq_ref, eff_ref, wdq_ref, wde_ref,
                      b_ref, o_ref, *, variant: str, num_layers: int):
    """One token through all L layers for one batch tile, int8 weights
    resident (a quarter of the f32 VMEM footprint — the paper's
    local-memory residency at AIE precision)."""
    b = b_ref[...].astype(jnp.float32)                    # (L, 3H)
    eff = eff_ref[...]                                    # (L, 3H)
    xp = xp_ref[...].astype(jnp.float32)                  # (Bt, 3H)
    for l in range(num_layers):                           # static unroll
        h_new = _gate_math_q8(h_ref[l].astype(jnp.float32), xp,
                              uq_ref[l], eff[l:l + 1], b[l:l + 1], variant)
        o_ref[l] = h_new.astype(o_ref.dtype)
        if l + 1 < num_layers:
            xp = (_doti(_q8_act(h_new), wdq_ref[l]).astype(jnp.float32)
                  * wde_ref[l][None])


@functools.partial(jax.jit, static_argnames=("variant", "batch_block",
                                             "interpret"))
def gru_stack_decode_q8_kernel(h: jax.Array, x_proj: jax.Array,
                               u_q: jax.Array, u_eff: jax.Array,
                               wd_q: jax.Array, wd_eff: jax.Array,
                               b: jax.Array, *, variant: str = "v1",
                               batch_block: int = 0,
                               interpret: bool = False) -> jax.Array:
    """q8 twin of :func:`gru_stack_decode_kernel` — the latency path at the
    paper's precision. h: (L,B,H) f32 states; x_proj: (B,3H) f32 layer-0
    Wx; u_q/u_eff, wd_q/wd_eff, b as in the q8 sequence kernel. Returns
    the new per-layer states (L,B,H) f32 (the state itself stays f32: the
    convex update accumulates full precision; only the matvecs are int8)."""
    L, B, H = h.shape
    Bt = batch_block or pick_batch_block(B)
    assert B % Bt == 0, (B, Bt)
    Ld = max(L - 1, 1)
    return pl.pallas_call(
        functools.partial(_decode_kernel_q8, variant=variant, num_layers=L),
        grid=(B // Bt,),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        in_specs=[
            pl.BlockSpec((L, Bt, H), lambda i: (0, i, 0)),     # batch tile
            pl.BlockSpec((Bt, 3 * H), lambda i: (i, 0)),       # its Wx slab
            pl.BlockSpec((L, 3 * H, H), lambda i: (0, 0, 0)),  # int8 U: ONCE
            pl.BlockSpec((L, 3 * H), lambda i: (0, 0)),
            pl.BlockSpec((Ld,) + wd_q.shape[1:], lambda i: (0, 0, 0)),
            pl.BlockSpec((Ld, 3 * H), lambda i: (0, 0)),
            pl.BlockSpec((L, 3 * H), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((L, Bt, H), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((L, B, H), h.dtype),
        interpret=interpret,
    )(h, x_proj, u_q, u_eff, wd_q, wd_eff, b)


# ---------------------------------------------------------------------------
# shard-shaped step kernels (the pallas_sharded backend's per-tile programs)
# ---------------------------------------------------------------------------
#
# Each kernel is the largest contiguous per-shard compute segment between
# two collectives of the shard_map GRU step; no grid (one whole-block
# invocation per call — the operands already ARE one shard's working set,
# and they live in VMEM for the duration of the kernel). The bodies repeat
# the XLA shard-step expressions verbatim so interpret-mode results are
# bitwise-identical to the `sharded` backend at the same shard shapes.


def _shard_call(body, out_shape, *args, interpret: bool):
    """One whole-block pallas_call: every operand is a full (already
    shard-local) block; TPU places them in VMEM, CPU runs interpreted."""
    return pl.pallas_call(body, out_shape=out_shape,
                          interpret=interpret)(*args)


def _rowwise_shard_step_body(hf_ref, hl_ref, xp_ref, u_ref, b_ref, o_ref):
    """v3 rowwise step, one shard: all three gate matvecs contract the FULL
    (replicated) h against this shard's output rows; finished local rows
    out (the trailing all-gather runs outside, between kernel calls)."""
    Hl = o_ref.shape[-1]
    hf = hf_ref[...]                                       # (B, H) replicated
    xp, u, b = xp_ref[...], u_ref[...], b_ref[...][0]
    z = jax.nn.sigmoid(xp[:, :Hl] + hf @ u[:, :Hl] + b[:Hl])
    r = jax.nn.sigmoid(xp[:, Hl:2 * Hl] + hf @ u[:, Hl:2 * Hl]
                       + b[Hl:2 * Hl])
    ht = jnp.tanh(xp[:, 2 * Hl:] + r * (hf @ u[:, 2 * Hl:] + b[2 * Hl:]))
    o_ref[...] = (1 - z) * hl_ref[...] + z * ht


def _rowwise_shard_zr_body(hf_ref, hl_ref, xp_ref, u_ref, b_ref, z_ref,
                           rh_ref):
    """v1 rowwise phase 1, one shard: z and r for this shard's rows plus
    the local ``r*h`` contribution the mid-step aggregation gathers."""
    Hl = z_ref.shape[-1]
    hf = hf_ref[...]
    xp, u, b = xp_ref[...], u_ref[...], b_ref[...][0]
    z = jax.nn.sigmoid(xp[:, :Hl] + hf @ u[:, :Hl] + b[:Hl])
    r = jax.nn.sigmoid(xp[:, Hl:] + hf @ u[:, Hl:] + b[Hl:])
    z_ref[...] = z
    rh_ref[...] = r * hl_ref[...]


def _rowwise_shard_candidate_body(rhf_ref, hl_ref, z_ref, xp_ref, u_ref,
                                  b_ref, o_ref):
    """v1 rowwise phase 2, one shard: candidate gate against the gathered
    full ``r*h``, then the convex state update on the local rows."""
    ht = jnp.tanh(xp_ref[...] + rhf_ref[...] @ u_ref[...] + b_ref[...][0])
    z = z_ref[...]
    o_ref[...] = (1 - z) * hl_ref[...] + z * ht


def _shard_matvec_body(x_ref, w_ref, o_ref):
    """Partial-product matvec: this shard's contraction slice (the cascade
    MAC segment; the psum combining shards runs outside)."""
    o_ref[...] = x_ref[...] @ w_ref[...]


def _cascade_shard_gates_body(g_ref, xp_ref, h_ref, o_ref):
    """v3 cascade epilogue, one shard: gate nonlinearities + state update
    on the LOCAL gate slices of the psum'd pre-activations (elementwise,
    so slicing before the kernel is bitwise-free)."""
    Hl = o_ref.shape[-1]
    g, xp = g_ref[...], xp_ref[...]
    z = jax.nn.sigmoid(xp[:, :Hl] + g[:, :Hl])
    r = jax.nn.sigmoid(xp[:, Hl:2 * Hl] + g[:, Hl:2 * Hl])
    ht = jnp.tanh(xp[:, 2 * Hl:] + r * g[:, 2 * Hl:])
    o_ref[...] = (1 - z) * h_ref[...] + z * ht


def _cascade_shard_zr_body(zr_ref, xp_ref, h_ref, u_ref, z_ref, p_ref):
    """v1 cascade mid-phase, one shard: z/r on the local slices of the
    psum'd z,r pre-activations, then this shard's candidate partial
    product ``(r_local * h_local) @ Uh_rows`` (psum'd outside)."""
    Hl = z_ref.shape[-1]
    zr, xp = zr_ref[...], xp_ref[...]
    z = jax.nn.sigmoid(xp[:, :Hl] + zr[:, :Hl])
    r = jax.nn.sigmoid(xp[:, Hl:] + zr[:, Hl:])
    z_ref[...] = z
    p_ref[...] = (r * h_ref[...]) @ u_ref[...]


def _cascade_shard_update_body(z_ref, ht_ref, h_ref, o_ref):
    """v1 cascade epilogue, one shard: candidate tanh on the local slice of
    the psum'd pre-activation, then the convex state update."""
    z = z_ref[...]
    o_ref[...] = (1 - z) * h_ref[...] + z * jnp.tanh(ht_ref[...])


def gru_rowwise_shard_step(h_full, h_local, xp, u, b, *,
                           interpret: bool = False):
    """v3 rowwise shard step. h_full (B,H) replicated f32, h_local (B,Hl)
    this shard's rows, xp (B,3Hl) / u (H,3Hl) / b (3Hl,) this shard's
    gate-major slices -> new local rows (B,Hl)."""
    B, Hl = h_local.shape
    return _shard_call(_rowwise_shard_step_body,
                       jax.ShapeDtypeStruct((B, Hl), jnp.float32),
                       h_full, h_local, xp, u, b[None, :],
                       interpret=interpret)


def gru_rowwise_shard_zr(h_full, h_local, xp_zr, u_zr, b_zr, *,
                         interpret: bool = False):
    """v1 rowwise phase 1 -> (z_local (B,Hl), rh_local (B,Hl))."""
    B, Hl = h_local.shape
    out = [jax.ShapeDtypeStruct((B, Hl), jnp.float32)] * 2
    return _shard_call(_rowwise_shard_zr_body, out, h_full, h_local, xp_zr,
                       u_zr, b_zr[None, :], interpret=interpret)


def gru_rowwise_shard_candidate(rh_full, h_local, z_local, xp_h, u_h, b_h, *,
                                interpret: bool = False):
    """v1 rowwise phase 2: gathered rh_full (B,H) -> new local rows."""
    B, Hl = h_local.shape
    return _shard_call(_rowwise_shard_candidate_body,
                       jax.ShapeDtypeStruct((B, Hl), jnp.float32),
                       rh_full, h_local, z_local, xp_h, u_h, b_h[None, :],
                       interpret=interpret)


def gru_shard_matvec(x, w, *, interpret: bool = False):
    """Cascade partial product: x (B,Hl) @ w (Hl,N) -> (B,N) f32."""
    return _shard_call(_shard_matvec_body,
                       jax.ShapeDtypeStruct((x.shape[0], w.shape[1]),
                                            jnp.float32),
                       x, w, interpret=interpret)


def gru_cascade_shard_gates(g_local, xp_local, h_shard, *,
                            interpret: bool = False):
    """v3 cascade epilogue: local (B,3Hl) gate slices -> new h shard."""
    return _shard_call(_cascade_shard_gates_body,
                       jax.ShapeDtypeStruct(h_shard.shape, jnp.float32),
                       g_local, xp_local, h_shard, interpret=interpret)


def gru_cascade_shard_zr(zr_local, xp_local, h_shard, u_h_rows, *,
                         interpret: bool = False):
    """v1 cascade mid-phase -> (z_local (B,Hl), ht_partial (B,H))."""
    B, Hl = h_shard.shape
    out = [jax.ShapeDtypeStruct((B, Hl), jnp.float32),
           jax.ShapeDtypeStruct((B, u_h_rows.shape[1]), jnp.float32)]
    return _shard_call(_cascade_shard_zr_body, out, zr_local, xp_local,
                       h_shard, u_h_rows, interpret=interpret)


def gru_cascade_shard_update(z_local, ht_in_local, h_shard, *,
                             interpret: bool = False):
    """v1 cascade epilogue: pre-activated local candidate -> new h shard."""
    return _shard_call(_cascade_shard_update_body,
                       jax.ShapeDtypeStruct(h_shard.shape, jnp.float32),
                       z_local, ht_in_local, h_shard, interpret=interpret)
