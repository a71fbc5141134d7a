"""The paper's GRU, TPU-adapted: row-wise vs cascade matvec, decoupled Wx,
fused vs unfused gate aggregation.

Gate math (paper eq. 1, "v1"/Cho variant):

    z = sigmoid(Wz x + Uz h + bz)
    r = sigmoid(Wr x + Ur h + br)
    h~ = tanh(Wh x + Uh (r*h) + bh)
    h' = (1-z)*h + z*h~

Structural modes (all numerically equal to the dense oracle; they differ in
the *shape of the computation*, which is what the paper studies):

* ``matvec_mode="rowwise"`` — output-stationary: the weight matrix is
  partitioned by output rows; every block consumes the full vector and emits
  complete outputs (no cross-block reduction). TPU analogue of the paper's
  row-wise AIE tiling; lowers to a parallel map over row blocks.
* ``matvec_mode="cascade"`` — contraction-stationary baseline: the matrix is
  partitioned by columns and partial sums accumulate sequentially across
  blocks (the AIE cascade-stream pipeline); lowers to ``lax.scan``.
* ``matvec_mode="dense"`` — plain ``x @ w`` oracle.

``fused_gates=True`` is the hybrid-aggregation analogue: gate matvecs are
batched into stacked matmuls and the bias+activation+Hadamard epilogue is
applied without materializing per-gate intermediates (2 matmuls/step).
``False`` is the unfused baseline (3 separate matvecs + separate adds).

``decoupled_wx=True`` hoists the input projection out of the recurrence:
``Xp = xs @ W`` runs as one MXU-shaped GEMM over all timesteps before the
scan — the paper's free-running ``W.x`` tiles that prefetch ahead of the
recurrent path.

``variant="v3"`` is a *beyond-paper* option (cuDNN-style gate math,
``h~ = tanh(Wh x + r*(Uh h + bh))``) that makes all three U matvecs
fusable into ONE matmul per step, shortening the recurrent critical path.

Deep stacks (beyond the paper's single validated layer): ``gru_stack_*``
run ``cfg.resolved_num_layers`` cells, layer ``l`` consuming layer
``l-1``'s hidden sequence. Layer 0 keeps the decoupled ``W.x`` hoisting;
deeper layers hoist their own input GEMM over the full lower-layer
sequence (layer-by-layer execution), so every layer's recurrent path stays
matvec-only. Per-layer ``matvec_mode`` overrides
(``cfg.layer_matvec_modes``) let row-wise and cascade layers mix in one
stack — the paper's hybrid AIE-PL split, generalized per layer. With
``backend="pallas"`` and uniform hidden sizes the whole stack lowers to
ONE fused pallas_call (see ``repro.kernels.gru_sequence``).

Backend DISPATCH lives in ``repro.core.runtime`` (the capability-driven
executor): this module keeps the gate math, the parameter specs, the
XLA-scan backend implementations (``gru_sequence_xla`` /
``gru_stack_sequence_xla`` / ``gru_stack_decode_xla``) and the dense
oracles. The historical entry points (``gru_sequence``,
``gru_stack_sequence``, ``gru_stack_decode_step``, ``gru_decode_step``)
remain as deprecated shims over the executor — bitwise-equal, warning
once per process.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from repro.configs.base import GRUConfig
from repro.core.params import Spec


# ---------------------------------------------------------------------------
# deprecation bookkeeping for the legacy entry points (now executor shims)
# ---------------------------------------------------------------------------

_DEPRECATION_WARNED: set = set()


def _warn_deprecated(old: str) -> None:
    """One DeprecationWarning per entry point per process: the legacy GRU
    entry points still work (and stay bitwise-equal to the executor) but
    new code should go through ``repro.core.runtime.compile()``."""
    if old in _DEPRECATION_WARNED:
        return
    _DEPRECATION_WARNED.add(old)
    warnings.warn(
        f"{old} is a deprecated entry point; use "
        "repro.core.runtime.compile() -> GRUExecutable (capability-"
        "dispatched executor, two-stage compile/execute) instead.",
        DeprecationWarning, stacklevel=3)


# ---------------------------------------------------------------------------
# parameter specs
# ---------------------------------------------------------------------------

def gru_cell_specs(input_dim: int, hidden_dim: int) -> dict:
    """One GRU layer. Gate stacking order along the last axis: [z, r, h]."""
    return {
        "w": Spec((input_dim, 3 * hidden_dim), ("rnn_in", "gates")),
        "u": Spec((hidden_dim, 3 * hidden_dim), ("hidden", "gates"), init="recurrent"),
        "b": Spec((3 * hidden_dim,), ("gates",), init="zeros"),
    }


def gru_stack_specs(cfg: GRUConfig) -> tuple:
    """Per-layer cell specs for a depth-L stack, layer 0 first."""
    return tuple(
        gru_cell_specs(cfg.layer_input_dim(l), h)
        for l, h in enumerate(cfg.resolved_layer_dims)
    )


def layer_config(cfg: GRUConfig, layer: int) -> GRUConfig:
    """Specialize a stack config to one layer (depth-1 view)."""
    return dataclasses.replace(
        cfg,
        input_dim=cfg.layer_input_dim(layer),
        hidden_dim=cfg.resolved_layer_dims[layer],
        matvec_mode=cfg.layer_matvec_mode(layer),
        num_layers=1, layer_dims=(), layer_matvec_modes=())


def stack_cell_params(params, cfg: Optional[GRUConfig] = None) -> tuple:
    """Normalize any accepted param layout to a tuple of per-layer cells.

    Accepts {"cells": (...)} (deep model), {"cell": {...}} (seed depth-1
    layout, kept for compatibility), a bare cell dict, or a sequence."""
    if isinstance(params, dict):
        if "cells" in params:
            return tuple(params["cells"])
        if "cell" in params:
            return (params["cell"],)
        return (params,)                      # bare {w,u,b}
    return tuple(params)


def gru_classifier_specs(cfg: GRUConfig) -> dict:
    """The paper's jet-tagging model: the config's cell-family stack
    (``cfg.family``, GRU by default) + a linear classifier head over the
    last layer's h.

    A depth-1 GRU keeps the seed's ``{"cell": ...}`` layout
    (checkpoint/example compatibility); every other stack uses
    ``{"cells": (layer0, layer1, ...)}``.
    """
    from repro.core import cells
    fam = cells.get_family(cells.cfg_family(cfg))
    head_in = cfg.resolved_layer_dims[-1]
    head = {
        "w": Spec((head_in, cfg.num_classes), ("hidden", None)),
        "b": Spec((cfg.num_classes,), (None,), init="zeros"),
    }
    stack = fam.stack_specs(cfg)
    if fam.name == "gru" and len(stack) == 1:
        return {"cell": stack[0], "head": head}
    return {"cells": stack, "head": head}


# ---------------------------------------------------------------------------
# structural matvec modes
# ---------------------------------------------------------------------------

def _row_blocks(n: int, blk: int) -> int:
    assert n % blk == 0, f"output dim {n} not divisible by row block {blk}"
    return n // blk


def matvec(x: jax.Array, w: jax.Array, mode: str = "dense", block: int = 0) -> jax.Array:
    """``x @ w`` with an explicit structural decomposition.

    x: (..., K), w: (K, N) -> (..., N).
    ``block`` = rows-per-block (rowwise) or contraction chunk (cascade);
    0 picks N//4 (rowwise, >=1) or K//4 (cascade, >=1).
    """
    K, N = w.shape
    if mode == "dense":
        return x @ w
    if mode == "rowwise":
        blk = block or max(N // 4, 1)
        while N % blk:
            blk -= 1
        nb = _row_blocks(N, blk)
        # (nb, K, blk): each block holds whole rows; every block sees the full
        # vector x and emits finished outputs. lax.map keeps the block
        # structure visible in HLO (parallel, no cross-block reduction).
        wb = jnp.moveaxis(w.reshape(K, nb, blk), 1, 0)
        yb = jax.lax.map(lambda wi: x @ wi, wb)          # (nb, ..., blk)
        return jnp.moveaxis(yb, 0, -2).reshape(*x.shape[:-1], N)
    if mode == "cascade":
        blk = block or max(K // 4, 1)
        while K % blk:
            blk -= 1
        kb = K // blk
        xs = x.reshape(*x.shape[:-1], kb, blk)
        ws = w.reshape(kb, blk, N)
        # sequential accumulation across contraction blocks = cascade stream.
        def body(carry, operand):
            xi, wi = operand
            return carry + xi @ wi, None
        x_first = jnp.moveaxis(xs, -2, 0)                # (kb, ..., blk)
        init = jnp.zeros((*x.shape[:-1], N), _acc_dtype(x.dtype))
        out, _ = jax.lax.scan(body, init, (x_first, ws))
        return out.astype(x.dtype)
    raise ValueError(f"unknown matvec mode {mode!r}")


def _acc_dtype(dt):
    return jnp.float32 if dt in (jnp.bfloat16, jnp.float16) else dt


# ---------------------------------------------------------------------------
# single step
# ---------------------------------------------------------------------------

def input_projection(params: dict, xs: jax.Array, cfg: GRUConfig) -> jax.Array:
    """The decoupled ``W.x`` path: one GEMM over however many timesteps are
    given (MXU-shaped; runs off the recurrent critical path)."""
    return matvec(xs, params["w"], cfg.matvec_mode, cfg.row_block)


def gru_step(params: dict, h: jax.Array, x: Optional[jax.Array] = None,
             x_proj: Optional[jax.Array] = None, *, cfg: GRUConfig) -> jax.Array:
    """One recurrent step. Pass ``x_proj`` (precomputed Wx, shape (..., 3H))
    when decoupled, else raw ``x``."""
    H = params["u"].shape[0]
    if x_proj is None:
        x_proj = input_projection(params, x, cfg)
    u, b = params["u"], params["b"]
    mode, blk = cfg.matvec_mode, cfg.row_block
    xz, xr, xh = x_proj[..., :H], x_proj[..., H:2 * H], x_proj[..., 2 * H:]

    if cfg.variant == "v3":
        # beyond-paper: single stacked U matvec per step (cuDNN gate math).
        uh_all = matvec(h, u, mode, blk) + b
        z = jax.nn.sigmoid(xz + uh_all[..., :H])
        r = jax.nn.sigmoid(xr + uh_all[..., H:2 * H])
        h_tilde = jnp.tanh(xh + r * uh_all[..., 2 * H:])
    elif cfg.fused_gates:
        # paper's hybrid aggregation: phase 1 fuses z,r (one (H,2H) matmul +
        # epilogue), phase 2 the candidate (one (H,H) matmul + epilogue).
        zr = matvec(h, u[:, :2 * H], mode, blk) + b[: 2 * H]
        z = jax.nn.sigmoid(xz + zr[..., :H])
        r = jax.nn.sigmoid(xr + zr[..., H:])
        h_tilde = jnp.tanh(xh + matvec(r * h, u[:, 2 * H:], mode, blk) + b[2 * H:])
    else:
        # unfused baseline: three separate matvecs, materialized per-gate
        # intermediates (the pure-AIE aggregator path).
        z = jax.nn.sigmoid(xz + matvec(h, u[:, :H], mode, blk) + b[:H])
        r = jax.nn.sigmoid(xr + matvec(h, u[:, H:2 * H], mode, blk) + b[H:2 * H])
        h_tilde = jnp.tanh(xh + matvec(r * h, u[:, 2 * H:], mode, blk) + b[2 * H:])
    return (1.0 - z) * h + z * h_tilde


# ---------------------------------------------------------------------------
# sequence
# ---------------------------------------------------------------------------

def gru_sequence_xla(params: dict, h0: jax.Array, xs: jax.Array, *,
                     cfg: GRUConfig, return_all: bool = False,
                     mask: Optional[jax.Array] = None):
    """The XLA-scan backend implementation (no dispatch): run the
    recurrence over ``xs`` (..., T, X), time axis = -2.

    Respects ``cfg.decoupled_wx`` (hoisted input GEMM) and ``cfg.unroll``
    (short-sequence latency mode). ``mask`` (B, T) bool, optional:
    timesteps where it is False leave the hidden state untouched —
    left-padded (bucketed) batches produce bitwise the same final state as
    their unpadded prompts, since GRU biases make zero *inputs*
    non-neutral.
    """
    # an unmasked call gates with an all-live mask: masked and unmasked
    # calls then trace one scan body, so live rows stay bitwise-equal
    m_t = (jnp.ones((xs.shape[-2],) + xs.shape[:-2], bool) if mask is None
           else jnp.moveaxis(mask, -1, 0))                # (T, B)
    step = functools.partial(gru_step, params, cfg=cfg)

    def gated(h, h2, mt):
        return jnp.where(mt[..., None], h2, h)

    if cfg.decoupled_wx:
        xp = input_projection(params, xs, cfg)           # (..., T, 3H) one GEMM
        xp_t = jnp.moveaxis(xp, -2, 0)

        def body(h, op):
            xpt, mt = op
            h2 = gated(h, step(h, x_proj=xpt), mt)
            return h2, (h2 if return_all else None)
        hT, hs = jax.lax.scan(body, h0, (xp_t, m_t), unroll=cfg.unroll)
    else:
        xs_t = jnp.moveaxis(xs, -2, 0)

        def body(h, op):
            xt, mt = op
            h2 = gated(h, step(h, x=xt), mt)
            return h2, (h2 if return_all else None)
        hT, hs = jax.lax.scan(body, h0, (xs_t, m_t), unroll=cfg.unroll)
    if return_all:
        return hT, jnp.moveaxis(hs, 0, -2)
    return hT, None


def gru_sequence(params: dict, h0: jax.Array, xs: jax.Array, *, cfg: GRUConfig,
                 return_all: bool = False, mask: Optional[jax.Array] = None):
    """DEPRECATED single-cell entry point — thin shim over the executor
    (``repro.core.runtime``), which capability-dispatches ``cfg.backend``
    to the XLA scan or the Pallas kernels (masked calls included)."""
    _warn_deprecated("gru_sequence")
    from repro.core import runtime
    lcfg = cfg if cfg.resolved_num_layers == 1 else layer_config(cfg, 0)
    finals, states = runtime.sequence((params,), (h0,), xs, cfg=lcfg,
                                      return_all=return_all, mask=mask)
    return finals[0], states


# ---------------------------------------------------------------------------
# deep stacks
# ---------------------------------------------------------------------------

def stack_h0(cfg: GRUConfig, batch: int, dtype=jnp.float32) -> tuple:
    """Zero initial hidden state per layer."""
    return tuple(jnp.zeros((batch, h), dtype) for h in cfg.resolved_layer_dims)


def gru_stack_sequence_xla(params: Sequence[dict], h0s: Sequence[jax.Array],
                           xs: jax.Array, *, cfg: GRUConfig,
                           return_all: bool = False,
                           mask: Optional[jax.Array] = None):
    """The XLA-scan stack backend (no dispatch): run a depth-L stack over
    ``xs`` (..., T, X), time axis = -2, layer-by-layer.

    ``params``/``h0s`` are per-layer sequences (layer 0 first). Returns
    ``(finals, all_states)`` where ``finals`` is the tuple of per-layer
    final hidden states and ``all_states`` is the LAST layer's full
    hidden sequence (or None). Every layer hoists its input GEMM over the
    lower layer's full sequence (layer 0: the paper's decoupled ``W.x``),
    so the recurrent path of each layer is matvec-only. Depth 1 is exactly
    ``gru_sequence_xla``.

    ``mask`` (B, T) bool, optional: False steps freeze EVERY layer's state
    (one shared mask is exact — during frozen steps upper layers ignore
    their input, so the real steps see exactly the unpadded computation).
    """
    params = stack_cell_params(params, cfg)
    L = len(params)
    finals = []
    cur = xs
    hs = None
    for l in range(L):
        lcfg = layer_config(cfg, l)
        last = l == L - 1
        hT, hs = gru_sequence_xla(params[l], h0s[l], cur, cfg=lcfg,
                                  return_all=(not last) or return_all,
                                  mask=mask)
        finals.append(hT)
        if not last:
            cur = hs
    return tuple(finals), (hs if return_all else None)


def gru_stack_sequence(params: Sequence[dict], h0s: Sequence[jax.Array],
                       xs: jax.Array, *, cfg: GRUConfig,
                       return_all: bool = False,
                       mask: Optional[jax.Array] = None):
    """DEPRECATED stack entry point — thin shim over the executor, which
    dispatches to the XLA scan, the fused Pallas stack kernel (uniform
    dims; masked calls stream the mask in-kernel, no XLA fallback), or the
    per-layer Pallas chain (heterogeneous dims)."""
    _warn_deprecated("gru_stack_sequence")
    from repro.core import runtime
    return runtime.sequence(params, tuple(h0s), xs, cfg=cfg,
                            return_all=return_all, mask=mask)


def gru_stack_decode_xla(params: Sequence[dict], hs: Sequence[jax.Array],
                         x: jax.Array, *, cfg: GRUConfig) -> tuple:
    """The XLA decode backend (no dispatch): one serve step through the
    whole stack via layer-by-layer structural-mode matvecs. Layer ``l``
    consumes layer ``l-1``'s NEW hidden state (same-timestep threading as
    the sequence path). Returns the tuple of per-layer new hidden states."""
    params = stack_cell_params(params, cfg)
    new_hs = []
    cur = x
    for l in range(len(params)):
        h2 = gru_step(params[l], hs[l], x=cur, cfg=layer_config(cfg, l))
        new_hs.append(h2)
        cur = h2
    return tuple(new_hs)


def gru_stack_decode_step(params: Sequence[dict], hs: Sequence[jax.Array],
                          x: jax.Array, *, cfg: GRUConfig,
                          impl: Optional[str] = None) -> tuple:
    """DEPRECATED decode entry point — thin shim over the executor.

    ``impl``: "pallas" / "xla" override ``cfg.backend`` as the dispatch
    preference (kept for compatibility); None follows ``cfg.backend``.
    Under the executor a "pallas" preference with heterogeneous layer
    sizes now runs the per-layer Pallas chain instead of silently taking
    the XLA path. A dict ``params`` may carry precomputed
    ``"stacked_cells"`` (see ``runtime.prepare``) so the fused path does
    no per-step weight restacking.
    """
    _warn_deprecated("gru_stack_decode_step")
    from repro.core import runtime
    if impl is not None and impl != cfg.backend:
        cfg = dataclasses.replace(cfg, backend=impl)
    return runtime.decode(params, tuple(hs), x, cfg=cfg)


def gru_stack_reference(params: Sequence[dict], h0s: Sequence[jax.Array],
                        xs: jax.Array, return_all: bool = False,
                        mask: Optional[jax.Array] = None):
    """Dense fp32 layer-by-layer oracle for the stack (depth-1 ==
    ``gru_reference``). Returns (per-layer finals, last-layer states|None)."""
    params = stack_cell_params(params)
    finals = []
    cur = xs
    hs = None
    for l, p in enumerate(params):
        last = l == len(params) - 1
        hT, hs = gru_reference(p, h0s[l], cur,
                               return_all=(not last) or return_all,
                               mask=mask)
        finals.append(hT)
        if not last:
            cur = hs
    return tuple(finals), (hs if return_all else None)


def gru_classify(params: dict, xs: jax.Array, *, cfg: GRUConfig) -> jax.Array:
    """Paper's jet-tagging forward pass for the config's cell family:
    xs (B, T, X) -> logits (B, C). Routed through the executor
    (``repro.core.runtime``)."""
    from repro.core import cells, runtime
    fam = cells.get_family(cells.cfg_family(cfg))
    state0 = fam.state0(cfg, xs.shape[0], fam.state_dtype or xs.dtype)
    finals, _ = runtime.sequence(stack_cell_params(params, cfg), state0, xs,
                                 cfg=cfg)
    return runtime.readout(finals[-1], params["head"])


def gru_decode_step(params: dict, h: jax.Array, x: jax.Array, *, cfg: GRUConfig) -> jax.Array:
    """DEPRECATED single-cell serve step — executor shim (batch can be 1)."""
    _warn_deprecated("gru_decode_step")
    from repro.core import runtime
    cell = params["cell"] if "cell" in params else params
    lcfg = cfg if cfg.resolved_num_layers == 1 else layer_config(cfg, 0)
    return runtime.decode((cell,), (h,), x, cfg=lcfg)[0]


# pure-jnp dense oracle used by every test --------------------------------

def gru_reference(params: dict, h0: jax.Array, xs: jax.Array,
                  return_all: bool = False,
                  mask: Optional[jax.Array] = None):
    """Dense, unfused, fp32 oracle (no structural modes, no scan tricks).
    ``mask`` (B, T): False steps leave h untouched (padding semantics)."""
    w = params["w"].astype(jnp.float32)
    u = params["u"].astype(jnp.float32)
    b = params["b"].astype(jnp.float32)
    H = u.shape[0]
    h = h0.astype(jnp.float32)
    out = []
    for t in range(xs.shape[-2]):
        x = xs[..., t, :].astype(jnp.float32)
        z = jax.nn.sigmoid(x @ w[:, :H] + h @ u[:, :H] + b[:H])
        r = jax.nn.sigmoid(x @ w[:, H:2 * H] + h @ u[:, H:2 * H] + b[H:2 * H])
        ht = jnp.tanh(x @ w[:, 2 * H:] + (r * h) @ u[:, 2 * H:] + b[2 * H:])
        h2 = (1 - z) * h + z * ht
        h = h2 if mask is None else jnp.where(mask[..., t, None], h2, h)
        if return_all:
            out.append(h)
    if return_all:
        return h, jnp.stack(out, axis=-2)
    return h, None
