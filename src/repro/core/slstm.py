"""The sLSTM cell family: scalar-gated recurrence with exponential gates
and a per-step stabilizer (xLSTM, Beck et al. 2024 — see SNIPPETS.md §3).

Second registered :class:`repro.core.cells.CellFamily` — the proof that the
paper's workload-distribution machinery (decoupled ``W.x`` GEMM, fused
recurrent path, capability dispatch, prepare()-placed weights) is not
GRU-specific. The cell keeps the repo's dense per-layer layout — ``w``
``(X, 4H)``, ``u`` ``(H, 4H)``, ``b`` ``(4H,)``, gate order ``[z, i, f, o]``
— so the same stacking/normalization helpers apply; the per-head
block-diagonal recurrence of ``repro.models.xlstm`` is a model-level
refinement, not part of the family contract.

Gate math (fp32, all backends and the oracle):

    z, i, f, o = split(W x + U h + b, 4)        # 2 matvecs/step, fused gates
    logf  = log_sigmoid(f)
    m'    = max(logf + m, i)                     # stabilizer state
    c'    = exp(logf + m - m') * c + exp(i - m') * tanh(z)
    n'    = exp(logf + m - m') * n + exp(i - m')
    h'    = sigmoid(o) * c' / max(n', 1e-6)

Per-layer state is FOUR ``(B, H)`` leaves ``(c, n, m, h)``; a depth-L
stack's flat runtime state is ``(c0, n0, m0, h0, c1, ...)`` (see
``repro.core.cells``). The stabilizer ``m`` is genuinely recurrent — it is
carried per step exactly like ``h``, in VMEM scratch for the fused Pallas
kernels (:mod:`repro.kernels.slstm_cell`).

This module owns the family registration, the parameter specs, the
XLA-scan fallback backend (``(slstm, xla)``) and the dense fp32 oracle.
The fused Pallas backend registers from ``repro.kernels.slstm_cell.ops``.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from repro.configs.base import GRUConfig
from repro.core import cells as cells_registry
from repro.core.gru import stack_cell_params
from repro.core.params import Spec

STATE_LEAVES = 4                      # (c, n, m, h) per layer
M_INIT = -1e30                        # stabilizer init: first step's f_ = 0


# ---------------------------------------------------------------------------
# parameter specs + state layout
# ---------------------------------------------------------------------------

def slstm_cell_specs(input_dim: int, hidden_dim: int) -> dict:
    """One sLSTM layer. Gate stacking order along the last axis:
    [z, i, f, o]."""
    return {
        "w": Spec((input_dim, 4 * hidden_dim), ("rnn_in", "gates")),
        "u": Spec((hidden_dim, 4 * hidden_dim), ("hidden", "gates"),
                  init="recurrent"),
        "b": Spec((4 * hidden_dim,), ("gates",), init="zeros"),
    }


def slstm_stack_specs(cfg: GRUConfig) -> tuple:
    """Per-layer cell specs for a depth-L stack, layer 0 first."""
    return tuple(
        slstm_cell_specs(cfg.layer_input_dim(l), h)
        for l, h in enumerate(cfg.resolved_layer_dims)
    )


def stack_state0(cfg: GRUConfig, batch: int, dtype=jnp.float32) -> tuple:
    """Flat initial state, layer-major: (c, n, m, h) per layer."""
    out = []
    for h in cfg.resolved_layer_dims:
        out += [jnp.zeros((batch, h), dtype), jnp.zeros((batch, h), dtype),
                jnp.full((batch, h), M_INIT, dtype),
                jnp.zeros((batch, h), dtype)]
    return tuple(out)


def group_states(state: Sequence[jax.Array], num_layers: int) -> tuple:
    """Flat (4L,) tuple -> per-layer ((c, n, m, h), ...) groups."""
    state = tuple(state)
    assert len(state) == STATE_LEAVES * num_layers, (len(state), num_layers)
    return tuple(state[STATE_LEAVES * l:STATE_LEAVES * (l + 1)]
                 for l in range(num_layers))


def flatten_states(groups) -> tuple:
    """Per-layer ((c, n, m, h), ...) groups -> flat (4L,) tuple."""
    return tuple(leaf for g in groups for leaf in g)


# ---------------------------------------------------------------------------
# gate math (fp32)
# ---------------------------------------------------------------------------

def slstm_gate_math(c, n, m, h, xp, u, b):
    """One cell update. c/n/m/h: (B,H); xp: (B,4H) precomputed W.x;
    u: (H,4H); b broadcastable (4H,). Returns the new (c, n, m, h)."""
    H = h.shape[-1]
    g = xp + h @ u + b                                   # (B, 4H) fused gates
    z, i = g[..., :H], g[..., H:2 * H]
    f, o = g[..., 2 * H:3 * H], g[..., 3 * H:]
    logf = jax.nn.log_sigmoid(f)
    m_new = jnp.maximum(logf + m, i)
    i_ = jnp.exp(i - m_new)
    f_ = jnp.exp(logf + m - m_new)
    c_new = f_ * c + i_ * jnp.tanh(z)
    n_new = f_ * n + i_
    h_new = jax.nn.sigmoid(o) * c_new / jnp.maximum(n_new, 1e-6)
    return c_new, n_new, m_new, h_new


def _f32_cell(cell: dict) -> tuple:
    return (cell["w"].astype(jnp.float32), cell["u"].astype(jnp.float32),
            cell["b"].astype(jnp.float32))


# ---------------------------------------------------------------------------
# XLA-scan backend (the slstm family's fallback, serves any shape)
# ---------------------------------------------------------------------------

def _layer_sequence_xla(cell: dict, group: tuple, xs: jax.Array, *,
                        return_all: bool, mask: Optional[jax.Array]):
    """One layer over xs (..., T, X): decoupled W.x GEMM + lax.scan over
    the recurrent path. Returns ((c,n,m,h) finals, (B,T,H) h states|None).
    ``mask`` (B,T): False steps freeze all four state leaves (select, not
    perturb — live steps stay bitwise-identical to unpadded)."""
    w, u, b = _f32_cell(cell)
    xp = xs.astype(jnp.float32) @ w                      # (B,T,4H) decoupled
    xp_t = jnp.moveaxis(xp, -2, 0)                       # time-major (T,B,4H)
    c0, n0, m0, h0 = (leaf.astype(jnp.float32) for leaf in group)

    # an unmasked call gates with an all-live mask: masked and unmasked
    # calls then trace one scan body, so live rows stay bitwise-equal
    mask_t = (jnp.ones(xp_t.shape[:-1], bool) if mask is None
              else jnp.moveaxis(mask, -1, 0) != 0)        # (T,B) bool

    def step(carry, inp):
        xp_step, keep = inp
        new = slstm_gate_math(*carry, xp_step, u, b)
        new = tuple(jnp.where(keep[..., None], a, old)
                    for a, old in zip(new, carry))
        return new, (new[3] if return_all else None)

    finals, hs = jax.lax.scan(step, (c0, n0, m0, h0), (xp_t, mask_t))
    if return_all:
        return finals, jnp.moveaxis(hs, 0, -2)           # (B,T,H)
    return finals, None


def slstm_stack_sequence_xla(params, state0: Sequence[jax.Array],
                             xs: jax.Array, *, cfg: GRUConfig,
                             return_all: bool = False,
                             mask: Optional[jax.Array] = None):
    """Depth-L sLSTM stack over xs (B,T,X), layer-by-layer (each layer
    hoists its input GEMM over the lower layer's full hidden sequence).
    ``state0``: flat (4L,) tuple. Returns (flat finals, last layer's
    (B,T,H) h sequence | None). One shared mask freezes every layer's
    state at padded steps (exact, same argument as the GRU stack)."""
    cells = stack_cell_params(params, cfg)
    L = len(cells)
    groups = group_states(state0, L)
    finals, cur, hs = [], xs, None
    for l in range(L):
        last = l == L - 1
        fin, hs = _layer_sequence_xla(cells[l], groups[l], cur,
                                      return_all=(not last) or return_all,
                                      mask=mask)
        finals.append(fin)
        if not last:
            cur = hs
    return flatten_states(finals), (hs if return_all else None)


def slstm_stack_decode_xla(params, state: Sequence[jax.Array], x: jax.Array,
                           *, cfg: GRUConfig) -> tuple:
    """One serve step through the stack: layer ``l`` consumes layer
    ``l-1``'s NEW hidden state. ``state``: flat (4L,); returns the flat
    new state."""
    cells = stack_cell_params(params, cfg)
    groups = group_states(state, len(cells))
    out, cur = [], x
    for cell, group in zip(cells, groups):
        w, u, b = _f32_cell(cell)
        xp = cur.astype(jnp.float32) @ w                 # (B,4H)
        c, n, m, h = (leaf.astype(jnp.float32) for leaf in group)
        new = slstm_gate_math(c, n, m, h, xp, u, b)
        out.append(new)
        cur = new[3]
    return flatten_states(out)


# pure-jnp dense oracle used by every slstm test ----------------------------

def slstm_stack_reference(params, state0: Sequence[jax.Array], xs: jax.Array,
                          return_all: bool = False,
                          mask: Optional[jax.Array] = None):
    """Dense fp32 step-by-step oracle (python time loop, no scan, no
    decoupled GEMM). Returns (flat finals, last layer's (B,T,H) | None)."""
    cells = stack_cell_params(params)
    L = len(cells)
    wub = [_f32_cell(c) for c in cells]
    states = [list(leaf.astype(jnp.float32) for leaf in g)
              for g in group_states(state0, L)]
    out = []
    for t in range(xs.shape[-2]):
        cur = xs[..., t, :].astype(jnp.float32)
        keep = None if mask is None else mask[..., t, None] != 0
        for l in range(L):
            w, u, b = wub[l]
            new = slstm_gate_math(*states[l], cur @ w, u, b)
            if keep is not None:
                new = tuple(jnp.where(keep, a, old)
                            for a, old in zip(new, states[l]))
            states[l] = list(new)
            cur = new[3]
        if return_all:
            out.append(states[-1][3])
    hs = jnp.stack(out, axis=-2) if return_all else None
    return flatten_states(tuple(tuple(s) for s in states)), hs


# ---------------------------------------------------------------------------
# registration: the family + its XLA fallback backend
# ---------------------------------------------------------------------------

def _slstm_family() -> cells_registry.CellFamily:
    def stacked_views(cells):
        from repro.kernels.slstm_cell import ops as slstm_ops
        return slstm_ops.prepare_stacked_cells(cells)

    def reference(cells, state0, xs, *, return_all=False, mask=None):
        return slstm_stack_reference(cells, tuple(state0), xs,
                                     return_all=return_all, mask=mask)

    return cells_registry.CellFamily(
        name="slstm",
        gates=4,
        state_leaves=STATE_LEAVES,
        state_names=("c", "n", "m", "h"),
        h_leaf=3,
        cell_specs=slstm_cell_specs,
        stack_specs=slstm_stack_specs,
        init_state=stack_state0,
        normalize=stack_cell_params,
        reference=reference,
        stacked_views=stacked_views,
        supports_quant=False,          # no q8 views for the exp-gate path yet
        supports_placement=False,      # no shard_map backends registered
        state_dtype="float32",
    )


cells_registry.register_family(_slstm_family())

_REGISTERED = False


def register_runtime_backends() -> None:
    """Idempotently register the ``(slstm, xla)`` fallback with the
    executor. Called by ``runtime._ensure_backends()`` on first use."""
    global _REGISTERED
    if _REGISTERED:
        return
    from repro.core import runtime

    def xla_seq(sp, state0, xs, *, cfg, return_all, mask, placement):
        return slstm_stack_sequence_xla(sp.cells, tuple(state0), xs, cfg=cfg,
                                        return_all=return_all, mask=mask)

    def xla_dec(sp, state, x, *, cfg, placement):
        return slstm_stack_decode_xla(sp.cells, tuple(state), x, cfg=cfg)

    runtime.register_backend(runtime.BackendSpec(
        family="slstm",
        name="xla",
        caps=runtime.Capabilities(supports_mask=True,
                                  supports_hetero_dims=True,
                                  supports_mesh=False, return_all=True,
                                  decode=True, sequence=True),
        cost=30,
        sequence_fn=xla_seq, decode_fn=xla_dec))
    _REGISTERED = True


__all__ = [
    "STATE_LEAVES", "M_INIT", "slstm_cell_specs", "slstm_stack_specs",
    "stack_state0", "group_states", "flatten_states", "slstm_gate_math",
    "slstm_stack_sequence_xla", "slstm_stack_decode_xla",
    "slstm_stack_reference", "register_runtime_backends",
]
