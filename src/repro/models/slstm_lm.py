"""The sLSTM family (``slstm-jet``) behind the framework model API.

Mirror of :mod:`repro.models.gru_lm` with the cell family switched to the
exponential-gated sLSTM (``repro.core.slstm``): same jet-tagging
classifier shape (recurrent stack + linear head), same serving path
(bucketed masked prefill + fixed-slot single-step decode), all execution
through the capability-dispatched executor with ``cfg.gru.family ==
"slstm"`` — ``compile()`` resolves backends from the ``(slstm, ·)``
registry namespace (fused Pallas stack kernels or the XLA-scan fallback).

The recurrent cache carries the family's FLAT state tuple under ``"h"``:
four leaves per layer, layer-major — ``(c0, n0, m0, h0, c1, ...)`` — each
a ``(B, H)`` array, so the engine's slot scatter/gather and the cache
specs work leaf-by-leaf exactly as they do for the GRU's one-leaf state.
The readout hidden state is the LAST leaf (layer L-1's ``h``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import runtime
from repro.core import slstm as slstm_core
from repro.core.gru import stack_cell_params
from repro.core.params import Spec, init_params
from repro.distributed.sharding import ShardCtx, constrain

_LEAVES = slstm_core.STATE_LEAVES


def slstm_classifier_specs(cfg) -> dict:
    """sLSTM stack + linear classifier head over the last layer's h."""
    head_in = cfg.resolved_layer_dims[-1]
    return {
        "cells": slstm_core.slstm_stack_specs(cfg),
        "head": {
            "w": Spec((head_in, cfg.num_classes), ("hidden", None)),
            "b": Spec((cfg.num_classes,), (None,), init="zeros"),
        },
    }


def lm_specs(cfg: ModelConfig) -> dict:
    return slstm_classifier_specs(cfg.gru)


def forward(params: dict, cfg: ModelConfig, batch: dict, *,
            ctx: ShardCtx = ShardCtx()) -> jax.Array:
    """batch: {features (B,T,X)} -> class logits (B,C)."""
    xs = batch["features"]
    cells = stack_cell_params(params, cfg.gru)
    state0 = slstm_core.stack_state0(cfg.gru, xs.shape[0], jnp.float32)
    finals, _ = runtime.sequence(cells, state0, xs, cfg=cfg.gru)
    return runtime.readout(finals[-1], params["head"])


def loss_fn(params: dict, cfg: ModelConfig, batch: dict, *,
            ctx: ShardCtx = ShardCtx()):
    """batch: {features (B,T,X), labels (B,)} -> softmax CE."""
    logits = forward(params, cfg, batch, ctx=ctx).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, batch["labels"][:, None], axis=-1)[:, 0]
    loss = (lse - ll).mean()
    acc = (logits.argmax(-1) == batch["labels"]).mean()
    return loss, {"ce": loss, "acc": acc, "aux": jnp.zeros((), jnp.float32)}


# --- serving -----------------------------------------------------------------

def _placement(ctx: ShardCtx) -> runtime.Placement:
    """The ctx mesh resolved to an executor Placement (host if none; the
    slstm family registers no mesh backends, so a mesh placement simply
    resolves to the replicated backends)."""
    return (runtime.HOST if ctx.mesh is None
            else runtime.Placement(mesh=ctx.mesh))


def prepare_params(params: dict, cfg: ModelConfig,
                   ctx: ShardCtx = ShardCtx()) -> dict:
    """One-time serving prep via ``runtime.prepare``: attach the fused
    kernels' stacked-weight views (``"stacked_cells"``, 4H gate columns)
    so the per-step decode trace never restacks U/W/b. No-op for
    already-prepared params."""
    sp = runtime.prepare(params, cfg.gru, _placement(ctx))
    out = {"cells": sp.cells, "head": params["head"]}
    if sp.stacked is not None:
        out["stacked_cells"] = sp.stacked
    return out


def serve_executable(cfg: ModelConfig, *, batch: int, seq: int = None,
                     masked: bool = False, mode: str = "serve",
                     mesh=None) -> runtime.GRUExecutable:
    """The executable a serving call with these shapes will use (same
    memoized object ``prefill``/``decode_step`` resolve internally)."""
    return runtime.compile(cfg.gru, batch=batch, seq=seq, placement=mesh,
                           mask=masked, mode=mode)


def cache_specs(cfg: ModelConfig, batch: int, capacity: int = 0) -> dict:
    """Recurrent cache: the flat sLSTM state — four (B, H) leaves per
    layer (c, n, m, h), layer-major. NOTE: the stabilizer leaf ``m`` must
    start at ``slstm.M_INIT``, not zero — use :func:`init_cache` (or a
    ``prefill``-produced cache), never ``init_params`` on these specs."""
    return {
        "h": tuple(
            Spec((batch, h), ("batch", "act_gates"), init="zeros",
                 dtype="float32")
            for h in cfg.gru.resolved_layer_dims
            for _ in range(_LEAVES)),
        "pos": Spec((), (), init="zeros", dtype="int32"),
    }


def init_cache(cfg: ModelConfig, batch: int, capacity: int = 0) -> dict:
    cache = init_params(cache_specs(cfg, batch), jax.random.key(0))
    cache["h"] = slstm_core.stack_state0(cfg.gru, batch)  # m leaf = M_INIT
    return cache


def decode_step(params: dict, cfg: ModelConfig, cache: dict, x: jax.Array, *,
                ctx: ShardCtx = ShardCtx()):
    """One recurrent step through the stack: x (B,X) features ->
    (class logits so far, cache). The executor dispatches within the
    ``(slstm, ·)`` namespace — uniform stacks run the fused decode kernel
    (all four state leaves advanced in ONE pallas_call)."""
    p = runtime.compile(cfg.gru, batch=x.shape[0], mode="decode",
                        placement=_placement(ctx))
    hs = p.decode(params, cache["h"], x)
    hs = tuple(constrain(h, ("batch", "act_gates"), ctx) for h in hs)
    logits = runtime.readout(hs[-1], params["head"])
    return logits.astype(jnp.float32), {"h": hs, "pos": cache["pos"] + 1}


def prefill(params: dict, cfg: ModelConfig, batch: dict, *,
            ctx: ShardCtx = ShardCtx()):
    """Run the full sequence, return (logits, flat recurrent state).

    ``batch["mask"]`` (B, T) bool, optional: False timesteps freeze all
    four state leaves (stabilizer included), so left-padded bucketed
    prompts yield the same state as their unpadded originals — streamed
    through whichever backend the executor picks."""
    xs = batch["features"]
    B = xs.shape[0]
    mask = batch.get("mask")
    state0 = slstm_core.stack_state0(cfg.gru, B, jnp.float32)
    p = runtime.compile(cfg.gru, batch=B, seq=xs.shape[1],
                        mask=mask is not None, mode="prefill",
                        placement=_placement(ctx))
    finals = p.prefill(params, state0, xs, mask=mask)
    logits = runtime.readout(finals[-1], params["head"]).astype(jnp.float32)
    cache = {"h": tuple(h.astype(jnp.float32) for h in finals),
             "pos": jnp.array(xs.shape[1] - 1, jnp.int32)}
    return logits, cache
