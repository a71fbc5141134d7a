"""The recurrent cell families (``gru-jet``, deep GRU stacks, ``slstm-jet``)
behind the framework model API: one module serves every family registered
in :mod:`repro.core.cells`.

Forward/loss = the jet-tagging sequence classifier (recurrent stack +
linear head; the paper's validated configuration is one GRU layer, H=20,
X=5, 5 classes). Serving = single-step recurrent decode through the whole
stack, the paper's latency-measurement path. What differs by family is
taken from its :class:`~repro.core.cells.CellFamily`: the parameter specs
(``stack_specs``), the initial state (``state0``: zeros, and the sLSTM
stabilizer at ``M_INIT``; in ``state_dtype`` where the family fixes one)
and the state's leaves per layer
(``state_leaves``: the GRU's ``h``; the sLSTM's ``c, n, m, h``). The cache
carries the family's flat state tuple under ``"h"``, layer-major, each
leaf a ``(B, H)`` array; the readout hidden state is the last leaf.

All execution routes through the capability-dispatched executor
(``repro.core.runtime``) via its two-stage compile/execute API:
``prefill``/``decode_step`` ask ``compile()`` for a memoized
``GRUExecutable`` from the family's ``(family, backend)`` namespace
(fused Pallas stack, per-layer Pallas chain, XLA scan, or — GRU only —
the shard_map programs when the ``ShardCtx`` carries a mesh — the ctx
mesh becomes the executable's ``Placement``, and mesh prefill resolves
to ``pallas_sharded``, the fused shard kernels INSIDE the shard_map,
unless pinned or calibrated otherwise), and ``serve_executable`` exposes
the resolved executable so the serving engine can record which backend
actually runs (e.g. that a masked bucketed prefill executes the Pallas
kernel, not an XLA fallback).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import cells, runtime
from repro.core import gru as gru_core
from repro.core.params import Spec, init_params
from repro.distributed.sharding import ShardCtx, constrain


def _family(cfg: ModelConfig) -> cells.CellFamily:
    return cells.get_family(cells.cfg_family(cfg.gru))


def lm_specs(cfg: ModelConfig) -> dict:
    return gru_core.gru_classifier_specs(cfg.gru)


def forward(params: dict, cfg: ModelConfig, batch: dict, *,
            ctx: ShardCtx = ShardCtx()) -> jax.Array:
    """batch: {features (B,T,X)} -> class logits (B,C)."""
    return gru_core.gru_classify(params, batch["features"], cfg=cfg.gru)


def loss_fn(params: dict, cfg: ModelConfig, batch: dict, *,
            ctx: ShardCtx = ShardCtx()):
    """batch: {features (B,T,X), labels (B,)} -> softmax CE."""
    logits = forward(params, cfg, batch, ctx=ctx).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, batch["labels"][:, None], axis=-1)[:, 0]
    loss = (lse - ll).mean()
    acc = (logits.argmax(-1) == batch["labels"]).mean()
    return loss, {"ce": loss, "acc": acc, "aux": jnp.zeros((), jnp.float32)}


# --- serving: the paper's latency path ---------------------------------------

def _placement(ctx: ShardCtx) -> runtime.Placement:
    """The ctx mesh resolved to an executor Placement (host if none)."""
    return (runtime.HOST if ctx.mesh is None
            else runtime.Placement(mesh=ctx.mesh))


def prepare_params(params: dict, cfg: ModelConfig,
                   ctx: ShardCtx = ShardCtx()) -> dict:
    """One-time serving prep, delegated to ``runtime.prepare`` with the
    ctx's placement: attach the stacked-weight views the fused kernels
    consume (``"stacked_cells"``) so the per-step decode trace never
    restacks U/W/b, and — under a mesh — perform the sharded backends'
    gate-major reshapes and ``device_put``s up front
    (``"placed_cells"``), so traced execute calls do no weight placement.
    When the config requests the q8 datapath (``cfg.gru.quant`` or a
    ``*_q8`` backend pin) the int8 weight views are computed here too
    (``"quant_cells"``) — the serve trace then contains no weight
    quantization ops. No-op for already-prepared params."""
    sp = runtime.prepare(params, cfg.gru, _placement(ctx))
    out = {"cells": sp.cells, "head": params["head"]}
    if sp.stacked is not None:
        out["stacked_cells"] = sp.stacked
    if sp.placed is not None:
        out["placed_cells"] = sp.placed
    if sp.quant is not None:
        out["quant_cells"] = sp.quant
    return out


def serve_executable(cfg: ModelConfig, *, batch: int, seq: int = None,
                     masked: bool = False, mode: str = "serve",
                     mesh=None) -> runtime.GRUExecutable:
    """The executable a serving call with these shapes will use (same
    memoized object ``prefill``/``decode_step`` resolve internally) —
    lets the engine assert/record backend choices without re-compiling."""
    return runtime.compile(cfg.gru, batch=batch, seq=seq, placement=mesh,
                           mask=masked, mode=mode)


def cache_specs(cfg: ModelConfig, batch: int, capacity: int = 0) -> dict:
    """Recurrent cache: the family's state leaves PER LAYER of the stack,
    layer-major. Zeros are not every family's initial state (the sLSTM
    stabilizer starts at ``M_INIT``): use :func:`init_cache` (or a
    ``prefill``-produced cache), never ``init_params`` on these specs."""
    leaves = _family(cfg).state_leaves
    return {
        "h": tuple(
            Spec((batch, h), ("batch", "act_gates"), init="zeros",
                 dtype="float32")
            for h in cfg.gru.resolved_layer_dims
            for _ in range(leaves)),
        "pos": Spec((), (), init="zeros", dtype="int32"),
    }


def init_cache(cfg: ModelConfig, batch: int, capacity: int = 0) -> dict:
    cache = init_params(cache_specs(cfg, batch), jax.random.key(0))
    cache["h"] = _family(cfg).state0(cfg.gru, batch, jnp.float32)
    return cache


def decode_step(params: dict, cfg: ModelConfig, cache: dict, x: jax.Array, *,
                ctx: ShardCtx = ShardCtx()):
    """One recurrent step through the stack: x (B,X) features ->
    (class logits so far, cache).

    The executor dispatches: with ``cfg.gru.backend == "pallas"`` (uniform
    layer sizes) the whole depth runs as ONE fused pallas_call — every
    layer's cache leaves are stacked device-side and fed straight to the
    kernel, no host round trips on the latency-critical path; hetero
    stacks run the per-layer Pallas chain. Params prepared by
    ``prepare_params`` carry pre-stacked (and, under a mesh, pre-placed)
    weights so the step also does no per-token weight restacking."""
    p = runtime.compile(cfg.gru, batch=x.shape[0], mode="decode",
                        placement=_placement(ctx))
    hs = p.decode(params, cache["h"], x)
    hs = tuple(constrain(h, ("batch", "act_gates"), ctx) for h in hs)
    logits = runtime.readout(hs[-1], params["head"])
    return logits.astype(jnp.float32), {"h": hs, "pos": cache["pos"] + 1}


def prefill(params: dict, cfg: ModelConfig, batch: dict, *,
            ctx: ShardCtx = ShardCtx()):
    """Run the full sequence, return (logits, flat recurrent state).

    ``batch["mask"]`` (B, T) bool, optional: False timesteps freeze every
    state leaf (the sLSTM stabilizer included), so left-padded bucketed
    prompts (ServeEngine) yield the same state as their unpadded
    originals — streamed through whichever backend the executor picks
    (the fused Pallas kernels included; masked bucketed prefill no longer
    falls back to the XLA scan)."""
    xs = batch["features"]
    B = xs.shape[0]
    mask = batch.get("mask")
    fam = _family(cfg)
    state0 = fam.state0(cfg.gru, B, fam.state_dtype or xs.dtype)
    p = runtime.compile(cfg.gru, batch=B, seq=xs.shape[1],
                        mask=mask is not None, mode="prefill",
                        placement=_placement(ctx))
    finals = p.prefill(params, state0, xs, mask=mask)
    logits = runtime.readout(finals[-1], params["head"]).astype(jnp.float32)
    cache = {"h": tuple(h.astype(jnp.float32) for h in finals),
             "pos": jnp.array(xs.shape[1] - 1, jnp.int32)}
    return logits, cache
