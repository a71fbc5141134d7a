"""Uniform model API: one namespace per family + abstract input builders.

Everything downstream (trainer, serving engine, dry-run, benchmarks) talks
to models exclusively through this module, so adding an architecture is:
write the module, register it here, add a config.
"""
from __future__ import annotations

from dataclasses import dataclass
from types import SimpleNamespace
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, ShapeConfig
from repro.core.params import Spec
from repro.core import cells as cell_families
from repro.models import gru_lm, hymba, llava, transformer, whisper, xlstm


def _transformer_api():
    return SimpleNamespace(
        specs=transformer.lm_specs,
        loss_fn=lambda p, cfg, batch, ctx: transformer.loss_fn(p, cfg, batch, ctx=ctx),
        forward=lambda p, cfg, batch, ctx: transformer.forward(p, cfg, batch["tokens"], ctx=ctx),
        prefill=lambda p, cfg, batch, ctx: transformer.prefill(p, cfg, batch["tokens"], ctx=ctx),
        decode_step=lambda p, cfg, cache, tok, ctx: transformer.decode_step(p, cfg, cache, tok, ctx=ctx),
        cache_specs=transformer.cache_specs,
        init_cache=transformer.init_cache,
    )


def _llava_api():
    return SimpleNamespace(
        specs=llava.lm_specs,
        loss_fn=lambda p, cfg, batch, ctx: llava.loss_fn(p, cfg, batch, ctx=ctx),
        forward=lambda p, cfg, batch, ctx: llava.forward(p, cfg, batch, ctx=ctx),
        prefill=lambda p, cfg, batch, ctx: llava.prefill(p, cfg, batch, ctx=ctx),
        decode_step=lambda p, cfg, cache, tok, ctx: llava.decode_step(p, cfg, cache, tok, ctx=ctx),
        cache_specs=llava.cache_specs,
        init_cache=llava.init_cache,
    )


def _whisper_api():
    return SimpleNamespace(
        specs=whisper.lm_specs,
        loss_fn=lambda p, cfg, batch, ctx: whisper.loss_fn(p, cfg, batch, ctx=ctx),
        forward=lambda p, cfg, batch, ctx: whisper.forward(p, cfg, batch, ctx=ctx),
        prefill=lambda p, cfg, batch, ctx: whisper.prefill(p, cfg, batch, ctx=ctx),
        decode_step=lambda p, cfg, cache, tok, ctx: whisper.decode_step(p, cfg, cache, tok, ctx=ctx),
        cache_specs=whisper.cache_specs,
        init_cache=whisper.init_cache,
    )


def _xlstm_api():
    return SimpleNamespace(
        specs=xlstm.lm_specs,
        loss_fn=lambda p, cfg, batch, ctx: xlstm.loss_fn(p, cfg, batch, ctx=ctx),
        forward=lambda p, cfg, batch, ctx: xlstm.forward(p, cfg, batch["tokens"], ctx=ctx),
        prefill=lambda p, cfg, batch, ctx: xlstm.prefill(p, cfg, batch["tokens"], ctx=ctx),
        decode_step=lambda p, cfg, cache, tok, ctx: xlstm.decode_step(p, cfg, cache, tok, ctx=ctx),
        cache_specs=xlstm.cache_specs,
        init_cache=xlstm.init_cache,
    )


def _hymba_api():
    return SimpleNamespace(
        specs=hymba.lm_specs,
        loss_fn=lambda p, cfg, batch, ctx: hymba.loss_fn(p, cfg, batch, ctx=ctx),
        forward=lambda p, cfg, batch, ctx: hymba.forward(p, cfg, batch["tokens"], ctx=ctx),
        prefill=lambda p, cfg, batch, ctx: hymba.prefill(p, cfg, batch["tokens"], ctx=ctx),
        decode_step=lambda p, cfg, cache, tok, ctx: hymba.decode_step(p, cfg, cache, tok, ctx=ctx),
        cache_specs=hymba.cache_specs,
        init_cache=hymba.init_cache,
    )


def _gru_api():
    # every cell family (gru, slstm): gru_lm takes what differs from the
    # family's registration (repro.core.cells)
    return SimpleNamespace(
        specs=gru_lm.lm_specs,
        prepare_params=gru_lm.prepare_params,      # one-time serving prep
        executable=gru_lm.serve_executable,        # compiled-plan introspection
        loss_fn=lambda p, cfg, batch, ctx: gru_lm.loss_fn(p, cfg, batch, ctx=ctx),
        forward=lambda p, cfg, batch, ctx: gru_lm.forward(p, cfg, batch, ctx=ctx),
        prefill=lambda p, cfg, batch, ctx: gru_lm.prefill(p, cfg, batch, ctx=ctx),
        decode_step=lambda p, cfg, cache, x, ctx: gru_lm.decode_step(p, cfg, cache, x, ctx=ctx),
        cache_specs=gru_lm.cache_specs,
        init_cache=gru_lm.init_cache,
    )


_FAMS: Dict[str, Callable] = {
    "dense": _transformer_api,
    "moe": _transformer_api,
    "vlm": _llava_api,
    "audio": _whisper_api,
    "ssm": _xlstm_api,
    "hybrid": _hymba_api,
    "gru": _gru_api,
    "slstm": _gru_api,
}


def get_api(cfg: ModelConfig) -> SimpleNamespace:
    try:
        return _FAMS[cfg.family]()
    except KeyError:
        # typed (still a KeyError subclass): serving surfaces fail loudly
        # on an unregistered family instead of silently degrading
        raise cell_families.UnknownCellFamily(
            cfg.family,
            known=set(_FAMS) | set(cell_families.families())) from None


# ---------------------------------------------------------------------------
# input specs: abstract (dry-run) and concrete (smoke/bench) batches
# ---------------------------------------------------------------------------

def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    """Spec tree for the model inputs of one (arch x shape) cell.

    kind="train"/"prefill": the full batch. kind="decode": ONLY the new
    token(s) — the cache is built separately from cache_specs."""
    B, S = shape.global_batch, shape.seq_len
    i32 = "int32"
    if cell_families.is_cell_family(cfg.family):
        # every cell family (gru, slstm, ...) describes its stack shapes
        # through the same GRUConfig fields
        g = cfg.gru
        if shape.kind == "decode":
            return {"x": Spec((B, g.input_dim), ("batch", None), dtype=cfg.dtype)}
        batch = {"features": Spec((B, S, g.input_dim), ("batch", "act_seq", None),
                                  dtype=cfg.dtype),
                 "labels": Spec((B,), ("batch",), dtype=i32)}
        return batch
    if shape.kind == "decode":
        return {"tokens": Spec((B,), ("batch",), dtype=i32)}
    batch = {"tokens": Spec((B, S), ("batch", "act_seq"), dtype=i32)}
    if shape.kind == "train":
        batch["targets"] = Spec((B, S), ("batch", "act_seq"), dtype=i32)
    if cfg.family == "audio":
        batch["frames"] = Spec((B, cfg.encoder.num_frames, cfg.d_model),
                               ("batch", None, None), dtype=cfg.dtype)
    if cfg.family == "vlm":
        batch["patches"] = Spec((B, cfg.vision.num_patches, cfg.vision.embed_dim),
                                ("batch", None, None), dtype=cfg.dtype)
    return batch


def concrete_batch(cfg: ModelConfig, shape: ShapeConfig, seed: int = 0) -> dict:
    """Small deterministic concrete batch for smoke tests and benchmarks."""
    specs = input_specs(cfg, shape)
    rng = np.random.default_rng(seed)

    def make(s: Spec):
        dt = jnp.dtype(s.dtype or "float32")
        if jnp.issubdtype(dt, jnp.integer):
            hi = (cfg.gru.num_classes
                  if cell_families.is_cell_family(cfg.family)
                  else cfg.vocab_size)
            return jnp.asarray(rng.integers(0, hi, size=s.shape), dt)
        return jnp.asarray(rng.normal(size=s.shape), jnp.float32).astype(dt)

    return jax.tree_util.tree_map(make, specs,
                                  is_leaf=lambda x: isinstance(x, Spec))
