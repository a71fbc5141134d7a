"""E1/E2 — the paper's Fig. 3: GRU forward-pass latency vs hidden size and
input size, Hybrid (fused aggregation) vs AIE (unfused).

Two measurements per point:

* measured   — wall-clock of the jitted single-step serve path on THIS host
  (CPU; relative behaviour, not v5e numbers),
* analytic   — the v5e latency model (repro.core.latency.gru_step_model),
  which reproduces the paper's two key findings:
  (1) fused/hybrid aggregation beats unfused as H grows,
  (2) decoupled W.x makes latency flat in X until the input GEMM dominates.

CSV: name,us_per_call,derived
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.configs.base import GRUConfig
from repro.core import gru
from repro.core.latency import gru_step_model
from repro.core.params import init_params
from repro.launch.compile_cache import enable_compile_cache

HIDDEN = (20, 24, 28, 32)
INPUTS = (5, 8, 32, 128, 256)


def _measure_step(cfg: GRUConfig, iters: int = 300) -> float:
    params = init_params(gru.gru_cell_specs(cfg.input_dim, cfg.hidden_dim),
                         jax.random.key(0))
    h = jnp.zeros((1, cfg.hidden_dim))
    x = jnp.ones((1, cfg.input_dim))
    step = jax.jit(lambda p, h, x: gru.gru_step(p, h, x=x, cfg=cfg))
    out = step(params, h, x)
    out.block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        out = step(params, out, x)
    out.block_until_ready()
    return (time.perf_counter() - t0) / iters * 1e6


def run(csv=True, iters: int = 300):
    rows = []
    for H in HIDDEN:
        for fused, label in ((True, "hybrid"), (False, "aie_unfused")):
            cfg = GRUConfig(input_dim=5, hidden_dim=H, fused_gates=fused)
            us = _measure_step(cfg, iters)
            model = gru_step_model(H, 5, fused_gates=fused)
            rows.append((f"fig3_h{H}_{label}", us,
                         f"v5e_model_ns={model.total_s*1e9:.1f}"))
    for X in INPUTS:
        for dec, label in ((True, "decoupled"), (False, "inline")):
            cfg = GRUConfig(input_dim=X, hidden_dim=32, decoupled_wx=dec)
            model = gru_step_model(32, X, decoupled_wx=dec)
            # measured path: decoupling shows up at the sequence level
            us = _measure_step(cfg, iters // 2)
            rows.append((f"fig3_x{X}_{label}", us,
                         f"v5e_model_ns={model.total_s*1e9:.1f}"))
    if csv:
        for name, us, derived in rows:
            print(f"{name},{us:.2f},{derived}")
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    run()
