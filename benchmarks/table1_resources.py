"""E3 — the paper's Table 1: resource utilization vs hidden size.

AIE columns translate to TPU-native resources (DESIGN.md §2):
  tiles used          -> paper's 3*3*H+1 model (reported for reference) and
                         the Pallas grid cells of the fused-step kernel
  PL FF/LUT           -> VMEM working-set bytes per kernel block
  AIE AGGR TILE LAT   -> unfused (separate-aggregation) HLO op count vs the
                         fused epilogue's, from the lowered step

CSV: name,us_per_call,derived
"""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp

from repro.configs.base import GRUConfig
from repro.core import gru
from repro.core.latency import gru_tile_cost
from repro.core.params import init_params
from repro.launch.compile_cache import enable_compile_cache

HIDDEN = (20, 24, 28, 32)


def _hlo_op_count(cfg: GRUConfig) -> int:
    params = init_params(gru.gru_cell_specs(cfg.input_dim, cfg.hidden_dim),
                         jax.random.key(0))
    h = jax.ShapeDtypeStruct((1, cfg.hidden_dim), jnp.float32)
    x = jax.ShapeDtypeStruct((1, cfg.input_dim), jnp.float32)
    txt = (jax.jit(lambda p, h, x: gru.gru_step(p, h, x=x, cfg=cfg))
           .lower(params, h, x).compile().as_text())
    return len(re.findall(r"^\s+(?:ROOT )?%\S+ = ", txt, re.MULTILINE))


def run(csv=True):
    rows = []
    for H in HIDDEN:
        # paper's tile count and our kernel's VMEM footprint for one block
        tiles = gru_tile_cost(H)
        vmem = (H * 3 * H + 4 * 1 * 3 * H + 2 * 1 * H) * 4   # u + xp/b + h/h'
        fused_ops = _hlo_op_count(GRUConfig(5, H, fused_gates=True))
        unfused_ops = _hlo_op_count(GRUConfig(5, H, fused_gates=False))
        rows.append((f"table1_h{H}", 0.0,
                     f"aie_tiles={tiles};vmem_bytes={vmem};"
                     f"hlo_ops_fused={fused_ops};hlo_ops_unfused={unfused_ops}"))
    if csv:
        for name, us, derived in rows:
            print(f"{name},{us:.2f},{derived}")
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    run()
