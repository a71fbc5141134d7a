"""E4 — row-wise vs cascade parallelization of the recurrent matvec.

Four views:
  (a) single-host wall-clock of the two STRUCTURAL modes (lax.map grid vs
      sequential-accumulation scan) at paper sizes and LM sizes,
  (b) the analytic v5e model across row_shards (the AIE-tiles -> TPU-chips
      translation of the paper's scaling argument),
  (c) collective bytes/ops parsed from the compiled shard_map programs on a
      4-device host mesh (subprocess; all-gather-only vs psum — Fig. 1b's
      aggregation study), including the beyond-paper v3 single-aggregation
      variant,
  (d) DEPTH SWEEP (``--num-layers 1 2 4``): per-step decode latency of a
      deep GRU stack per structural mode, written to BENCH_gru_depth.json —
      the paper's figure of merit extended to multi-layer stacks.

CSV: name,us_per_call,derived
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp

from repro.configs.base import GRUConfig
from repro.core import gru, runtime
from repro.core.latency import gru_step_model
from repro.core.params import init_params
from repro.launch.compile_cache import enable_compile_cache

_SUB = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp
from repro.configs.base import GRUConfig
from repro.compat import make_mesh
from repro.core import gru, rowparallel
from repro.core.params import init_params
from repro.launch.hloparse import analyze
H, X, B, T = 64, 16, 1, 8
mesh = make_mesh((4,), ("model",))
params = init_params(gru.gru_cell_specs(X, H), jax.random.key(0))
h0 = jnp.zeros((B, H)); xs = jnp.ones((B, T, X))
for mode in ("rowwise", "cascade"):
    for variant in ("v1", "v3"):
        cfg = GRUConfig(input_dim=X, hidden_dim=H, matvec_mode=mode, variant=variant)
        f = jax.jit(lambda p, h, x: rowparallel.gru_sequence_sharded(p, h, x, mesh=mesh, cfg=cfg))
        a = analyze(f.lower(params, h0, xs).compile().as_text())
        kinds = ",".join(f"{k}:{int(v)}" for k, v in sorted(a.coll_counts.items()))
        print(f"E4SUB,{mode}_{variant},{a.total_coll_bytes:.0f},{kinds}")
"""


def _measure_seq(cfg: GRUConfig, H: int, X: int, T: int = 32,
                 iters: int = 50) -> float:
    params = init_params(gru.gru_cell_specs(X, H), jax.random.key(0))
    h0 = jnp.zeros((1, H))
    xs = jnp.ones((1, T, X))
    exe = runtime.compile(cfg, batch=1, seq=T, mode="sequence")
    f = jax.jit(lambda p, h, x: exe.sequence(p, (h,), x)[0][0])
    f((params,), h0, xs).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        out = f((params,), h0, xs)
    out.block_until_ready()
    return (time.perf_counter() - t0) / iters * 1e6


def _measure_stack_decode(cfg: GRUConfig, iters: int = 200):
    """Per-step decode latency (us) of one compiled-executable pass through
    the stack, plus the backend the executable resolved."""
    params = runtime.prepare(
        init_params(gru.gru_stack_specs(cfg), jax.random.key(0)), cfg)
    hs = gru.stack_h0(cfg, 1)
    x = jnp.ones((1, cfg.input_dim))
    exe = runtime.compile(cfg, batch=1, mode="decode")
    f = jax.jit(lambda p, h, xv: exe.decode(p, h, xv))
    out = f(params, hs, x)
    out[-1].block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        out = f(params, out, x)
    out[-1].block_until_ready()
    return (time.perf_counter() - t0) / iters * 1e6, exe.decode_backend


def run_depth_sweep(layers=(1, 2, 4), H: int = 32, X: int = 5,
                    json_path: str = "BENCH_gru_depth.json", csv=True):
    """Decode-latency depth sweep; emits the BENCH_gru_depth.json artifact."""
    results = []
    for L in layers:
        for mode in ("rowwise", "cascade", "dense"):
            cfg = GRUConfig(input_dim=X, hidden_dim=H, num_layers=L,
                            matvec_mode=mode)
            us, backend = _measure_stack_decode(cfg)
            results.append({"num_layers": L, "mode": mode, "hidden_dim": H,
                            "input_dim": X, "backend": backend,
                            "decode_step_us": round(us, 2)})
            if csv:
                print(f"e4_depth_L{L}_{mode},{us:.2f},stack_decode_step;"
                      f"backend={backend}")
    with open(json_path, "w") as f:
        json.dump({"bench": "gru_depth_decode_latency", "rows": results}, f,
                  indent=2)
    if csv:
        print(f"e4_depth_artifact,0.00,{json_path}")
    return results


def run(csv=True):
    rows = []
    for H, X in ((32, 5), (256, 64)):
        for mode in ("rowwise", "cascade", "dense"):
            cfg = GRUConfig(input_dim=X, hidden_dim=H, matvec_mode=mode)
            us = _measure_seq(cfg, H, X)
            rows.append((f"e4_seq_h{H}_{mode}", us, "structural_wall_clock"))
    for shards in (1, 4, 16):
        m = gru_step_model(1024, 256, row_shards=shards, dtype_bytes=2)
        rows.append((f"e4_model_shards{shards}", 0.0,
                     f"v5e_step_ns={m.total_s*1e9:.1f};"
                     f"coll_ns={m.collective_s*1e9:.1f}"))
    # (c) compiled collective study: a host-mesh HLO study in a child with
    # four virtual CPU devices. The child is held to the CPU: this process
    # already holds the accelerator, if there is one.
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _SUB], env=env, text=True,
                         capture_output=True, timeout=420)
    if out.returncode != 0:
        raise RuntimeError("collective study child failed "
                           f"(rc={out.returncode}):\n{out.stderr[-2000:]}")
    for line in out.stdout.splitlines():
        if line.startswith("E4SUB,"):
            _, name, cbytes, kinds = line.split(",", 3)
            rows.append((f"e4_coll_{name}", 0.0,
                         f"coll_bytes={cbytes};{kinds}"))
    if csv:
        for name, us, derived in rows:
            print(f"{name},{us:.2f},{derived}")
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--num-layers", type=int, nargs="+", default=None,
                    help="run ONLY the depth sweep at these stack depths")
    ap.add_argument("--depth-json", default="BENCH_gru_depth.json")
    args = ap.parse_args()
    if args.num_layers:
        run_depth_sweep(tuple(args.num_layers), json_path=args.depth_json)
    else:
        run()
        run_depth_sweep(json_path=args.depth_json)
