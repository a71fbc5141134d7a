"""Decode-step latency: fused persistent stack kernel vs layer-by-layer XLA
(plus the per-layer Pallas chain and, under a mesh, the sharded step).

The paper's figure of merit is the latency of ONE recurrent step. This
benchmark tracks it per PR for the serving implementations:

* ``xla``     — layer-by-layer structural modes (the paper's row-wise
  scheme by default), L separate dispatch chains per step.
* ``fused``   — ONE pallas_call advances the whole batch through all L
  layers (weights pinned in VMEM via constant index maps; interpret mode
  on CPU).
* ``chain``   — per-layer Pallas kernels (``--via runtime`` only; the
  hetero-capable backend, measured so the cost model can rank it).
* ``sharded`` — ONE persistent shard_map step over pre-sharded weights
  (``--mesh N``; requires N host devices, e.g. via XLA_FLAGS).
* ``fused_q8`` / ``chain_q8`` — the int8-weight-row twins (``--q8``,
  implied by ``--emit-costs``): exact-name pins, so they are measured
  regardless of the accuracy gate; every row carries a ``dtype`` column
  (``int8`` vs ``float32``) naming the served datapath.

``--via`` picks how the step is obtained:

* ``direct``  — the legacy entry point ``gru_stack_decode_step(impl=...)``
  (now an executor shim, kept for continuity of the series).
* ``runtime`` — ``repro.core.runtime.compile(cfg, ..., mode="decode")``:
  the compiled-executable path ServeEngine uses; each row then records
  WHICH backend the executable resolved (``backend`` field) and whether
  the choice came from measured calibration (``cost_source``), so the
  artifact documents the dispatch decision alongside the latency.

``--emit-costs`` additionally writes ``BENCH_backend_costs.json`` in the
schema ``repro.core.runtime.CostModel`` loads — the calibration artifact
that turns ``backend="auto"`` into measured per-shape dispatch. It forces
``--via runtime`` (cost entries are keyed by executor backend names) and
adds the ``chain`` impl so every single-host decode candidate is covered
(the CostModel only trusts calibrations that cover ALL legal candidates).
It also measures whole-SEQUENCE (prefill) latency per backend and emits
``op="sequence"`` rows next to the decode ones, so ``auto`` can pick the
prefill backend per shape too (``--seq-len`` sets the measured T).

``--family slstm`` measures the sLSTM cell family through the identical
sweep (xla + fused impls — the names its ``(slstm, ·)`` registry
namespace serves; forces ``--via runtime``). Every row in both artifacts
carries a ``family`` column, so one BENCH_backend_costs.json can hold
measured dispatch rows for several families side by side (the CostModel
keys on it; missing column = gru, pre-registry artifacts load unchanged).

``--mesh N`` extends both sweeps with the shard_map backends: the
``sharded`` decode step (``sharded_decode``), and — for sequences AND
decode — ``pallas_sharded``, the fused shard kernels inside the
shard_map.

Sweeps depth x batch and reports the per-step latency DISTRIBUTION
(p50/p99 — the paper's constraint is a tail bound, not an average), each
step timed individually with a device sync, all impls measured in
alternating rounds (shared-host drift bias). Emits BENCH_gru_decode.json.

    PYTHONPATH=src python benchmarks/decode_latency.py [--smoke] \
        [--via runtime] [--emit-costs] [--mesh N]

CSV: name,us_per_call,derived
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import GRUConfig
from repro.core import cells, gru, runtime
from repro.core.params import init_params
from repro.launch.compile_cache import enable_compile_cache

# impl label -> executor backend preference. ALL exact names: each impl
# pins one backend, so measurements are hermetic even when a stale
# calibration artifact sits in the cwd (a family pref like "pallas" would
# let measured costs from a previous run pick pallas_chain for the
# "fused" rows and drop pallas_fused from the emitted coverage). The
# "sharded" label pins the op-matching shard_map backend (sharded_decode
# for decode steps, sharded for sequences); pallas_sharded serves both.
_IMPL_PREF = {"xla": "xla", "fused": "pallas_fused", "chain": "pallas_chain",
              "sharded": "sharded_decode", "pallas_sharded": "pallas_sharded",
              "fused_q8": "pallas_fused_q8", "chain_q8": "pallas_chain_q8"}
_SEQ_IMPL_PREF = {"xla": "xla", "fused": "pallas_fused",
                  "chain": "pallas_chain", "sharded": "sharded",
                  "pallas_sharded": "pallas_sharded",
                  "fused_q8": "pallas_fused_q8", "chain_q8": "pallas_chain_q8"}
_MESH_IMPLS = ("sharded", "pallas_sharded")
_Q8_IMPLS = ("fused_q8", "chain_q8")

# impls each cell family registers backends for (``--family``): the sLSTM
# family serves xla + pallas_fused only (no chain/q8/sharded twins yet),
# and both its backend names resolve in the (slstm, ·) registry namespace
# under the same impl labels as GRU's.
_FAMILY_IMPLS = {
    "gru": tuple(_IMPL_PREF),
    "slstm": ("xla", "fused"),
}


def _family_params_state(cfg: GRUConfig, batch: int):
    """(raw params pytree, initial flat state) for ``cfg``'s cell family.
    The GRU path is kept byte-for-byte on its historical code path so the
    measured rows stay comparable across the artifact series."""
    if cells.cfg_family(cfg) == "gru":
        return (init_params(gru.gru_stack_specs(cfg), jax.random.key(0)),
                gru.stack_h0(cfg, batch))
    fam = cells.get_family(cfg.family)
    raw = init_params({"cells": fam.stack_specs(cfg)}, jax.random.key(0))
    return raw, fam.state0(cfg, batch)


def _make_step(cfg: GRUConfig, impl: str, batch: int, via: str = "direct",
               placement=None):
    """(jitted step fn, params, warm state, input, backend, cost_source)
    for one impl routed either through the legacy entry point or the
    compiled executable."""
    raw, hs = _family_params_state(cfg, batch)
    rcfg = dataclasses.replace(cfg, backend=_IMPL_PREF[impl])
    # serving prepares params once (ServeEngine via runtime.prepare);
    # measure the same placement-resident fast path here
    params = runtime.prepare(raw, rcfg, placement)
    x = jnp.ones((batch, cfg.input_dim))
    if via == "runtime":
        exe = runtime.compile(rcfg, batch=batch, placement=placement,
                              mode="decode")
        backend, src = exe.decode_backend, exe.cost_source
        f = jax.jit(lambda p, h, xv: exe.decode(p, h, xv))
    else:
        assert impl in ("xla", "fused"), \
            f"--via direct serves xla/fused only, not {impl!r}"
        assert cells.cfg_family(cfg) == "gru", \
            "--via direct is the legacy GRU entry point; other families " \
            "measure --via runtime"
        backend, src = impl, "n/a"
        params = {"cells": params.cells,
                  **({"stacked_cells": params.stacked}
                     if params.stacked is not None else {})}
        f = jax.jit(lambda p, h, xv: gru.gru_stack_decode_step(
            p, h, xv, cfg=cfg,
            impl="pallas" if impl == "fused" else impl))
    with warnings.catch_warnings():
        # the legacy shim warns at first TRACE, i.e. on this first call
        warnings.simplefilter("ignore", DeprecationWarning)
        out = f(params, hs, x)
    out[-1].block_until_ready()
    return f, params, out, x, backend, src


def _per_step_times(cfg: GRUConfig, batch: int, iters: int, via: str,
                    impls=("xla", "fused"), placement=None,
                    warmup: int = 10, rounds: int = 10):
    """Per-step latencies for ALL impls, measured in alternating rounds so
    machine-load drift (shared CI hosts) biases no implementation."""
    bench, backends, sources = {}, {}, {}
    for impl in impls:
        f, params, out, x, backend, src = _make_step(
            cfg, impl, batch, via,
            placement=placement if impl in _MESH_IMPLS else None)
        bench[impl] = (f, params, out, x)
        backends[impl] = backend
        sources[impl] = src
    ts = {impl: [] for impl in bench}
    for impl, (f, params, out, x) in bench.items():
        for _ in range(warmup):
            out = f(params, out, x)
        out[-1].block_until_ready()
        bench[impl] = (f, params, out, x)
    per_round = max(iters // rounds, 1)
    for _ in range(rounds):
        for impl, (f, params, out, x) in bench.items():
            for _ in range(per_round):
                t0 = time.perf_counter()
                out = f(params, out, x)
                out[-1].block_until_ready()
                ts[impl].append(time.perf_counter() - t0)
            bench[impl] = (f, params, out, x)
    return {impl: np.array(v) for impl, v in ts.items()}, backends, sources


def _make_seq(cfg: GRUConfig, impl: str, batch: int, seq_len: int,
              placement=None):
    """(jitted prefill fn, prepared params, h0s, xs, backend, cost_source)
    for one sequence impl, always via the compiled executable (sequence
    cost rows are keyed by executor backend names)."""
    raw, h0s = _family_params_state(cfg, batch)
    rcfg = dataclasses.replace(cfg, backend=_SEQ_IMPL_PREF[impl])
    params = runtime.prepare(raw, rcfg, placement)
    xs = jnp.ones((batch, seq_len, cfg.input_dim))
    exe = runtime.compile(rcfg, batch=batch, seq=seq_len,
                          placement=placement, mode="prefill")
    f = jax.jit(lambda p, h, x: exe.prefill(p, h, x))
    out = f(params, h0s, xs)
    out[-1].block_until_ready()
    return f, params, h0s, xs, exe.sequence_backend, exe.cost_source


def _per_seq_times(cfg: GRUConfig, batch: int, seq_len: int, iters: int,
                   impls=("xla", "fused"), placement=None, warmup: int = 3,
                   rounds: int = 5):
    """Whole-sequence (prefill) latencies for ALL impls, interleaved in
    alternating rounds like the decode sweep (same drift-bias rule)."""
    bench, backends, sources = {}, {}, {}
    for impl in impls:
        f, params, h0s, xs, backend, src = _make_seq(
            cfg, impl, batch, seq_len,
            placement=placement if impl in _MESH_IMPLS else None)
        bench[impl] = (f, params, h0s, xs)
        backends[impl] = backend
        sources[impl] = src
    ts = {impl: [] for impl in bench}
    for impl, (f, params, h0s, xs) in bench.items():
        for _ in range(warmup):
            f(params, h0s, xs)[-1].block_until_ready()
    per_round = max(iters // rounds, 1)
    for _ in range(rounds):
        for impl, (f, params, h0s, xs) in bench.items():
            for _ in range(per_round):
                t0 = time.perf_counter()
                f(params, h0s, xs)[-1].block_until_ready()
                ts[impl].append(time.perf_counter() - t0)
    return {impl: np.array(v) for impl, v in ts.items()}, backends, sources


def emit_costs(rows, json_path: str = "BENCH_backend_costs.json",
               csv: bool = True) -> dict:
    """Convert measured rows into the CostModel calibration artifact.

    Schema (``repro.core.runtime.CostModel.load``): one entry per
    (family, backend, op, depth, batch, hidden_dim) with the measured
    ``p50_us`` — ``op`` is ``"decode"`` or ``"sequence"`` (rows without an
    ``op`` field are decode rows from older sweeps; rows without a
    ``family`` column are GRU rows from pre-registry sweeps). Rows must
    come from ``--via runtime`` so ``backend`` holds executor backend
    names (the keys dispatch ranks by)."""
    seen, entries = set(), []
    for r in rows:
        if r.get("via") != "runtime":
            continue
        op = r.get("op", "decode")
        fam = r.get("family", "gru")
        key = (fam, r["backend"], op, r["depth"], r["batch"],
               r["hidden_dim"])
        if key in seen:
            continue
        seen.add(key)
        entries.append({"family": fam, "backend": r["backend"], "op": op,
                        "depth": r["depth"], "batch": r["batch"],
                        "hidden_dim": r["hidden_dim"],
                        "p50_us": r["p50_us"]})
    out = {"bench": "gru_backend_costs", "schema": 1,
           "device": jax.default_backend(), "entries": entries}
    with open(json_path, "w") as f:
        json.dump(out, f, indent=2)
    if csv:
        print(f"decode_costs_artifact,0.00,{json_path};"
              f"entries={len(entries)}")
    return out


def run(depths=(1, 2, 3), batches=(1, 8, 32), H=32, X: int = 5,
        iters: int = 300, json_path: str = "BENCH_gru_decode.json",
        csv: bool = True, via: str = "direct",
        impls=("xla", "fused"), mesh_axis: int = 0,
        costs_path: str = None, seq_len: int = 0, seq_iters: int = None,
        family: str = "gru"):
    """Depth x batch x hidden x impl sweep; emits the BENCH_gru_decode.json
    artifact (and, with ``costs_path``, the CostModel calibration).
    ``seq_len`` > 0 additionally measures whole-sequence prefill latency
    per impl at that T (``op="sequence"`` rows — the prefill half of the
    calibration). ``H`` may be one hidden size or a tuple — the q8 rows
    only become interesting at serving widths (the int8 working-set win is
    a bandwidth effect: B=1, H >= 256). ``family`` selects the cell family
    (``repro.core.cells``) every row measures and is recorded as a column
    in both artifacts; impls the family has no backend for are dropped."""
    allowed = _FAMILY_IMPLS[family]
    dropped = tuple(i for i in impls if i not in allowed)
    impls = tuple(i for i in impls if i in allowed)
    if dropped and csv:
        print(f"decode_family_drop,0.00,family={family};"
              f"no_backend_for={'/'.join(dropped)}")
    placement = None
    if mesh_axis:
        assert len(jax.devices()) >= mesh_axis, (
            f"--mesh {mesh_axis} needs {mesh_axis} devices; set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={mesh_axis}")
        from repro.compat import make_mesh
        placement = runtime.Placement(mesh=make_mesh((mesh_axis,),
                                                     ("model",)))
        impls = tuple(impls) + _MESH_IMPLS
    hiddens = (H,) if isinstance(H, int) else tuple(H)
    rows = []
    for H in hiddens:
        for L in depths:
            for B in batches:
                _sweep_one(rows, L, B, H, X, iters, via, impls, placement,
                           seq_len, seq_iters, csv, family)
    summary = _summarize(rows, depths, batches, hiddens)
    out = {"bench": "gru_decode_step_latency", "family": family,
           "backend": jax.default_backend(), "via": via,
           "rows": rows, "summary": summary}
    with open(json_path, "w") as f:
        json.dump(out, f, indent=2)
    if csv:
        for k, v in summary.items():
            print(f"decode_{k},{v:.3f},speedup")
        print(f"decode_artifact,0.00,{json_path}")
    if costs_path:
        emit_costs(rows, costs_path, csv=csv)
    return out


def _sweep_one(rows, L, B, H, X, iters, via, impls, placement, seq_len,
               seq_iters, csv, family: str = "gru"):
    cfg = GRUConfig(input_dim=X, hidden_dim=H, num_layers=L, family=family)
    series, backends, sources = _per_step_times(
        cfg, B, iters, via, impls=impls, placement=placement)
    for impl, ts in series.items():
        row = {"op": "decode", "family": family,
               "depth": L, "batch": B, "impl": impl,
               "hidden_dim": H,
               "input_dim": X, "steps": len(ts),
               "via": via, "backend": backends[impl],
               "dtype": runtime.backend_dtype(backends[impl]),
               "cost_source": sources[impl],
               "p50_us": round(float(np.percentile(ts, 50)) * 1e6, 2),
               "p90_us": round(float(np.percentile(ts, 90)) * 1e6, 2),
               "p99_us": round(float(np.percentile(ts, 99)) * 1e6, 2),
               "mean_us": round(float(ts.mean()) * 1e6, 2)}
        rows.append(row)
        tag = "" if family == "gru" else f"{family}_"
        if csv:
            print(f"decode_{tag}L{L}_B{B}_H{H}_{impl},{row['p50_us']:.2f},"
                  f"p99={row['p99_us']:.2f}us;backend={row['backend']}")
    if seq_len:
        seq_impls = tuple(i for i in impls if i in _SEQ_IMPL_PREF)
        series, backends, sources = _per_seq_times(
            cfg, B, seq_len, seq_iters or max(iters // 4, 20),
            impls=seq_impls, placement=placement)
        for impl, ts in series.items():
            row = {"op": "sequence", "family": family,
                   "depth": L, "batch": B,
                   "impl": impl, "hidden_dim": H, "input_dim": X,
                   "seq_len": seq_len, "steps": len(ts),
                   "via": "runtime", "backend": backends[impl],
                   "dtype": runtime.backend_dtype(backends[impl]),
                   "cost_source": sources[impl],
                   "p50_us": round(float(np.percentile(ts, 50)) * 1e6, 2),
                   "p99_us": round(float(np.percentile(ts, 99)) * 1e6, 2),
                   "mean_us": round(float(ts.mean()) * 1e6, 2)}
            rows.append(row)
            tag = "" if family == "gru" else f"{family}_"
            if csv:
                print(f"seq_{tag}L{L}_B{B}_H{H}_T{seq_len}_{impl},"
                      f"{row['p50_us']:.2f},"
                      f"p99={row['p99_us']:.2f}us;"
                      f"backend={row['backend']}")


def _summarize(rows, depths, batches, hiddens):
    """Per-depth fused-vs-xla speedups (legacy keys, at the smallest swept
    hidden/batch) plus per-shape q8-vs-f32 speedups wherever both the f32
    and the int8 fused rows were measured."""
    summary = {}
    for L in depths:
        pair = {r["impl"]: r for r in rows
                if r.get("op", "decode") == "decode"
                and r["depth"] == L and r["batch"] == min(batches)
                and r["hidden_dim"] == min(hiddens)}
        if {"xla", "fused"} <= pair.keys():
            summary[f"p50_speedup_depth{L}"] = round(
                pair["xla"]["p50_us"] / max(pair["fused"]["p50_us"], 1e-9), 3)
    for H in hiddens:
        for L in depths:
            for B in batches:
                pair = {r["impl"]: r for r in rows
                        if r.get("op", "decode") == "decode"
                        and r["depth"] == L and r["batch"] == B
                        and r["hidden_dim"] == H}
                if {"fused", "fused_q8"} <= pair.keys():
                    summary[f"q8_p50_speedup_L{L}_B{B}_H{H}"] = round(
                        pair["fused"]["p50_us"]
                        / max(pair["fused_q8"]["p50_us"], 1e-9), 3)
    return summary


if __name__ == "__main__":
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced sweep for CI (still emits the artifacts)")
    ap.add_argument("--via", choices=("direct", "runtime"), default="direct",
                    help="route steps through the legacy entry point or the "
                         "compiled executable (records the resolved backend "
                         "in the artifact)")
    ap.add_argument("--emit-costs", nargs="?", const="BENCH_backend_costs.json",
                    default=None, metavar="PATH",
                    help="also write the CostModel calibration artifact "
                         "(forces --via runtime and adds the chain impl so "
                         "every single-host decode candidate is covered)")
    ap.add_argument("--mesh", type=int, default=0, metavar="N",
                    help="also measure the shard_map backends (the sharded "
                         "decode step and pallas_sharded sequence+decode) "
                         "on an N-device mesh (needs N host devices via "
                         "XLA_FLAGS)")
    ap.add_argument("--seq-len", type=int, default=0, metavar="T",
                    help="also measure whole-sequence prefill latency at "
                         "this T per impl (op=\"sequence\" rows; "
                         "--emit-costs defaults it to 16 so the "
                         "calibration covers prefill dispatch too)")
    ap.add_argument("--depths", type=int, nargs="+", default=None)
    ap.add_argument("--batches", type=int, nargs="+", default=None)
    ap.add_argument("--hidden", type=int, nargs="+", default=None,
                    metavar="H",
                    help="hidden sizes to sweep (default 32; the q8 rows "
                         "want serving widths too, e.g. --hidden 32 512)")
    ap.add_argument("--q8", action="store_true",
                    help="also measure the int8 backends (fused_q8 + "
                         "chain_q8 rows, exact-name pins — no accuracy "
                         "artifact needed to MEASURE them); --emit-costs "
                         "implies it so the calibration carries their "
                         "CostModel rows")
    ap.add_argument("--family", choices=sorted(_FAMILY_IMPLS),
                    default="gru",
                    help="cell family to measure (repro.core.cells "
                         "registry); slstm serves xla + fused only and "
                         "forces --via runtime; rows carry a family "
                         "column in both artifacts")
    ap.add_argument("--iters", type=int, default=None)
    ap.add_argument("--json", default="BENCH_gru_decode.json")
    args = ap.parse_args()
    via = args.via
    impls = ("xla", "fused")
    if args.family != "gru":
        via = "runtime"                 # legacy direct path is GRU-only
    seq_len = args.seq_len
    if args.emit_costs:
        via = "runtime"                 # cost entries need backend names
        impls = ("xla", "fused", "chain")
        seq_len = seq_len or 16         # calibrate prefill dispatch too
    if args.q8 or args.emit_costs:
        via = "runtime"                 # q8 impls are executor-only
        impls = tuple(impls) + _Q8_IMPLS
    if args.mesh:
        via = "runtime"                 # the sharded impls are executor-only
    if args.smoke:
        run(depths=tuple(args.depths or (1, 3)),
            batches=tuple(args.batches or (1, 8)),
            H=tuple(args.hidden or (32,)),
            iters=args.iters or 120, json_path=args.json, via=via,
            impls=impls, mesh_axis=args.mesh, costs_path=args.emit_costs,
            seq_len=seq_len, family=args.family)
    else:
        run(depths=tuple(args.depths or (1, 2, 3)),
            batches=tuple(args.batches or (1, 8, 32)),
            H=tuple(args.hidden or (32,)),
            iters=args.iters or 300, json_path=args.json, via=via,
            impls=impls, mesh_axis=args.mesh, costs_path=args.emit_costs,
            seq_len=seq_len, family=args.family)
