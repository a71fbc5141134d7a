"""Serving driver: batched requests through the ServeEngine, or — with
``--replicas N`` (N > 1, cell families only) — through the fault-tolerant
FleetRouter (``repro.serve.fleet``). Cell-family archs (gru-jet,
slstm-jet, ...) serve feature-vector waves; which family a config runs is
resolved through the ``repro.core.cells`` registry, never hardcoded.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --smoke \
        --requests 4 --max-new 16
    PYTHONPATH=src python -m repro.launch.serve --arch gru-jet --smoke \
        --replicas 2 --inject-faults --requests 8

GRU waves run bucketed continuous batching: ``--slots`` bounds the live
batch (defaults to ``--requests``); give MORE requests than slots to
exercise mid-wave admit/retire. ``--gru-backend`` sets the executor
preference (``repro.core.runtime``): ``pallas`` serves through the fused
persistent stack kernel (one pallas_call per step), ``auto`` lets the
plan pick the cheapest legal backend. The resolved prefill/decode
backends are printed with the latency stats.

``--async`` serves through the asyncio front-end
(``repro.serve.async_frontend``): one client coroutine per request over a
FleetRouter — solo (``--replicas 1``) or fleet — with token streams
bitwise-identical to the synchronous path.

Fleet mode: ``--routing`` picks depth-aware vs static round-robin
dispatch; ``--inject-faults`` runs a seeded kill/restore + slow schedule
under a deterministic ManualClock (virtual time, zero sleeps) and prints
the fleet's fault accounting — the CLI face of ``docs/serving.md``.
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import numpy as np

from repro.configs.base import get_config, get_smoke_config
from repro.core import cells as cell_families
from repro.core.params import init_params
from repro.distributed.sharding import ShardCtx
from repro.launch.compile_cache import enable_compile_cache
from repro.models import api as mapi
from repro.serve.engine import Request, ServeEngine


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True)
    p.add_argument("--smoke", action="store_true")
    p.add_argument("--requests", type=int, default=4)
    p.add_argument("--slots", type=int, default=0,
                   help="decode batch slots (0 = --requests); requests "
                        "beyond this queue and admit as slots free up (gru)")
    p.add_argument("--prompt-len", type=int, default=12)
    p.add_argument("--vary-prompt", action="store_true",
                   help="gru: ragged prompt lengths (exercises buckets+mask)")
    p.add_argument("--max-new", type=int, default=16)
    p.add_argument("--gru-backend",
                   choices=("xla", "pallas", "auto", "pallas_fused",
                            "pallas_chain", "sharded", "pallas_sharded",
                            "sharded_decode", "pallas_fused_q8",
                            "pallas_chain_q8"),
                   default=None,
                   help="executor backend preference (pallas = fused "
                        "kernel family; an exact name pins that backend — "
                        "the mesh-requiring ones [sharded, pallas_sharded, "
                        "sharded_decode] need a sharded launch and fall "
                        "through otherwise; the *_q8 pins serve the int8 "
                        "datapath regardless of the accuracy gate [explicit "
                        "opt-in]; auto = cheapest legal backend "
                        "— measured per-shape costs when "
                        "BENCH_backend_costs.json is loaded, the static "
                        "table otherwise, with the q8 backends eligible "
                        "only when BENCH_quant_accuracy.json records a "
                        "pass)")
    p.add_argument("--bucket-min", type=int, default=8)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--replicas", type=int, default=1,
                   help="gru: serve through a FleetRouter with this many "
                        "engine replicas (admission control, depth routing, "
                        "retry/hedging; see docs/serving.md)")
    p.add_argument("--inject-faults", action="store_true",
                   help="fleet: run a seeded kill/restore+slow schedule "
                        "under a deterministic virtual clock and print the "
                        "fault accounting (requires --replicas > 1)")
    p.add_argument("--routing", choices=("depth", "static"), default="depth",
                   help="fleet dispatch policy: measured queue-depth scoring "
                        "vs static round-robin")
    p.add_argument("--async", dest="use_async", action="store_true",
                   help="serve through the asyncio front-end "
                        "(repro.serve.async_frontend): one client coroutine "
                        "per request over a FleetRouter — works solo "
                        "(--replicas 1) and fleet; token streams are "
                        "bitwise-identical to the synchronous path "
                        "(cell families only; see docs/serving.md)")
    p.add_argument("--autotune", action="store_true",
                   help="attach an online AutoTuner (repro.serve.autotune): "
                        "wave size from the measured batch-latency curve, "
                        "prompt-bucket ladder from observed length "
                        "quantiles, served step timings folded back into "
                        "the CostModel — retuned only at wave boundaries; "
                        "prints the applied decisions (fleet mode tunes "
                        "each replica independently)")
    args = p.parse_args(argv)
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    is_cell = cell_families.is_cell_family(cfg.family)
    if args.gru_backend and is_cell:
        cfg = cfg.replace(gru=dataclasses.replace(cfg.gru,
                                                  backend=args.gru_backend))
    A = mapi.get_api(cfg)
    params = init_params(A.specs(cfg), jax.random.key(args.seed),
                         cfg.param_dtype)
    rng = np.random.default_rng(args.seed)
    if is_cell:
        # cell-family (gru/slstm/...) feature-vector waves: prompts are
        # (S, X) float windows
        def plen():
            return (int(rng.integers(1, args.prompt_len + 1))
                    if args.vary_prompt else args.prompt_len)
        reqs = [Request(prompt=rng.normal(size=(plen(), cfg.gru.input_dim))
                        .astype(np.float32),
                        max_new_tokens=args.max_new)
                for _ in range(args.requests)]
    else:
        reqs = [Request(prompt=rng.integers(0, cfg.vocab_size,
                                            size=args.prompt_len)
                        .astype(np.int32),
                        max_new_tokens=args.max_new)
                for _ in range(args.requests)]
    if args.replicas > 1 or args.use_async:
        if not is_cell:
            p.error("--async/--replicas>1 serve through the FleetRouter, "
                    "which is cell-family only")
        # --async with --replicas 1 is the solo path through the same
        # front-end: one replica behind the asyncio transport
        return _serve_fleet(cfg, params, reqs, args)
    tuner = None
    if args.autotune:
        from repro.serve.autotune import AutoTuner
        tuner = AutoTuner()
    engine = ServeEngine(cfg, params, ShardCtx(),
                         max_batch=args.slots or args.requests,
                         bucket_min=args.bucket_min, tuner=tuner)
    done = engine.generate(reqs)
    for i, r in enumerate(done):
        print(f"req{i}: {len(r.out)} tokens -> {r.out[:8]}...")
    stats = engine.latency_stats()
    print(f"decode latency: mean={stats['mean_s']*1e3:.2f}ms "
          f"p50={stats['p50_s']*1e3:.2f}ms p90={stats['p90_s']*1e3:.2f}ms "
          f"p99={stats['p99_s']*1e3:.2f}ms ({stats['steps']} steps); "
          f"prefill mean={stats['prefill_mean_s']*1e3:.2f}ms "
          f"({stats['prefills']} prefills, "
          f"{len(engine._wave_jit) + len(engine._prefill_jit)} "
          f"bucket jits)")
    if is_cell:
        pf = sorted(set(engine.prefill_backends))
        steps = stats.get("decode_backend_steps", {})
        attributed = ",".join(f"{k}:{v}" for k, v in sorted(steps.items()))
        print(f"executor: prefill={'/'.join(pf) or '-'} "
              f"decode={engine.decode_backend} "
              f"dtype={stats.get('served_dtype')} "
              f"decode_steps=[{attributed or '-'}]")
    if args.autotune:
        _print_autotune(stats["autotune"])
    return done


def _print_autotune(at: dict) -> None:
    ladder = at.get("bucket_ladder")
    print(f"autotune: wave_size={at['wave_size']} "
          f"bucket_ladder={ladder or 'pow2'} "
          f"retunes={at.get('retunes', 0)} "
          f"prompts_observed={at.get('prompts_observed', 0)}")
    for d in at.get("decisions", ()):
        print(f"  [{d['kind']}] {d['from']} -> {d['to']} "
              f"({d['measurement'].get('rule', '')})")


def _serve_fleet(cfg, params, reqs, args):
    """Fleet mode: N supervised replicas behind one generate() call.
    ``--inject-faults`` runs the whole thing in deterministic virtual time
    (ManualClock) against a seeded kill/restore+slow schedule."""
    from repro.distributed.fault_tolerance import ManualClock
    from repro.serve.fleet import FaultInjector, FleetConfig, FleetRouter

    names = [f"replica{i}" for i in range(args.replicas)]
    clock = injector = None
    if args.inject_faults:
        clock = ManualClock()
        injector = FaultInjector.seeded(args.seed, names, horizon_s=0.6)
        print(f"fault schedule (seed {args.seed}): "
              + "; ".join(f"t={e.t:.3f} {e.kind} {e.replica}"
                          + (f" x{e.factor:g}" if e.kind == "slow" else "")
                          for e in injector._events))
    router = FleetRouter(cfg, params, replicas=args.replicas,
                         max_batch=args.slots or max(2, args.requests // 2),
                         bucket_min=args.bucket_min, clock=clock,
                         config=FleetConfig(routing=args.routing),
                         injector=injector, autotune=args.autotune)
    if args.use_async:
        from repro.serve.async_frontend import run_clients
        done = run_clients(router, reqs)
        print(f"async front-end: {len(reqs)} concurrent client coroutines "
              f"over {args.replicas} replica(s)")
    else:
        done = router.generate(reqs)
    for i, r in enumerate(done):
        print(f"req{i}: {len(r.out)} tokens -> {r.out[:8]}...")
    s = router.stats()
    print(f"fleet: {args.replicas} replicas routing={s['routing']} "
          f"completed={s['completed']}/{s['submitted']} "
          f"failed={s['failed']} shed={s['shed'] or '{}'} "
          f"retries={s['retries']} hedges={s['hedges']} "
          f"kills={s['kills']} restores={s['restores']}")
    for name, rs in s["replicas"].items():
        line = (f"  {name}: alive={rs['alive']} restarts={rs['restarts']} "
                f"steps={rs['steps']} requests={rs['requests']}")
        if args.autotune:
            line += (f" wave_size={rs['wave_size']} "
                     f"bucket_ladder={rs['bucket_ladder'] or 'pow2'} "
                     f"retunes={rs['retunes']}")
        print(line)
    return done


if __name__ == "__main__":
    main()
