"""Fleet serving under load: throughput vs per-request tail latency, with
and without injected replica faults, depth-aware vs static routing (A/B).

The paper's constraint is a per-request latency bound; a single engine
meets it per kernel, the fleet (``repro.serve.fleet``) must keep meeting
it while replicas crash and recover. This benchmark drives a FleetRouter
with an open-loop arrival process under the REAL clock:

* **Arrivals** — seeded Poisson process (exponential inter-arrival gaps)
  at ``--rate`` requests/s.
* **Prompts** — heavy-tailed lengths (clipped lognormal), so the prefill
  bucket mix is realistic and depth routing has something to exploit.
* **Faults** (``faults=True`` arms) — a deterministic schedule placed at
  fractions of the arrival horizon: replica0 is killed at 25% and
  restored at 60%; replica1 runs a slow window (recorded-signal
  inflation — the fleet is single-process under a real clock, see the
  fleet module docstring) from 20% to 50% so the straggler/hedging path
  exercises too.

Four runs share one request seed: {depth, static} x {no-fault, faults};
``--autotune`` adds two more arms, ``tuned_{nofault,faults}`` — depth
routing plus a per-replica :class:`AutoTuner` warmed on a replay of the
same workload (tune on yesterday's traffic, serve today's), the fleet
A/B the CI gate judges (``tuned e2e p99 <= 1.1x static`` and zero
drops). Tuned runs snapshot/restore the process-global CostModel so
online recalibration in one arm never leaks into the next, and the full
decision records land in ``BENCH_autotune_decisions.json``.

Every run reports throughput, e2e p50/p99 (admit->finish, including
fleet queueing, retries and hedging — the honest per-request numbers)
and the full fault accounting. CI asserts the faulted runs drop nothing:
``completed == admitted`` and ``failed == 0`` with ``kills >= 1``.

``--horizon SECONDS`` sizes the workload from the arrival process
(``n = rate x horizon``) instead of a raw count; ``--saturation``
sweeps offered-load multipliers and emits an offered-load vs e2e-p99
curve per arm (where the tuned arm peels away from static as the fleet
saturates).

    PYTHONPATH=src python benchmarks/serve_fleet.py [--smoke] [--autotune]

Emits BENCH_serve_fleet.json. CSV: name,value,notes
"""
from __future__ import annotations

import argparse
import dataclasses
import json

import jax
import numpy as np

from repro.configs.base import GRUConfig, get_smoke_config
from repro.core import runtime
from repro.core.params import init_params
from repro.launch.compile_cache import enable_compile_cache
from repro.models import api as mapi
from repro.serve.engine import Request, bucket_len
from repro.serve.fleet import (FaultEvent, FaultInjector, FleetConfig,
                               FleetRejected, FleetRouter)


def _setup(hidden: int, layers: int):
    cfg = get_smoke_config("gru-jet").replace(
        gru=GRUConfig(input_dim=5, hidden_dim=hidden, num_classes=5,
                      seq_len=64, num_layers=layers))
    A = mapi.get_api(cfg)
    params = init_params(A.specs(cfg), jax.random.key(0), cfg.param_dtype)
    return cfg, params


def _workload(cfg, n: int, rate: float, seed: int, max_prompt: int,
              max_new: int):
    """Seeded open-loop workload: Poisson arrival offsets + heavy-tail
    (lognormal, clipped) prompt lengths. Same seed -> same requests, so
    the A/B arms serve identical traffic."""
    rng = np.random.default_rng(seed)
    t_arr = np.cumsum(rng.exponential(1.0 / rate, n))
    lens = np.clip(np.rint(np.exp(rng.normal(1.5, 0.8, n))),
                   2, max_prompt).astype(int)
    X = cfg.gru.input_dim
    reqs = [Request(prompt=rng.normal(size=(int(L), X)).astype(np.float32),
                    max_new_tokens=max_new)
            for L in lens]
    return t_arr, reqs


def _prewarm(router: FleetRouter, cfg, lens) -> None:
    """Compile every replica's prefill buckets + decode jit out-of-band
    (direct engine calls — no router counters touched), so measured
    queue waits are service, not trace time, and slow first steps don't
    trip the heartbeat/straggler detectors spuriously."""
    bucket_min = router.replicas[0].engine.bucket_min
    buckets = sorted({bucket_len(int(L), bucket_min) for L in lens})
    X = cfg.gru.input_dim
    for rep in router.replicas:
        warm = [Request(prompt=np.zeros((b, X), np.float32),
                        max_new_tokens=1) for b in buckets]
        rep.engine.generate(warm)


def _tune_warmup(router: FleetRouter, reqs) -> None:
    """Replay the workload through each replica's engine directly (no
    router counters): the tuners observe the real prompt-length
    distribution and real step timings, retune at the drain boundary,
    and a second pass compiles the tuned bucket ladder — so the measured
    run starts with yesterday's-traffic tuning applied and pays no
    mid-run ladder compiles."""
    for rep in router.replicas:
        for p in range(2):
            clones = [Request(prompt=r.prompt,
                              max_new_tokens=(r.max_new_tokens if p == 0
                                              else 1))
                      for r in reqs]
            rep.engine.generate(clones)


def _fault_schedule(horizon_s: float, t0: float):
    """Kill/restore + slow window at fixed fractions of the arrival
    horizon, shifted to absolute clock time ``t0``."""
    rel = [FaultEvent(t=0.25 * horizon_s, kind="kill", replica="replica0"),
           FaultEvent(t=0.60 * horizon_s, kind="restore", replica="replica0"),
           FaultEvent(t=0.20 * horizon_s, kind="slow", replica="replica1",
                      factor=5.0),
           FaultEvent(t=0.50 * horizon_s, kind="slow", replica="replica1",
                      factor=1.0)]
    return FaultInjector([dataclasses.replace(e, t=t0 + e.t) for e in rel])


def run_once(cfg, params, *, routing: str, faults: bool, n: int, rate: float,
             seed: int, replicas: int, max_batch: int, max_prompt: int,
             max_new: int, label: str, csv: bool = True,
             autotune: bool = False,
             wall_limit_s: float = 300.0) -> dict:
    t_arr, reqs = _workload(cfg, n, rate, seed, max_prompt, max_new)
    horizon = float(t_arr[-1])
    config = FleetConfig(
        routing=routing,
        queue_limit=n + 8,               # open-loop: never shed own traffic
        retry_budget=5,                  # headroom over the injected kill
        # real clock: a tick is one decode step per replica; the timeout
        # must dominate any single step or a busy replica reads as dead
        heartbeat_timeout_s=max(1.0, 0.15 * horizon),
        backoff_base_s=0.05,
        straggler_factor=4.0)
    # online recalibration mutates the PROCESS-GLOBAL CostModel; restore
    # the pre-run model afterwards so one arm's folds never leak into the
    # next arm's dispatch (each run_once is a self-contained experiment)
    model_snap = runtime.cost_model()
    try:
        router = FleetRouter(cfg, params, replicas=replicas,
                             max_batch=max_batch, config=config,
                             autotune=autotune)
        _prewarm(router, cfg, [len(r.prompt) for r in reqs])
        if autotune:
            _tune_warmup(router, reqs)
        t0 = router.clock.now()
        if faults:
            router.injector = _fault_schedule(horizon, t0)
        admitted, arrival_shed, i = 0, 0, 0
        while i < n or any(t.outstanding for t in router.tickets):
            now = router.clock.now() - t0
            if now > wall_limit_s:
                raise RuntimeError(f"{label}: fleet run exceeded "
                                   f"{wall_limit_s}s wall limit")
            while i < n and t_arr[i] <= now:
                try:
                    router.submit(reqs[i])
                    admitted += 1
                except FleetRejected:
                    arrival_shed += 1
                i += 1
            router.tick()
        dur = router.clock.now() - t0
        s = router.stats()
        if s["completed"] == 0:
            # an arm that served nothing has NaN percentiles (never a
            # fake-perfect 0.0) — and NaN fails every <= comparison, so
            # the p99 gates would silently become vacuous. Die loudly.
            raise RuntimeError(
                f"{label}: arm completed 0 requests (admitted={admitted}, "
                f"arrival_shed={arrival_shed}, failed={s['failed']}) — "
                f"empty arms have no percentiles and cannot be gated")
        row = {"label": label, "routing": routing, "faults": faults,
               "autotune": autotune,
               "arrivals": n, "admitted": admitted,
               "arrival_shed": arrival_shed,
               "completed": s["completed"], "failed": s["failed"],
               "shed": s["shed"], "retries": s["retries"],
               "hedges": s["hedges"],
               "hedges_cancelled": s["hedges_cancelled"],
               "kills": s["kills"], "restores": s["restores"],
               "duration_s": round(dur, 4),
               "throughput_rps": round(s["completed"] / max(dur, 1e-9), 2),
               "e2e_p50_s": round(s["e2e_p50_s"], 5),
               "e2e_p99_s": round(s["e2e_p99_s"], 5),
               "queue_wait_p50_s": round(s["queue_wait_p50_s"], 5),
               "queue_wait_p99_s": round(s["queue_wait_p99_s"], 5),
               "replicas": {name: {k: v[k] for k in
                                   ("alive", "restarts", "steps",
                                    "requests", "wave_size",
                                    "bucket_ladder", "retunes")}
                            for name, v in s["replicas"].items()}}
        if autotune:
            # compact per-run counts on the row; the FULL decision records
            # (with justifying measurements) go to the decisions artifact
            full = {rep.name: rep.engine.latency_stats()["autotune"]
                    for rep in router.replicas}
            row["autotune_summary"] = {
                name: {"retunes": at.get("retunes", 0),
                       "decisions": len(at.get("decisions", ())),
                       "wave_size": at["wave_size"],
                       "bucket_ladder": at["bucket_ladder"]}
                for name, at in full.items()}
            row["_decisions_full"] = {
                name: at.get("decisions", []) for name, at in full.items()}
    finally:
        if autotune:
            runtime.set_cost_model(model_snap)
    if csv:
        print(f"fleet_{label},{row['throughput_rps']:.2f},"
              f"rps;e2e_p99={row['e2e_p99_s'] * 1e3:.1f}ms;"
              f"completed={row['completed']}/{row['admitted']};"
              f"retries={row['retries']};hedges={row['hedges']};"
              f"kills={row['kills']}")
    return row


def run(n: int = 120, rate: float = 20.0, hidden: int = 32, layers: int = 2,
        replicas: int = 2, max_batch: int = 4, max_prompt: int = 32,
        max_new: int = 8, seed: int = 0, autotune: bool = False,
        saturation: tuple = (),
        json_path: str = "BENCH_serve_fleet.json",
        decisions_path: str = "BENCH_autotune_decisions.json",
        csv: bool = True) -> dict:
    cfg, params = _setup(hidden, layers)
    runs, decisions = [], []
    arms = [(routing, faults, False)
            for routing in ("depth", "static") for faults in (False, True)]
    if autotune:
        # the tuned arms ride depth routing: tuned-vs-static isolates what
        # the AUTOTUNER buys on top of the better routing baseline
        arms += [("depth", False, True), ("depth", True, True)]
    for routing, faults, tuned in arms:
        label = (f"{'tuned' if tuned else routing}_"
                 f"{'faults' if faults else 'nofault'}")
        row = run_once(
            cfg, params, routing=routing, faults=faults, n=n, rate=rate,
            seed=seed, replicas=replicas, max_batch=max_batch,
            max_prompt=max_prompt, max_new=max_new, label=label,
            autotune=tuned, csv=csv)
        full = row.pop("_decisions_full", None)
        if full is not None:
            decisions.append({"label": label, "replicas": full})
        runs.append(row)
    summary = {}
    by = {r["label"]: r for r in runs}
    if by["depth_nofault"]["e2e_p99_s"] > 0:
        summary["static_over_depth_p99"] = round(
            by["static_nofault"]["e2e_p99_s"]
            / by["depth_nofault"]["e2e_p99_s"], 3)
    if autotune and by["static_nofault"]["e2e_p99_s"] > 0:
        # the CI gate's A/B: the feedback loop must never LOSE to the
        # static configuration it replaced (<= 1.1x static e2e p99)
        summary["tuned_over_static_p99"] = round(
            by["tuned_nofault"]["e2e_p99_s"]
            / by["static_nofault"]["e2e_p99_s"], 3)
        summary["tuned_retunes"] = sum(
            v["retunes"] for v in by["tuned_nofault"]["autotune_summary"]
            .values())
    for label, r in by.items():
        if r["faults"]:
            summary[f"{label}_zero_drops"] = bool(
                r["failed"] == 0 and r["completed"] == r["admitted"])
    # saturation sweep: same workload shape at scaled offered load, per
    # arm — where the curves peel apart is the fleet's capacity knee
    sat_rows = []
    sat_arms = ["depth", "static"] + (["tuned"] if autotune else [])
    for mult in saturation:
        n_sat = max(16, n // 2)          # shorter runs: the sweep is a
        for arm in sat_arms:             # curve, not a precision estimate
            r = run_once(
                cfg, params, routing="depth" if arm == "tuned" else arm,
                faults=False, n=n_sat, rate=rate * mult, seed=seed,
                replicas=replicas, max_batch=max_batch,
                max_prompt=max_prompt, max_new=max_new,
                label=f"sat_{arm}_x{mult:g}", autotune=(arm == "tuned"),
                csv=False)
            r.pop("_decisions_full", None)
            sat_rows.append({"offered_rps": round(rate * mult, 3),
                             "arm": arm, "arrivals": n_sat,
                             "completed": r["completed"],
                             "throughput_rps": r["throughput_rps"],
                             "e2e_p99_s": r["e2e_p99_s"]})
            if csv:
                print(f"fleet_sat_{arm}_x{mult:g},"
                      f"{r['e2e_p99_s'] * 1e3:.1f},"
                      f"e2e_p99_ms@offered={rate * mult:g}rps")
    out = {"bench": "serve_fleet", "backend": jax.default_backend(),
           "replicas": replicas, "rate_rps": rate, "autotune": autotune,
           "runs": runs, "summary": summary}
    if sat_rows:
        out["saturation"] = sat_rows
    with open(json_path, "w") as f:
        json.dump(out, f, indent=2)
    if autotune:
        with open(decisions_path, "w") as f:
            json.dump({"bench": "autotune_decisions",
                       "rate_rps": rate, "replicas": replicas,
                       "runs": decisions}, f, indent=2)
    if csv:
        for k, v in summary.items():
            print(f"fleet_{k},{float(v) if not isinstance(v, bool) else int(v)},summary")
        print(f"fleet_artifact,0.00,{json_path}")
        if autotune:
            print(f"fleet_autotune_artifact,0.00,{decisions_path}")
    return out


if __name__ == "__main__":
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced load for CI (still emits the artifact and "
                         "runs the faulted arms)")
    ap.add_argument("--n", type=int, default=None, help="total arrivals")
    ap.add_argument("--rate", type=float, default=None, help="arrivals/s")
    ap.add_argument("--horizon", type=float, default=None,
                    help="arrival horizon in seconds; sizes the workload "
                         "as n = rate x horizon (overrides --n)")
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--autotune", action="store_true",
                    help="add the tuned_{nofault,faults} arms (per-replica "
                         "AutoTuner warmed on a workload replay) and emit "
                         "BENCH_autotune_decisions.json")
    ap.add_argument("--saturation", default=None,
                    help="comma-separated offered-load multipliers for the "
                         "saturation sweep (default: 0.5,1,2 for full "
                         "runs, off for --smoke; pass '' to disable)")
    ap.add_argument("--json", default="BENCH_serve_fleet.json")
    ap.add_argument("--decisions-json",
                    default="BENCH_autotune_decisions.json")
    args = ap.parse_args()
    if args.saturation is None:
        sat = () if args.smoke else (0.5, 1.0, 2.0)
    else:
        sat = tuple(float(m) for m in args.saturation.split(",") if m)
    rate = args.rate or (6.0 if args.smoke else 20.0)
    n = args.n or (24 if args.smoke else 120)
    if args.horizon is not None:
        n = max(1, int(round(rate * args.horizon)))
    if args.smoke:
        run(n=n, rate=rate, hidden=16, layers=1,
            replicas=args.replicas, max_prompt=16, max_new=4,
            seed=args.seed, autotune=args.autotune, saturation=sat,
            json_path=args.json, decisions_path=args.decisions_json)
    else:
        run(n=n, rate=rate,
            replicas=args.replicas, seed=args.seed,
            autotune=args.autotune, saturation=sat,
            json_path=args.json, decisions_path=args.decisions_json)
