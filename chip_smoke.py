"""Chip smoke check: serve the cell-family configs through the fused Pallas
kernels on a TPU and hold the results to a float32 reference.

    python chip_smoke.py               # one chip: gru-jet, gru-jet-deep, slstm-jet
    python chip_smoke.py --four-chips  # four chips: gru-jet-deep, row-parallel

One chip: every config runs at its full published width through the normal
serving path (``ServeEngine`` -> ``runtime.compile`` -> the fused kernels,
with ``gru.backend="pallas"``). Each serves two waves of requests with
ragged prompt lengths, so the masked bucketed prefill runs: 12 requests
into 8 slots (admission happens mid-wave), then 9 into 6 slots (a slot
count that is not a multiple of 8). The script fails unless every prefill
and every decode step resolved to ``pallas_fused``, every request's final
recurrent state is within ``TOL`` of a plain float32 ``jnp`` reference run
under ``jax.default_matmul_precision("highest")``, and every emitted class
equals the reference's (bar reference near-ties, which are counted).

Four chips: the gru-jet-deep wave through a ``ServeEngine`` whose
``ShardCtx`` holds a mesh over all four devices, pinned to
``pallas_sharded``, against the same wave on one chip through
``pallas_fused``, both against the reference. It also checks that the
row-parallel weights really are partitioned over the four devices.

The times printed are smoke timings, not benchmark numbers. The last line
of standard output is one JSON object, printed only when every phase passed
on a TPU; any failed phase exits non-zero.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import numpy as np

CONFIGS = ("gru-jet", "gru-jet-deep", "slstm-jet")
SEED = 0
TOL = 1e-5        # max |state - reference| over every state leaf
TIE = 1e-3        # reference top-2 logit margin below which a class may flip
WAVES = ((12, 8), (9, 6))   # (requests, slots) per wave


class SmokeFailure(AssertionError):
    """A phase produced a wrong or unexpected result."""


def _check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def make_requests(cfg, n: int, seed: int):
    """``n`` requests with ragged prompt lengths 1..T and 2..8 decode steps,
    each with its own decode feature stream (so the reference replays
    exactly what the engine fed)."""
    from repro.serve.engine import Request
    rng = np.random.default_rng(seed)
    X, T = cfg.gru.input_dim, cfg.gru.seq_len
    reqs = []
    for _ in range(n):
        S, new = int(rng.integers(1, T + 1)), int(rng.integers(2, 9))
        reqs.append(Request(
            prompt=rng.normal(size=(S, X)).astype(np.float32),
            max_new_tokens=new,
            stream=rng.normal(size=(new, X)).astype(np.float32)))
    return reqs


def serve_wave(engine, reqs):
    """Serve one wave through the engine's stepwise wave API and return,
    per request, its final recurrent state: the row its slot held in the
    wave cache when it retired. (Every request decodes at least two steps,
    so none retires in the step that admits it.) Each step waits on the
    device once, for its classes."""
    finals = {}
    engine.gru_wave_begin(reqs)
    wave = engine._wave
    waits, steps = engine.device_waits, 0
    while engine.gru_wave_active():
        lanes = {id(s.req): j for j, s in enumerate(wave.slots) if s}
        finished = engine.gru_wave_step()
        steps += 1
        for r in finished:
            j = lanes[id(r)]
            finals[id(r)] = [np.asarray(leaf[j]) for leaf in wave.cache["h"]]
    _check(all(r.done and len(r.out) == r.max_new_tokens for r in reqs),
           "a request did not finish with its full decode budget")
    waits = engine.device_waits - waits
    _check(waits == steps,
           f"the wave waited on the device {waits} times in {steps} steps")
    return [finals[id(r)] for r in reqs]


def reference(cfg, params, reqs):
    """Plain float32 jnp reference at the highest matmul precision: every
    request's prompt followed by its decode stream, right-aligned in one
    masked batch. Returns (per request: its final state leaves; per
    request: (steps, classes) logits of its decode steps)."""
    import jax
    import jax.numpy as jnp
    from repro.core import cells
    fam = cells.get_family(cells.cfg_family(cfg.gru))
    seqs = [np.concatenate([r.prompt, r.stream[:r.max_new_tokens]])
            for r in reqs]
    Tm = max(len(s) for s in seqs)
    xs = np.zeros((len(reqs), Tm, cfg.gru.input_dim), np.float32)
    mask = np.zeros((len(reqs), Tm), bool)
    for i, s in enumerate(seqs):
        xs[i, Tm - len(s):] = s
        mask[i, Tm - len(s):] = True

    def run(cells_, head, state0, xs, mask):
        finals, hs = fam.reference(cells_, state0, xs, return_all=True,
                                   mask=mask)
        return finals, hs @ head["w"] + head["b"]

    with jax.default_matmul_precision("highest"):
        finals, logits = jax.jit(run)(
            fam.normalize(params, cfg.gru), params["head"],
            fam.state0(cfg.gru, len(reqs)), jnp.asarray(xs),
            jnp.asarray(mask))
    finals = [np.asarray(f) for f in finals]
    logits = np.asarray(logits)
    return ([[f[i] for f in finals] for i in range(len(reqs))],
            [logits[i, Tm - r.max_new_tokens:] for i, r in enumerate(reqs)])


def compare(reqs, states, ref_states, ref_logits):
    """-> (max abs state error, classes that differ from the reference
    where its top-2 margin is at least TIE, near-tie flips)."""
    err = max(float(np.max(np.abs(a - b)))
              for got, ref in zip(states, ref_states)
              for a, b in zip(got, ref))
    wrong = ties = 0
    for r, lg in zip(reqs, ref_logits):
        top2 = np.sort(lg, axis=-1)[:, -2:]
        for k, cls in enumerate(r.out):
            if cls != int(np.argmax(lg[k])):
                if top2[k, 1] - top2[k, 0] < TIE:
                    ties += 1
                else:
                    wrong += 1
    return err, wrong, ties


def check_result(err: float, wrong: int) -> None:
    _check(np.isfinite(err) and err <= TOL,
           f"state error {err:.3g} above tolerance {TOL:g}")
    _check(wrong == 0, f"{wrong} classes differ from the reference")


def check_backends(engine, expected: str) -> None:
    stats = engine.latency_stats()
    seen = (set(engine.prefill_backends), set(stats["decode_backend_steps"]))
    _check(seen == ({expected}, {expected}),
           f"dispatch resolved prefill/decode to {seen}, expected {expected}")


def cost_sources(engine) -> str:
    """How each executable the engine compiled chose its backend."""
    exe = engine.api.executable
    mesh = engine.ctx.mesh
    parts = [f"prefill[S={S}]="
             + exe(engine.cfg, batch=engine.max_batch, seq=S, masked=True,
                   mode="prefill", mesh=mesh).cost_source
             for S in sorted(engine._prefill_jit)]
    parts.append("decode=" + exe(engine.cfg, batch=engine.max_batch,
                                 mode="decode", mesh=mesh).cost_source)
    return " ".join(parts)


def pinned(cfg, backend: str):
    return cfg.replace(gru=dataclasses.replace(cfg.gru, backend=backend))


def smoke_config(arch: str, waves=WAVES) -> dict:
    """Serve ``arch`` through the fused kernels, wave by wave; raises
    SmokeFailure on a wrong result. Returns the printed figures."""
    import jax
    from repro.configs.base import get_config
    from repro.core.params import init_params
    from repro.distributed.sharding import ShardCtx
    from repro.models import api as mapi
    from repro.serve.engine import ServeEngine

    cfg = pinned(get_config(arch), "pallas")
    params = init_params(mapi.get_api(cfg).specs(cfg), jax.random.key(SEED),
                         cfg.param_dtype)
    out = {"max_abs_err": 0.0, "ties": 0}
    for w, (n, slots) in enumerate(waves):
        engine = ServeEngine(cfg, params, ShardCtx(), max_batch=slots)
        reqs = make_requests(cfg, n, SEED + w)
        t0 = time.perf_counter()
        states = serve_wave(engine, reqs)
        cold = time.perf_counter() - t0
        check_backends(engine, "pallas_fused")
        ref_states, ref_logits = reference(cfg, params, reqs)
        err, wrong, ties = compare(reqs, states, ref_states, ref_logits)
        out["max_abs_err"] = max(out["max_abs_err"], err)
        out["ties"] += ties
        print(f"  {arch} wave {w}: {n} requests / {slots} slots, "
              f"prefill={sorted(set(engine.prefill_backends))} "
              f"decode={engine.decode_backend} "
              f"max_abs_err={err:.3g} (tol {TOL:g}) wrong_classes={wrong} "
              f"near_ties={ties} device_waits={engine.device_waits}")
        print(f"  {arch} wave {w} cost_source: {cost_sources(engine)}")
        check_result(err, wrong)
        if w == 0:
            # the same wave again on warm jits: the compile time is what
            # the cold pass spent beyond it, and the classes must repeat
            again = make_requests(cfg, n, SEED + w)
            t0 = time.perf_counter()
            serve_wave(engine, again)
            warm = time.perf_counter() - t0
            _check([r.out for r in again] == [r.out for r in reqs],
                   "replaying the wave changed its classes")
            st = engine.latency_stats()
            out["compile_s"] = cold - warm
            print(f"  {arch} smoke timing (not a benchmark): cold wave "
                  f"{cold:.3f}s, warm wave {warm:.3f}s, compile "
                  f"{cold - warm:.3f}s; steady decode step "
                  f"p50={st['p50_s'] * 1e6:.1f}us "
                  f"p90={st['p90_s'] * 1e6:.1f}us over {st['steps']} steps")
    return out


def smoke_four_chips(arch: str = "gru-jet-deep", n: int = 12,
                     slots: int = 8) -> dict:
    """One wave of ``arch`` row-parallel over every device (pallas_sharded)
    against the same wave on device 0 (pallas_fused) and the reference."""
    import jax
    from repro import compat
    from repro.configs.base import get_config
    from repro.core.params import init_params
    from repro.distributed.sharding import ShardCtx
    from repro.models import api as mapi
    from repro.serve.engine import ServeEngine

    ndev = len(jax.devices())
    base = get_config(arch)
    params = init_params(mapi.get_api(base).specs(base),
                         jax.random.key(SEED), base.param_dtype)
    mesh = compat.make_mesh((ndev,), ("model",))
    sharded = ServeEngine(pinned(base, "pallas_sharded"), params,
                          ShardCtx(mesh=mesh), max_batch=slots)
    for l, layer in enumerate(sharded.params["placed_cells"]):
        spans = [len({s.device for s in a.addressable_shards})
                 for a in layer.values() if not a.sharding.is_fully_replicated]
        _check(spans and min(spans) == ndev,
               f"layer {l}: no weight partitioned over all {ndev} devices "
               f"(partitioned arrays span {spans})")
    single = ServeEngine(pinned(base, "pallas"), params, ShardCtx(),
                         max_batch=slots)
    reqs_m, reqs_1 = make_requests(base, n, SEED), make_requests(base, n, SEED)
    t0 = time.perf_counter()
    st_m = serve_wave(sharded, reqs_m)
    t_m = time.perf_counter() - t0
    check_backends(sharded, "pallas_sharded")
    t0 = time.perf_counter()
    st_1 = serve_wave(single, reqs_1)
    t_1 = time.perf_counter() - t0
    check_backends(single, "pallas_fused")
    ref_states, ref_logits = reference(base, params, reqs_m)
    err_m, wrong_m, ties_m = compare(reqs_m, st_m, ref_states, ref_logits)
    err_1, wrong_1, ties_1 = compare(reqs_1, st_1, ref_states, ref_logits)
    diff = max(float(np.max(np.abs(a - b)))
               for ga, gb in zip(st_m, st_1) for a, b in zip(ga, gb))
    print(f"  {arch}: pallas_sharded over {ndev} devices "
          f"max_abs_err={err_m:.3g} wrong_classes={wrong_m} "
          f"near_ties={ties_m}; pallas_fused on one chip "
          f"max_abs_err={err_1:.3g} wrong_classes={wrong_1} "
          f"near_ties={ties_1}; mesh vs one chip {diff:.3g} (tol {TOL:g})")
    print(f"  smoke timing (not a benchmark), cold waves with compile: "
          f"mesh {t_m:.3f}s, one chip {t_1:.3f}s")
    check_result(err_m, wrong_m)
    check_result(err_1, wrong_1)
    _check(diff <= TOL, f"mesh vs one chip differ by {diff:.3g} > {TOL:g}")
    return {"max_abs_err": max(err_m, err_1), "mesh_vs_one_chip": diff}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip row-parallel phase")
    args = ap.parse_args(argv)
    t_start = time.perf_counter()
    try:
        import jax
        from repro import kernels
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the program: {e}", file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (jax.devices()[0] is {dev.platform}); "
              "nothing was run", file=sys.stderr)
        return 3
    if kernels.on_cpu():
        print("chip_smoke: the kernels would run in interpret mode",
              file=sys.stderr)
        return 3
    print(f"device: {dev.device_kind} x{len(devices)}; kernels compiled "
          f"(interpret=False); compile cache {cache}")
    failures = []
    phases = ([("four_chips", smoke_four_chips)] if args.four_chips
              else [(a, lambda a=a: smoke_config(a)) for a in CONFIGS])
    if args.four_chips and len(devices) != 4:
        failures.append(f"four_chips: needs 4 devices, found {len(devices)}")
        phases = []
    compile_s = 0.0
    for name, phase in phases:
        print(f"[{name}]")
        try:
            compile_s += phase().get("compile_s", 0.0)
        except Exception as e:  # noqa: BLE001 - report every phase, fail at the end
            failures.append(f"{name}: {type(e).__name__}: {e}")
            print(f"  FAILED: {type(e).__name__}: {e}")
    if not args.four_chips:
        print(f"compile time, summed over the configs' first waves: "
              f"{compile_s:.3f}s")
    print(f"total wall time {time.perf_counter() - t_start:.1f}s")
    if failures:
        print("chip_smoke FAILED:\n  " + "\n  ".join(failures),
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
