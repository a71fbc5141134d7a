"""One run of one cell: set-up, the measured window, the metrics, and the
comparison that decides ``correct``."""
from __future__ import annotations

import gc
import shutil
import tempfile
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Optional

import numpy as np

from harness import check, drive, spec, system, traffic
from harness import trace as tracing
from harness.spec import Cell

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"


class HarnessError(RuntimeError):
    """The run cannot give a valid result (no result line is printed)."""


@dataclass
class Run:
    """What a metric reader sees of one run (``bench/metrics/*.py``)."""
    cell: Cell
    sizes: dict
    work: object                      # bench/work/<family>.py
    peaks: dict
    window: drive.Window
    setup_s: float
    decode_s: np.ndarray              # the engine's decode call times
    prefill_s: np.ndarray             # the engine's prefill call times
    calls: dict                       # kernel calls in the window
    rows: dict                        # served rows and row-steps
    trace: Optional[tracing.Trace] = None

    @property
    def seconds(self) -> float:
        return self.window.seconds


class _Compiles:
    """Counts compilations and traces while ``on``."""

    def __init__(self):
        self.on = False
        self.compiles = self.traces = 0

    def __call__(self, event, duration, **_):
        if self.on and event == COMPILE_EVENT:
            self.compiles += 1
        elif self.on and event == TRACE_EVENT:
            self.traces += 1


def _key(seed: int):
    import jax
    bits = np.random.SeedSequence([int(seed), 0]).generate_state(2)
    return jax.random.wrap_key_data(bits.astype(np.uint32))


# the engine's per-call records that the window is read from
RECORDS = ("step_times", "prefill_times", "queue_waits")


def _marks(eng) -> dict:
    return {a: len(getattr(eng, a)) for a in RECORDS}


def _between(eng, m0: dict, m1: dict, attr: str) -> np.ndarray:
    return np.asarray(getattr(eng, attr)[m0[attr]:m1[attr]], np.float64)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, root: Path = spec.ROOT,
             peaks: Optional[dict] = None, hook=None) -> dict:
    """One run; returns the result line's object. ``hook``, when given, is
    called with the reference, weights, request pool and window once the
    result is known (the readings tools use it; a run does not)."""
    import jax

    devices = jax.devices()[:cell.chips]
    dev = devices[0]
    peaks = peaks if peaks is not None else spec.peaks(dev.device_kind, root)
    sizes, tr = cell.config, cell.traffic
    ref = spec.family_module("reference", sizes["family"], root)
    work = spec.family_module("work", sizes["family"], root)
    cfg = system.program_config(sizes)

    marks = [("devices", time.monotonic())]
    params = ref.init(sizes, _key(seed))
    jax.block_until_ready(params)
    pool = traffic.pool(tr, sizes["input_dim"], seed)
    marks.append(("weights and traffic", time.monotonic()))
    eng = system.engine(cfg, params, int(tr["slots"]),
                        system.system_clock())
    marks.append(("system", time.monotonic()))
    drive.warm(eng, pool, tr)
    # what set-up left on the heap (JAX, the program, the harness) is moved
    # out of the collector's reach, so that a full collection in the window
    # walks only what the window allocates and keeps
    gc.collect()
    gc.freeze()
    marks.append(("warm-up", time.monotonic()))

    counter = _Compiles()
    jax.monitoring.register_event_duration_secs_listener(counter)
    log_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    state = {}

    def start():
        state["backends"] = system.backend_marks(eng)
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(log_dir, profiler_options=opts)
            state["span"] = jax.profiler.TraceAnnotation(tracing.WINDOW)
            state["span"].__enter__()
        state["setup_s"] = time.monotonic() - t_start
        state["m0"] = _marks(eng)
        counter.on = True

    def stop():
        counter.on = False
        state["m1"] = _marks(eng)
        if trace:
            state["span"].__exit__(None, None, None)
            jax.profiler.stop_trace()

    span = jax.profiler.TraceAnnotation if trace else drive.no_span
    try:
        w = drive.closed_loop(eng, pool, tr, seconds, start, stop, span)
        memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                          for d in devices)
        system.check_backends(eng, sizes["resolved_backend"],
                              state["backends"])
        tr_data = tracing.load(log_dir) if trace else None
    finally:
        if log_dir:
            shutil.rmtree(log_dir, ignore_errors=True)
    print("setup: " + ", ".join(
        f"{name} {t - prev:.3f} s" for (name, t), prev in
        zip(marks, [t_start] + [t for _, t in marks])), flush=True)
    print(f"window: {w.seconds:.6f} s, compilations {counter.compiles}, "
          f"traces {counter.traces}", flush=True)
    if counter.compiles:
        raise HarnessError(f"{counter.compiles} programs compiled inside "
                           "the measured window")
    m0, m1 = state["m0"], state["m1"]
    decode_rows = (sum(w.count) + sum(len(r.out) for r, _ in w.inflight))
    # the engine records one queue wait per request it admits
    admitted = m1["queue_waits"] - m0["queue_waits"]
    run = Run(
        cell=cell, sizes=sizes, work=work, peaks=peaks, window=w,
        setup_s=state["setup_s"],
        decode_s=_between(eng, m0, m1, "step_times"),
        prefill_s=_between(eng, m0, m1, "prefill_times"),
        calls={"decode": m1["step_times"] - m0["step_times"],
               "prefill": m1["prefill_times"] - m0["prefill_times"]},
        rows={"decode": decode_rows, "prefill": admitted,
              "prefill_steps": admitted * pool.prompt_len},
        trace=tr_data)

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = spec.metric_reader(m["name"], root).read(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    rows = system.wave_rows(eng)
    # the program's state goes before the reference runs on the chip
    del eng
    gc.unfreeze()
    gc.collect()
    live = {id(r): j for r, j in w.inflight}
    pairs = check.state_pairs(ref, params, pool, rows, live, w.last)
    checks = correctness(cell, ref, params, pool, w, rows, pairs)
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": int(memory_peak)}
    result = {"correct": bool(correct),
              "attempted": len(w.index),
              "failed": 0,
              "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = tr_data.busy_s()
        device["window_s"] = tr_data.window_s
        result["breakdown"] = {"device_ops": tr_data.top_ops(),
                               "idle_gaps": tr_data.idle_gaps()}
    result["checks"] = checks
    if hook is not None:
        hook(ref=ref, params=params, pool=pool, window=w, rows=rows,
             pairs=pairs, result=result)
    return result


def correctness(cell: Cell, ref, params, pool, w, rows, pairs) -> dict:
    """The numbers ``correct`` is decided by, each with its limit: those
    the cell's limits file names (``check.NUMBERS``)."""
    unknown = set(cell.limits) - set(check.NUMBERS)
    if unknown or not cell.limits:
        raise spec.SpecError(f"limits of {cell.name} name {sorted(unknown)}"
                             f"; known numbers: {check.NUMBERS}")
    items = check.compared(w)
    logits = check.reference_logits(ref, params, pool,
                                    {j for j, _ in items}, "highest")
    got = {"gap": check.served_gap(items, logits),
           **check.state_stats(ref, params, pool, rows, pairs),
           **check.counts(w)}
    return {k: {"value": got[k], "limit": v} for k, v in cell.limits.items()}
