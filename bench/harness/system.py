"""The system under test, built from a configuration file and a seed.

This is the only module of the benchmark that imports the program
(``repro``, under the checkout's ``src/``). It takes from it the serving
path (``ServeEngine``) and the spans and counters it keeps; everything
else (weights, traffic, reference, work counts) is the benchmark's own.
"""
from __future__ import annotations

import dataclasses
import sys

import numpy as np

from harness import spec


class ConfigMismatch(RuntimeError):
    """The program is not running as the configuration states."""


def import_program():
    src = str(spec.ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def program_config(config: dict):
    """The program's own configuration for ``config``, with the backend the
    configuration runs on, checked size by size against the file."""
    import_program()
    from repro.configs.base import get_config
    cfg = get_config(config["repo_config"])
    g = dataclasses.replace(cfg.gru, backend=config["gru_backend"])
    cfg = cfg.replace(gru=g)
    have = {"family": cfg.family, "input_dim": g.input_dim,
            "num_layers": g.resolved_num_layers,
            "num_classes": g.num_classes, "seq_len": g.seq_len,
            "dtype": cfg.dtype}
    if set(g.resolved_layer_dims) != {config["hidden_dim"]}:
        raise ConfigMismatch(f"program layer widths {g.resolved_layer_dims} "
                           f"!= hidden_dim {config['hidden_dim']}")
    for k, v in have.items():
        if v != config[k]:
            raise ConfigMismatch(
                f"program {config['repo_config']}: {k}={v!r}, the "
                f"configuration file says {config[k]!r}")
    return cfg


def program_params(params: dict) -> dict:
    """The benchmark's weights in the program's parameter layout."""
    cells = tuple(params["cells"])
    if len(cells) == 1:
        return {"cell": cells[0], "head": params["head"]}
    return {"cells": cells, "head": params["head"]}


def request_type():
    """The program's request class: ``Request(prompt, max_new_tokens,
    stream=...)``, a float (S, X) prompt window and its stream."""
    import_program()
    from repro.serve.engine import Request
    return Request


def engine(cfg, params: dict, slots: int, clock):
    from repro.distributed.sharding import ShardCtx
    from repro.serve.engine import ServeEngine
    return ServeEngine(cfg, program_params(params), ShardCtx(),
                       max_batch=slots, clock=clock)


def system_clock():
    import_program()
    from repro.distributed.fault_tolerance import SystemClock
    return SystemClock()


def wave_rows(eng) -> list:
    """After the window: for each decode slot of the engine's wave, the
    request it serves (None for a free slot, whose row still holds the
    state its last request finished with) and its state, one row per
    layer. Reads the engine's wave internals; nothing on the timed path."""
    w = eng._wave
    hs = [np.asarray(h) for h in w.cache["h"]]
    return [(s.req if s is not None else None,
             np.stack([h[j] for h in hs]))
            for j, s in enumerate(w.slots)]


def check_backends(eng, expected: str, marks) -> None:
    """Every prefill and decode step since ``marks`` ran on ``expected``."""
    p0, d0 = marks
    seen = set(eng.prefill_backends[p0:]) | set(eng.decode_backends[d0:])
    if seen != {expected}:
        raise ConfigMismatch(f"dispatch resolved to {sorted(map(str, seen))}, "
                           f"the configuration runs on {expected}")


def backend_marks(eng) -> tuple:
    return len(eng.prefill_backends), len(eng.decode_backends)
