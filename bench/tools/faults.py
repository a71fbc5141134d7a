"""Faults planted in the program's timed path, to show that the comparison
deciding ``correct`` fails a broken run. Each patches the program in this
process only (the tests, and ``readings.py --fault`` on the chip); a
benchmark run never imports this module.

- ``state_unchanged``: the decode step returns its state unchanged;
- ``half_batch``: every second slot (1, 3, 5, ...) is left out of the
  decode step, its state and answer as they were before it; interleaved,
  so that a lightly loaded wave, which fills its first slots, loses some
  of its live rows too;
- ``class_altered``: slot 0's answer is changed where it is produced;
- ``scatter_one``: an admission of k windows writes only k - 1 of them
  into their slots (the first keeps its slot's old state).
"""
from __future__ import annotations

import contextlib

KINDS = ("state_unchanged", "half_batch", "class_altered", "scatter_one")


def _decode(kind, orig):
    import jax.numpy as jnp

    def broken(params, cfg, cache, x, *, ctx):
        logits, new = orig(params, cfg, cache, x, ctx=ctx)
        if kind == "state_unchanged":
            return logits, {**new, "h": cache["h"]}
        if kind == "half_batch":
            out = (jnp.arange(x.shape[0]) % 2 == 1)[:, None]
            h = tuple(jnp.where(out, o, n)
                      for n, o in zip(new["h"], cache["h"]))
            head = params["head"]
            old = h[-1] @ head["w"] + head["b"]
            return jnp.where(out, old, logits), {**new, "h": h}
        if kind == "class_altered":
            return logits.at[0].set(jnp.roll(logits[0], 1)), new
        raise ValueError(kind)
    return broken


def _scatter(_):
    import jax

    def get(self, k):
        jits = self.__dict__.setdefault("_broken_scatter", {})
        if k not in jits:
            def fn(cache, fresh, slots_):
                return {"h": tuple(h.at[slots_[1:]].set(f[1:k]) for h, f
                                   in zip(cache["h"], fresh["h"])),
                        "pos": cache["pos"]}
            jits[k] = jax.jit(fn)
        return jits[k]
    return get


@contextlib.contextmanager
def planted(kind: str):
    """Plant ``kind`` in the program for the duration of the block."""
    if kind not in KINDS:
        raise ValueError(f"unknown fault {kind!r}; known: {KINDS}")
    from harness import system
    system.import_program()
    from repro.models import gru_lm
    from repro.serve.engine import ServeEngine
    if kind == "scatter_one":
        owner, name = ServeEngine, "_get_scatter"
        repl = _scatter(None)
    else:
        owner, name = gru_lm, "decode_step"
        repl = _decode(kind, gru_lm.decode_step)
    orig = getattr(owner, name)
    setattr(owner, name, repl)
    try:
        yield
    finally:
        setattr(owner, name, orig)
