"""One wait on the device per wave step: ``gru_wave_step`` dispatches one
program (an admitting step's ``gru_wave``: prefill, scatter, decode; else
``gru_decode``) and downloads only the classes, which that program
computes itself (CPU, smoke sizes).

Each wave has more requests than slots, ragged prompts over two buckets,
streamed, free-running and stream-exhausted lanes, and requests enqueued
into the live wave."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_smoke_config
from repro.core.params import init_params
from repro.distributed.sharding import ShardCtx
from repro.models import api as mapi
from repro.serve.engine import Request, ServeEngine

ARCHS = ["gru-jet", "gru-jet-deep", "slstm-jet"]
SLOTS = 3
BUDGETS = [2, 5, 3, 4, 1, 2, 3, 6, 1, 4]
LATE = 4                    # requests enqueued into the live wave
LATE_AT = 2                 # ... before this step


def _requests(seed):
    """Ragged prompts (buckets 8 and 16); odd requests stream, every
    fourth runs out of stream halfway, the rest run free."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i, n in enumerate(BUDGETS):
        S = (3, 9, 5, 12)[i % 4]
        m = n if i % 2 else n // 2 if i % 4 == 2 else 0
        reqs.append(Request(
            prompt=rng.normal(size=(S, 5)).astype(np.float32),
            max_new_tokens=n,
            stream=rng.normal(size=(m, 5)).astype(np.float32) if m else None))
    return reqs


def _count_waits(mp):
    """Count every ``block_until_ready``: the jax array method (on the
    concrete array type, which overrides ``jax.Array``'s) and the
    function."""
    waits = {"n": 0}
    impl = type(jnp.zeros(()))
    method, fn = impl.block_until_ready, jax.block_until_ready

    def counted_method(self):
        waits["n"] += 1
        return method(self)

    def counted_fn(x):
        waits["n"] += 1
        return fn(x)
    mp.setattr(impl, "block_until_ready", counted_method)
    mp.setattr(jax, "block_until_ready", counted_fn)
    return waits


def _serve(arch):
    """Serve one wave step by step with the waits counted. Returns the
    engine, its requests, each request's state after every step it decoded
    in, the number of steps and of admitting steps, and the waits inside
    ``gru_wave_step``."""
    cfg = get_smoke_config(arch)
    params = init_params(mapi.get_api(cfg).specs(cfg), jax.random.key(1),
                         cfg.param_dtype)
    eng = ServeEngine(cfg, params, ShardCtx(), max_batch=SLOTS, bucket_min=8)
    reqs = _requests(2)
    states = {id(r): [] for r in reqs}
    steps = admits = waited = 0
    with pytest.MonkeyPatch.context() as mp:
        waits = _count_waits(mp)
        eng.gru_wave_enqueue(reqs[:-LATE])
        while eng.gru_wave_active():
            if steps == LATE_AT:
                eng.gru_wave_enqueue(reqs[-LATE:])
            w = eng._wave
            # the lanes this step decodes: those live, then the queue's
            # head admitted into the empty slots in order
            lanes = {j: s.req for j, s in enumerate(w.slots) if s is not None}
            empty = [j for j in range(SLOTS) if j not in lanes]
            lanes.update(zip(empty, list(w.pending)))
            n, q = waits["n"], len(eng.queue_waits)
            eng.gru_wave_step()
            steps += 1
            admits += len(eng.queue_waits) > q
            waited += waits["n"] - n
            hs = [np.asarray(h) for h in w.cache["h"]]
            for j, r in lanes.items():
                states[id(r)].append([h[j] for h in hs])
    return cfg, params, eng, reqs, states, (steps, admits), waited


@pytest.fixture(scope="module", params=ARCHS)
def served(request):
    return _serve(request.param)


@functools.lru_cache(maxsize=None)
def _model_api(cfg):
    """The model API's prefill and ``decode_step`` at batch 1, jitted."""
    A, ctx = mapi.get_api(cfg), ShardCtx()
    return (jax.jit(lambda params, f: A.prefill(params, cfg,
                                                {"features": f}, ctx)),
            jax.jit(lambda params, cache, x: A.decode_step(params, cfg, cache,
                                                           x, ctx)))


def _reference(cfg, params, r):
    """The request alone through the model API: its unpadded prompt's
    prefill, then ``decode_step`` and ``argmax`` once a class; the classes
    and the state after every step."""
    prefill, decode = _model_api(cfg)
    p = np.asarray(r.prompt, np.float32)
    _, cache = prefill(params, jnp.asarray(p[None]))
    classes, states = [], []
    for t in range(r.max_new_tokens):
        x = (r.stream[t] if r.stream is not None and t < len(r.stream)
             else p[-1])
        logits, cache = decode(params, cache, jnp.asarray(x[None]))
        classes.append(int(np.argmax(np.asarray(logits)[0])))
        states.append([np.asarray(h)[0] for h in cache["h"]])
    return classes, states


def test_the_wave_step_never_blocks_and_waits_once_a_step(served):
    _, _, eng, reqs, _, (steps, _), waited = served
    assert all(r.done for r in reqs)
    assert waited == 0
    # every step of a drained wave decodes: one wait each, for the classes
    assert eng.device_waits == steps
    assert eng.latency_stats()["device_waits"] == steps


def test_classes_and_states_match_the_model_api_step_by_step(served):
    cfg, params, _, reqs, states, _, _ = served
    for r in reqs:
        classes, ref_states = _reference(cfg, params, r)
        assert r.out == classes
        assert len(states[id(r)]) == len(ref_states) == r.max_new_tokens
        for got, want in zip(states[id(r)], ref_states):
            for g, e in zip(got, want):
                np.testing.assert_allclose(g, e, rtol=1e-5, atol=1e-6)


def test_records_keep_their_lengths(served):
    """One ``step_times`` record a decoding step (each step program's
    first, compiling step excluded: the wave program of buckets 8 and 16
    and the decode-only program) and one ``prefill_times`` record an
    admitting step (every bucket compiled in this wave included)."""
    _, _, eng, reqs, _, (steps, admits), _ = served
    assert len(eng._wave_jit) == 2 and len(eng._decode_jit) == 1
    assert len(eng.step_times) == len(eng.decode_backends) == steps - 3
    assert len(eng.prefill_times) == len(eng.prefill_backends) == admits
    assert len(eng.queue_waits) == len(reqs)
