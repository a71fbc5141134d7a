"""One device program per wave step (CPU, smoke sizes): a step that admits
runs ``gru_wave`` (the bucketed prefill, the scatter into the freed slots,
the decode and the argmax) and a step that admits nothing runs
``gru_decode``. Checked for one GRU layer, a GRU stack and the sLSTM:

- the programs each step dispatches, from the profiler's host trace;
- classes and every state leaf against the model API step by step,
  bitwise, through the first cohort, admits of every size and lanes freed
  by ``gru_wave_cancel``;
- after one warm admit at each bucket, admits of any size compile
  nothing."""
import glob
import os

import jax
import numpy as np
import pytest

from repro.configs.base import get_smoke_config
from repro.core.params import init_params
from repro.distributed.sharding import ShardCtx
from repro.models import api as mapi
from repro.serve.engine import Request, ServeEngine, bucket_len

ARCHS = ["gru-jet", "gru-jet-deep", "slstm-jet"]
SLOTS = 4
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _engine(arch):
    cfg = get_smoke_config(arch)
    params = init_params(mapi.get_api(cfg).specs(cfg), jax.random.key(3),
                         cfg.param_dtype)
    return ServeEngine(cfg, params, ShardCtx(), max_batch=SLOTS,
                       bucket_min=8)


def _requests(rng, lens, budget, stream=True):
    """One request a prompt length; every second streams its features."""
    return [Request(prompt=rng.normal(size=(S, 5)).astype(np.float32),
                    max_new_tokens=budget,
                    stream=(rng.normal(size=(budget, 5)).astype(np.float32)
                            if stream and i % 2 else None))
            for i, S in enumerate(lens)]


class _ModelAPI:
    """The wave served by the model API alone: each bucketed prefill and
    each decode step its own jitted call, the admitted rows copied into
    their slots on the host. Follows the engine's schedule (FIFO admits
    into the empty slots in order)."""

    def __init__(self, eng):
        A, cfg, ctx = eng.api, eng.cfg, eng.ctx
        self.eng = eng
        self.prefill = jax.jit(lambda p, b: A.prefill(p, cfg, b, ctx))
        self.decode = jax.jit(lambda p, c, x: A.decode_step(p, cfg, c, x,
                                                            ctx))
        self.cache = jax.tree.map(np.asarray, A.init_cache(cfg, SLOTS))

    def step(self):
        """Before the engine's step: the request and the class it must
        serve, per slot, and the admit size. Advances the model API's
        cache."""
        w, X = self.eng._wave, self.eng.cfg.gru.input_dim
        lanes = {j: s.req for j, s in enumerate(w.slots) if s is not None}
        empty = [j for j in range(SLOTS) if j not in lanes]
        admits = list(w.pending)[:len(empty)]
        h = [np.array(a) for a in self.cache["h"]]
        if admits:
            prompts = [np.asarray(r.prompt, np.float32).reshape(-1, X)
                       for r in admits]
            Sb = bucket_len(max(len(p) for p in prompts), 8)
            feats = np.zeros((SLOTS, Sb, X), np.float32)
            mask = np.zeros((SLOTS, Sb), bool)
            for i, p in enumerate(prompts):
                feats[i, Sb - len(p):] = p
                mask[i, Sb - len(p):] = True
            _, fresh = self.prefill(self.eng.params,
                                    {"features": feats, "mask": mask})
            for leaf, f in zip(h, fresh["h"]):
                f = np.asarray(f)
                for i, j in enumerate(empty[:len(admits)]):
                    leaf[j] = f[i]
            lanes.update(zip(empty, admits))
        nxt = np.zeros((SLOTS, X), np.float32)
        for j, r in lanes.items():
            t, p = len(r.out), np.asarray(r.prompt, np.float32)
            nxt[j] = (r.stream[t] if r.stream is not None
                      and t < len(r.stream) else p.reshape(-1, X)[-1])
        logits, cache = self.decode(self.eng.params,
                                    {"h": tuple(h), "pos": self.cache["pos"]},
                                    nxt)
        self.cache = jax.tree.map(np.asarray, cache)
        classes = np.argmax(np.asarray(logits), -1)
        return ({j: (r, int(classes[j])) for j, r in lanes.items()},
                len(admits))


def _serve_script(arch):
    """A wave driven through every admit size: a first cohort of
    ``SLOTS``, a step that admits nothing, then 1..``SLOTS`` lanes freed
    by ``gru_wave_cancel`` and refilled (over buckets 8 and 16), a lane
    left empty, and the rest served to the end with retires freeing the
    slots. Returns, per step, its admit size, whether the engine matched
    the model API (the classes served, every row of every state leaf, and
    ``pos``, which counts the wave's decode steps), its waits on the
    device and its ``gru_wave`` calls."""
    eng = _engine(arch)
    ref = _ModelAPI(eng)
    rng = np.random.default_rng(11)
    eng.gru_wave_enqueue(_requests(rng, [3, 9, 5, 7], 9))
    script = [(0, []), (0, []), (1, [4]), (2, [12, 6]), (3, [3, 10, 5]),
              (SLOTS, [8, 2, 13, 4]), (1, [])]
    steps = []
    for t in range(64):
        if not eng.gru_wave_active():
            break
        if t < len(script):
            cancel, lens = script[t]
            live = [s.req for s in eng._wave.slots if s is not None]
            for r in live[:cancel]:
                assert eng.gru_wave_cancel(r)
            eng.gru_wave_enqueue(_requests(rng, lens, 3 + t % 3))
        elif t == len(script):
            # queued behind full slots: admitted as retires free them
            eng.gru_wave_enqueue(_requests(rng, [5, 11, 3, 6, 9], 2))
        want, k = ref.step()
        waits, calls = eng.device_waits, eng.wave_calls
        eng.gru_wave_step()
        w = eng._wave
        same = (all(r.out[-1] == c for r, c in want.values())
                and all(np.array_equal(np.asarray(a), b) for a, b in
                        zip(w.cache["h"], ref.cache["h"]))
                and int(w.cache["pos"]) == t + 1)
        steps.append({"k": k, "same": same,
                      "waits": eng.device_waits - waits,
                      "calls": eng.wave_calls - calls})
    return eng, steps


@pytest.fixture(scope="module", params=ARCHS)
def scripted(request):
    return _serve_script(request.param)


def test_every_admit_size_matches_the_model_api_bitwise(scripted):
    eng, steps = scripted
    assert not eng.gru_wave_active()
    assert steps[0]["k"] == SLOTS                  # the first cohort
    assert steps[1]["k"] == 0                      # a step admitting none
    assert set(range(1, SLOTS + 1)) <= {s["k"] for s in steps[1:]}
    assert [i for i, s in enumerate(steps) if not s["same"]] == []


def test_one_wait_a_step_and_one_wave_call_an_admitting_step(scripted):
    eng, steps = scripted
    assert all(s["waits"] == 1 for s in steps)     # one wait a step
    assert [s["calls"] for s in steps] == [int(s["k"] > 0) for s in steps]
    assert eng.wave_calls == sum(s["k"] > 0 for s in steps)
    assert eng.latency_stats()["wave_calls"] == eng.wave_calls


def _host_events(log_dir):
    """(name, start, end) of every event on the serving thread's line."""
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert len(files) == 1, files
    data = jax.profiler.ProfileData.from_file(files[0])
    for plane in data.planes:
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for e in line.events]
            if any(n == "engine.step" for n, _, _ in evs):
                return evs
    return []


@pytest.mark.parametrize("arch", ARCHS)
def test_each_step_dispatches_one_program(arch, tmp_path):
    """Traced on a warm engine: each ``engine.step`` executes one program,
    ``gru_wave`` when it admits and ``gru_decode`` when it does not, copies
    no host array to the device outside it (its numpy arguments are
    transferred by the call itself) and waits on the device once."""
    eng = _engine(arch)
    rng = np.random.default_rng(5)

    def wave():
        eng.gru_wave_enqueue(_requests(rng, [3, 6, 5, 4, 7, 2], 3))
        admitted = []
        while eng.gru_wave_active():
            n = len(eng.queue_waits)
            eng.gru_wave_step()
            admitted.append(len(eng.queue_waits) > n)
        return admitted

    wave()                                          # every program compiles
    waits, calls = eng.device_waits, eng.wave_calls
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        admitted = wave()
    finally:
        jax.profiler.stop_trace()
    assert any(admitted) and not all(admitted)
    assert eng.device_waits - waits == len(admitted)
    assert eng.wave_calls - calls == sum(admitted)
    evs = _host_events(str(tmp_path))
    steps = sorted((s, e) for n, s, e in evs if n == "engine.step")
    assert len(steps) == len(admitted)
    for (s, e), adm in zip(steps, admitted):
        inside = [n for n, a, b in evs if s <= a and b <= e]
        runs = [n for n in inside if n.endswith("Executable::Execute")]
        programs = {n for n in inside if n.startswith("PjitFunction")}
        assert len(runs) == 1, inside
        assert not {"shard_args", "DevicePutWithSharding"} & set(inside)
        assert programs == {"PjitFunction(gru_wave)" if adm
                            else "PjitFunction(gru_decode)"}, inside


@pytest.mark.parametrize("arch", ARCHS)
def test_warm_buckets_compile_nothing_for_any_admit_size(arch):
    """One warm admit at each bucket (a first cohort at bucket 8, an admit
    at bucket 16), as the benchmark's warm-up runs them; then admits of
    every size at both buckets, and steps that admit nothing (compiled
    beside the first wave program), compile nothing and trace no new
    shape."""
    eng = _engine(arch)
    rng = np.random.default_rng(9)

    def serve(lens):
        eng.gru_wave_enqueue(_requests(rng, lens, 1))
        while eng.gru_wave_active():
            eng.gru_wave_step()

    serve([5] * SLOTS)                              # bucket 8, first cohort
    serve([12] * SLOTS)                             # bucket 16, live cache
    sizes = {k: f._cache_size() for k, f in eng._wave_jit.items()}
    assert sorted(sizes) == [8, 16]
    compiles = []

    def listen(event, duration, **_):
        if event == COMPILE_EVENT:
            compiles.append(duration)
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        for k in range(1, SLOTS + 1):
            serve([3 + k] * k)
            serve([9 + k] * k)
        calls = eng.wave_calls
        eng.gru_wave_enqueue(_requests(rng, [4] * SLOTS, 3))
        while eng.gru_wave_active():
            eng.gru_wave_step()
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert eng.wave_calls == calls + 1              # then decode-only steps
    assert compiles == []
    assert {k: f._cache_size() for k, f in eng._wave_jit.items()} == sizes
    assert list(eng._decode_jit) == [eng._wave.key]
    assert eng._prefill_jit == {}
