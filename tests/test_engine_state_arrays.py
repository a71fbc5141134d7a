"""Every cell family is served by one model module (``models/gru_lm``),
which takes what differs from the family's registration; and the engine
counts the device arrays its wave cache's state is made of
(``ServeEngine.state_arrays``: layers x the family's state leaves)."""
import importlib.util

import jax
import numpy as np
import pytest

from repro.configs.base import get_smoke_config
from repro.core import cells, slstm
from repro.core.params import init_params
from repro.distributed.sharding import ShardCtx
from repro.models import api as mapi
from repro.models import gru_lm
from repro.serve.engine import Request, ServeEngine

ARCHS = {"gru-jet": 1, "gru-jet-deep": 3, "slstm-jet": 4}


def _engine(arch, slots=4):
    cfg = get_smoke_config(arch)
    api = mapi.get_api(cfg)
    params = init_params(api.specs(cfg), jax.random.key(0))
    return cfg, ServeEngine(cfg, params, ShardCtx(), max_batch=slots)


@pytest.mark.parametrize("arch,arrays", sorted(ARCHS.items()))
def test_state_arrays_counts_the_wave_caches_arrays(arch, arrays):
    cfg, eng = _engine(arch)
    assert eng.state_arrays == arrays
    assert eng.latency_stats()["state_arrays"] == arrays
    rng = np.random.default_rng(0)
    X = cfg.gru.input_dim
    reqs = [Request(prompt=rng.normal(size=(5, X)).astype(np.float32),
                    max_new_tokens=2) for _ in range(6)]
    eng.gru_wave_begin(reqs)
    eng.gru_wave_step()
    assert len(eng._wave.cache["h"]) == arrays
    while eng.gru_wave_active():
        eng.gru_wave_step()
    assert all(r.done and len(r.out) == 2 for r in reqs)
    assert eng.latency_stats()["state_arrays"] == arrays


def test_lm_families_hold_no_wave_state():
    cfg = get_smoke_config("qwen3-0.6b")
    api = mapi.get_api(cfg)
    params = init_params(api.specs(cfg), jax.random.key(0))
    assert ServeEngine(cfg, params, ShardCtx()).state_arrays == 0


def test_one_model_module_serves_every_cell_family():
    assert importlib.util.find_spec("repro.models.slstm_lm") is None
    for arch in ("gru-jet", "slstm-jet"):
        api = mapi.get_api(get_smoke_config(arch))
        assert api.specs is gru_lm.lm_specs
        assert api.cache_specs is gru_lm.cache_specs
        assert api.init_cache is gru_lm.init_cache
        assert api.prepare_params is gru_lm.prepare_params


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_cache_is_the_familys_initial_state(arch):
    cfg = get_smoke_config(arch)
    fam = cells.get_family(cfg.gru.family)
    cache = gru_lm.init_cache(cfg, 3)
    want = fam.state0(cfg.gru, 3)
    assert len(cache["h"]) == ARCHS[arch] == len(
        gru_lm.cache_specs(cfg, 3)["h"])
    for got, w in zip(cache["h"], want):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(w))
    if fam.name == "slstm":
        np.testing.assert_array_equal(np.asarray(cache["h"][2]),
                                      np.float32(slstm.M_INIT))


@pytest.mark.parametrize("arch,layout", [("gru-jet", "cell"),
                                         ("gru-jet-deep", "cells"),
                                         ("slstm-jet", "cells")])
def test_specs_take_the_familys_gate_columns(arch, layout):
    cfg = get_smoke_config(arch)
    specs = gru_lm.lm_specs(cfg)
    fam = cells.get_family(cfg.gru.family)
    first = specs["cell"] if layout == "cell" else specs["cells"][0]
    H = cfg.gru.resolved_layer_dims[0]
    assert first["u"].shape == (H, fam.gates * H)
    assert specs["head"]["w"].shape == (cfg.gru.resolved_layer_dims[-1],
                                        cfg.gru.num_classes)


class _Seen(Exception):
    pass


@pytest.mark.parametrize("arch,want", [("gru-jet", "bfloat16"),
                                       ("slstm-jet", "float32")])
def test_state_dtype_under_narrow_features(arch, want, monkeypatch):
    """The classifier's forward and prefill start the state in the
    features' dtype, except where the family fixes one: the sLSTM's
    normalizer and stabilizer stay float32 under bfloat16 features."""
    import jax.numpy as jnp
    from repro.core import runtime
    cfg = get_smoke_config(arch)
    seen = []

    def spy(*args, **kw):
        seen.append({str(a.dtype) for a in args[1]})
        raise _Seen

    monkeypatch.setattr(runtime, "sequence", spy)
    prog = type("Prog", (), {"prefill": staticmethod(spy)})()
    monkeypatch.setattr(runtime, "compile", lambda *a, **kw: prog)
    params = init_params(gru_lm.lm_specs(cfg), jax.random.key(0))
    xs = jnp.zeros((2, 3, cfg.gru.input_dim), jnp.bfloat16)
    for call in (gru_lm.forward, gru_lm.prefill):
        with pytest.raises(_Seen):
            call(params, cfg, {"features": xs})
    assert seen == [{want}, {want}]
