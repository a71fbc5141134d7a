"""The compile/execute split (repro.core.runtime): GRUExecutable caching,
Placement-resident prepare(), the measured CostModel, and the legacy
``plan()``/``ExecPlan`` shims.

Acceptance contract of the redesign:

* ``compile()`` is memoized by (cfg, shapes, placement, cost epoch) —
  identical keys return the SAME object (jit stability), distinct
  placements (different meshes) compile distinct executables.
* ``prepare(params, cfg, placement)`` with a mesh performs ALL device
  placement up front: a traced sharded sequence/decode call contains no
  ``device_put`` of weight arrays (jaxpr inspection, multidev test).
* With a calibration file, ``backend="auto"`` selects per shape (two
  shapes whose measured costs invert the static preference order pick
  different backends); with a missing/corrupt file, selection degrades
  to the static table — identical to the pre-CostModel executor.
* ``plan()``/``ExecPlan`` warn once and are bitwise-equal to
  ``compile()``/``GRUExecutable`` across the dispatch matrix.
"""
import json
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import GRUConfig
from repro.core import gru, runtime
from repro.core.params import init_params

TOL = dict(rtol=3e-5, atol=3e-6)


@pytest.fixture(autouse=True)
def _cost_isolation():
    """Restore the suite's hermetic (empty -> static) cost model after any
    test that installs its own calibration."""
    yield
    runtime.set_cost_model(runtime.CostModel({}, source="<tests: static>"))


def _cfg(depth=3, hetero=False, backend="auto", **kw):
    if hetero:
        return GRUConfig(input_dim=5, layer_dims=(16, 8, 12)[:depth],
                         backend=backend, **kw)
    return GRUConfig(input_dim=5, hidden_dim=16, num_layers=depth,
                     backend=backend, **kw)


def _data(cfg, B=2, T=6, key=1):
    xs = jax.random.normal(jax.random.key(key), (B, T, cfg.input_dim))
    return xs, gru.stack_h0(cfg, B)


def _calib(depth, H, costs_by_backend, batch=1, op="decode"):
    return [{"backend": b, "op": op, "depth": depth, "batch": batch,
             "hidden_dim": H, "p50_us": us}
            for b, us in costs_by_backend.items()]


# ---------------------------------------------------------------------------
# executable cache keying
# ---------------------------------------------------------------------------

def test_recompile_identical_key_returns_same_object():
    cfg = _cfg(2)
    a = runtime.compile(cfg, batch=4, seq=8, mode="serve")
    b = runtime.compile(cfg, batch=4, seq=8, mode="serve")
    assert a is b and a.sequence is b.sequence and a.decode is b.decode
    # any key component changes the executable
    assert runtime.compile(cfg, batch=8, seq=8, mode="serve") is not a
    assert runtime.compile(cfg, batch=4, seq=8, mask=True,
                           mode="serve") is not a


def test_distinct_placements_compile_distinct_executables():
    """Host vs mesh, and two meshes differing only in axis naming, all
    key separately; re-compiling each key hits its memoized object."""
    from jax.sharding import Mesh
    cfg = _cfg(2)
    dev = np.array(jax.devices()[:1])
    pa = runtime.Placement(mesh=Mesh(dev, ("model",)))
    pb = runtime.Placement(mesh=Mesh(dev, ("row",)), axis="row")
    host = runtime.compile(cfg, batch=2, seq=6, mode="prefill")
    ea = runtime.compile(cfg, batch=2, seq=6, placement=pa, mode="prefill")
    eb = runtime.compile(cfg, batch=2, seq=6, placement=pb, mode="prefill")
    assert len({id(host), id(ea), id(eb)}) == 3
    assert ea is runtime.compile(cfg, batch=2, seq=6, placement=pa,
                                 mode="prefill")
    assert ea.sequence_backend == "pallas_sharded"      # mesh: kernel-fused
    assert host.sequence_backend not in ("sharded", "pallas_sharded")
    # the 1-device mesh placements execute correctly, axis naming included
    params = init_params(gru.gru_stack_specs(cfg), jax.random.key(0))
    xs, h0s = _data(cfg)
    ref, _ = gru.gru_stack_reference(params, h0s, xs)
    for exe in (ea, eb):
        finals, _ = exe.sequence(exe.prepare(params), h0s, xs)
        for a, b in zip(finals, ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), **TOL)


def test_cost_epoch_invalidates_memoized_executables():
    """Installing a calibration must not resurrect executables planned
    under the old costs (the epoch is part of the cache key)."""
    cfg = _cfg(3)
    before = runtime.compile(cfg, batch=1, mode="decode")
    assert before.decode_backend == "pallas_fused"       # static order
    runtime.set_cost_model(runtime.CostModel.from_entries(_calib(
        3, 16, {"xla": 10.0, "pallas_fused": 90.0, "pallas_chain": 95.0})))
    after = runtime.compile(cfg, batch=1, mode="decode")
    assert after is not before
    assert after.decode_backend == "xla" and after.cost_source == "measured"


# ---------------------------------------------------------------------------
# cost model: measured per-shape selection, static fallback
# ---------------------------------------------------------------------------

def test_calibration_selects_per_shape_inverting_static_order():
    """The acceptance case: two shapes whose measured costs invert the
    static preference order (pallas_fused=10 < xla=30) pick DIFFERENT
    backends under one calibration."""
    cfg = _cfg(3)
    entries = (_calib(3, 16, {"xla": 40.0, "pallas_fused": 200.0,
                              "pallas_chain": 250.0}, batch=1)
               + _calib(3, 16, {"xla": 400.0, "pallas_fused": 80.0,
                                "pallas_chain": 90.0}, batch=8))
    runtime.set_cost_model(runtime.CostModel.from_entries(entries))
    e1 = runtime.compile(cfg, batch=1, mode="decode")
    e8 = runtime.compile(cfg, batch=8, mode="decode")
    assert e1.decode_backend == "xla"            # inverts the static order
    assert e8.decode_backend == "pallas_fused"
    assert e1.cost_source == e8.cost_source == "measured"
    # an uncalibrated shape (different depth) degrades to static per call
    e_other = runtime.compile(_cfg(2), batch=1, mode="decode")
    assert e_other.cost_source == "static"
    assert e_other.decode_backend == "pallas_fused"


def test_sequence_calibration_flips_prefill_choice_per_shape():
    """The sequence half of the calibration (op="sequence" rows, emitted
    by decode_latency.py --emit-costs): two shapes whose measured SEQUENCE
    costs invert the static order pick different prefill backends, while
    decode selection is untouched (stays static: no decode rows here)."""
    cfg = _cfg(3)
    entries = (_calib(3, 16, {"xla": 40.0, "pallas_fused": 200.0,
                              "pallas_chain": 250.0}, batch=1, op="sequence")
               + _calib(3, 16, {"xla": 400.0, "pallas_fused": 80.0,
                                "pallas_chain": 90.0}, batch=8,
                        op="sequence"))
    runtime.set_cost_model(runtime.CostModel.from_entries(entries))
    e1 = runtime.compile(cfg, batch=1, seq=12, mode="prefill")
    e8 = runtime.compile(cfg, batch=8, seq=12, mode="prefill")
    assert e1.sequence_backend == "xla"          # inverts the static order
    assert e8.sequence_backend == "pallas_fused"
    assert e1.cost_source == e8.cost_source == "measured"
    # decode at the same shapes has no measured rows -> static order
    ed = runtime.compile(cfg, batch=1, mode="decode")
    assert ed.cost_source == "static"
    assert ed.decode_backend == "pallas_fused"


def test_decode_only_calibration_degrades_sequence_to_static_only():
    """A calibration that covers decode but NOT sequence must degrade to
    the static order for sequence selection ONLY — decode keeps its
    measured choice (per-op fallback, not global)."""
    cfg = _cfg(3)
    runtime.set_cost_model(runtime.CostModel.from_entries(_calib(
        3, 16, {"xla": 1.0, "pallas_fused": 50.0, "pallas_chain": 60.0},
        batch=1, op="decode")))
    es = runtime.compile(cfg, batch=1, seq=8, mode="prefill")
    assert es.cost_source == "static"            # sequence: no coverage
    assert es.sequence_backend == "pallas_fused"     # the static winner
    ed = runtime.compile(cfg, batch=1, mode="decode")
    assert ed.cost_source == "measured"          # decode: fully covered
    assert ed.decode_backend == "xla"            # inverts the static order
    # one executable carrying both ops keeps the per-op split
    eb = runtime.compile(cfg, batch=1, seq=8, mode="serve")
    assert eb.sequence_backend == "pallas_fused"
    assert eb.decode_backend == "xla"


def test_calibration_interpolates_and_clamps_batch():
    m = runtime.CostModel.from_entries(
        _calib(1, 16, {"xla": 100.0}, batch=2)
        + _calib(1, 16, {"xla": 300.0}, batch=6))
    lk = lambda b: m.lookup("xla", "decode", depth=1, batch=b, hidden=16)
    assert lk(2) == 100.0 and lk(6) == 300.0
    assert lk(4) == 200.0                        # linear between points
    assert lk(1) == 100.0 and lk(64) == 300.0    # clamped to the edges
    assert lk(2) is not None
    assert m.lookup("xla", "decode", depth=2, batch=2, hidden=16) is None
    assert m.lookup("pallas_fused", "decode", depth=1, batch=2,
                    hidden=16) is None


def test_partial_calibration_falls_back_to_static():
    """µs and static ints are not comparable: if ANY legal candidate is
    uncovered, the whole selection uses the static table."""
    cfg = _cfg(3)
    runtime.set_cost_model(runtime.CostModel.from_entries(_calib(
        3, 16, {"xla": 1.0, "pallas_fused": 2.0})))   # chain missing
    exe = runtime.compile(cfg, batch=1, mode="decode")
    assert exe.cost_source == "static"
    assert exe.decode_backend == "pallas_fused"


def test_missing_and_corrupt_calibration_resolve_to_static(tmp_path):
    missing = runtime.CostModel.load(tmp_path / "nope.json")
    assert len(missing) == 0 and missing.error is not None
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    corrupt = runtime.load_cost_model(bad)
    assert len(corrupt) == 0 and corrupt.error is not None
    exe = runtime.compile(_cfg(3), batch=1, mode="decode")
    assert exe.cost_source == "static"
    assert exe.decode_backend == "pallas_fused"      # unchanged from PR 3
    schema_mismatch = tmp_path / "other.json"
    schema_mismatch.write_text(json.dumps({"bench": "something_else",
                                           "entries": []}))
    assert len(runtime.CostModel.load(schema_mismatch)) == 0


def test_default_calibration_loads_from_env(tmp_path, monkeypatch):
    """The lazy default load honors $REPRO_GRU_COSTS (the CI artifact
    path), and a benchmark-emitted file round-trips through CostModel."""
    path = tmp_path / "BENCH_backend_costs.json"
    path.write_text(json.dumps({
        "bench": "gru_backend_costs", "schema": 1, "device": "cpu",
        "entries": _calib(3, 16, {"xla": 5.0, "pallas_fused": 50.0,
                                  "pallas_chain": 60.0})}))
    monkeypatch.setenv("REPRO_GRU_COSTS", str(path))
    runtime.set_cost_model(None)                 # re-arm the lazy load
    exe = runtime.compile(_cfg(3), batch=1, mode="decode")
    assert exe.cost_source == "measured" and exe.decode_backend == "xla"
    assert runtime.cost_model().source == str(path)


@pytest.mark.parametrize("device", ["tpu", None])
def test_calibration_from_another_platform_is_refused(tmp_path, monkeypatch,
                                                      device):
    """An artifact measured on another platform (or naming none) loads as
    an empty model with ``error`` set, so the static table decides: CPU
    interpret-mode timings never steer dispatch on the chip."""
    path = tmp_path / "BENCH_backend_costs.json"
    art = {"bench": "gru_backend_costs", "schema": 1,
           "entries": _calib(3, 16, {"xla": 5.0, "pallas_fused": 50.0,
                                     "pallas_chain": 60.0})}
    if device is not None:
        art["device"] = device
    path.write_text(json.dumps(art))
    m = runtime.CostModel.load(path)
    assert len(m) == 0 and str(device) in m.error
    monkeypatch.setenv("REPRO_GRU_COSTS", str(path))
    runtime.set_cost_model(None)                 # re-arm the lazy load
    exe = runtime.compile(_cfg(3), batch=1, mode="decode")
    assert exe.cost_source == "static"
    assert exe.decode_backend == "pallas_fused"


def test_emit_costs_schema_loads():
    """benchmarks/decode_latency.py --emit-costs writes exactly what
    CostModel.load expects (schema lockstep, no benchmark run needed)."""
    import importlib.util, pathlib
    spec = importlib.util.spec_from_file_location(
        "decode_latency", pathlib.Path(__file__).resolve().parents[1]
        / "benchmarks" / "decode_latency.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    rows = [{"via": "runtime", "backend": "xla", "depth": 1, "batch": 1,
             "hidden_dim": 32, "p50_us": 12.5},       # no op field: decode
            {"via": "runtime", "backend": "pallas_fused", "depth": 1,
             "batch": 1, "hidden_dim": 32, "p50_us": 8.0, "op": "decode"},
            {"via": "runtime", "backend": "xla", "depth": 1, "batch": 1,
             "hidden_dim": 32, "p50_us": 95.0, "op": "sequence",
             "seq_len": 16},                          # same key, other op
            {"via": "direct", "backend": "fused", "depth": 1, "batch": 8,
             "hidden_dim": 32, "p50_us": 9.0}]      # non-runtime: dropped
    import tempfile, os
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "BENCH_backend_costs.json")
        out = mod.emit_costs(rows, path, csv=False)
        assert len(out["entries"]) == 3
        m = runtime.CostModel.load(path)
    assert len(m) == 3
    assert m.lookup("xla", "decode", depth=1, batch=1, hidden=32) == 12.5
    assert m.lookup("xla", "sequence", depth=1, batch=1, hidden=32) == 95.0
    assert m.lookup("pallas_fused", "sequence", depth=1, batch=1,
                    hidden=32) is None
    assert m.lookup("fused", "decode", depth=1, batch=8, hidden=32) is None


# ---------------------------------------------------------------------------
# legacy shims: plan() / ExecPlan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("depth,hetero", [(1, False), (3, False), (3, True)])
def test_plan_shim_bitwise_equals_compile(depth, hetero):
    """plan() returns the SAME memoized executable compile() builds, and
    running through either surface is bitwise-identical."""
    cfg = _cfg(depth, hetero)
    params = init_params(gru.gru_stack_specs(cfg), jax.random.key(0))
    xs, h0s = _data(cfg)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        p = runtime.plan(cfg, batch=2, seq=6, mode="serve")
    c = runtime.compile(cfg, batch=2, seq=6, mode="serve")
    assert p is c
    f_p, _ = p.sequence(params, h0s, xs)
    f_c, _ = c.sequence(params, h0s, xs)
    for a, b in zip(f_p, f_c):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(p.decode(params, h0s, xs[:, 0]),
                    c.decode(params, h0s, xs[:, 0])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_plan_and_execplan_warn_once():
    gru._DEPRECATION_WARNED.discard("runtime.plan")
    gru._DEPRECATION_WARNED.discard("runtime.ExecPlan")
    cfg = _cfg(2)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        runtime.plan(cfg, batch=2, seq=6, mode="serve")
        runtime.plan(cfg, batch=2, seq=6, mode="serve")     # no second warn
        assert runtime.ExecPlan is runtime.GRUExecutable
        runtime.ExecPlan                                     # no second warn
    deps = [str(x.message) for x in w
            if issubclass(x.category, DeprecationWarning)]
    assert len(deps) == 2, deps
    assert any("runtime.plan" in m for m in deps)
    assert any("runtime.ExecPlan" in m for m in deps)
    assert all("compile" in m for m in deps)
    assert isinstance(runtime.compile(cfg, mode="serve"), runtime.ExecPlan)


# ---------------------------------------------------------------------------
# prepare(): placement-resident params
# ---------------------------------------------------------------------------

def test_prepare_params_dict_carries_placed_views():
    """gru_lm.prepare_params under a mesh ctx attaches pre-placed views
    that runtime.prepare reuses verbatim — the engine's params round-trip
    never re-places weights."""
    from jax.sharding import Mesh
    from repro.configs.base import get_smoke_config
    from repro.distributed.sharding import ShardCtx
    from repro.models import gru_lm
    from repro.models import api as mapi
    cfg = get_smoke_config("gru-jet-deep")
    A = mapi.get_api(cfg)
    params = init_params(A.specs(cfg), jax.random.key(0), cfg.param_dtype)
    mesh = Mesh(np.array(jax.devices()[:1]), ("model",))
    prepared = gru_lm.prepare_params(params, cfg, ShardCtx(mesh=mesh))
    assert "placed_cells" in prepared and "stacked_cells" in prepared
    sp = runtime.prepare(prepared, cfg.gru, runtime.Placement(mesh=mesh))
    assert sp.placed is prepared["placed_cells"]
    # host ctx: no placed views, stacked only (the PR 3 behavior)
    host = gru_lm.prepare_params(params, cfg, ShardCtx())
    assert "placed_cells" not in host and "stacked_cells" in host


def test_prepare_replaces_stale_placed_views_from_another_mesh():
    """A dict prepared for mesh A must not leak its placed views into a
    prepare for mesh B: the guard re-places instead of feeding arrays
    committed elsewhere into the new mesh's shard_map."""
    from jax.sharding import Mesh, NamedSharding
    cfg = _cfg(2)
    params = init_params(gru.gru_stack_specs(cfg), jax.random.key(0))
    dev = np.array(jax.devices()[:1])
    pa = runtime.Placement(mesh=Mesh(dev, ("model",)))
    pb = runtime.Placement(mesh=Mesh(dev, ("row",)), axis="row")
    sp_a = runtime.prepare(params, cfg, pa)
    carrier = {"cells": sp_a.cells, "placed_cells": sp_a.placed}
    sp_b = runtime.prepare(carrier, cfg, pb)
    assert sp_b.placed is not sp_a.placed            # stale views dropped
    arr = next(iter(sp_b.placed[0].values()))
    assert isinstance(arr.sharding, NamedSharding)
    assert arr.sharding.mesh == pb.mesh
    # matching mesh: reused verbatim
    sp_a2 = runtime.prepare(carrier, cfg, pa)
    assert sp_a2.placed is sp_a.placed


def test_executable_prepare_builds_only_what_its_backends_read():
    cfg = _cfg(2, backend="xla")
    params = init_params(gru.gru_stack_specs(cfg), jax.random.key(0))
    exe = runtime.compile(cfg, batch=2, seq=6, mode="serve")
    sp = exe.prepare(params)
    assert sp.stacked is None and sp.placed is None    # xla reads cells
    cfg_p = _cfg(2, backend="pallas")
    exe_p = runtime.compile(cfg_p, batch=2, seq=6, mode="serve")
    sp_p = exe_p.prepare(params)
    assert sp_p.stacked is not None                    # fused kernel views


def test_compile_mesh_placement_resident(multidev):
    """Acceptance: prepare(params, cfg, placement) with a mesh performs
    ALL device placement up front — the traced sharded sequence AND decode
    calls contain no device_put of weight arrays (jaxpr inspection); the
    raw-params path DOES trace device_puts (the assertion bites); distinct
    meshes compile distinct executables; prepared and raw execution agree
    bitwise."""
    multidev("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import GRUConfig
from repro.core import gru, runtime
from repro.core.params import init_params

def prim_names(fn, *args):
    names = set()
    def walk(j):
        for e in j.eqns:
            names.add(e.primitive.name)
            for v in e.params.values():
                if hasattr(v, "jaxpr"):
                    walk(v.jaxpr)
    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return names

mesh = jax.make_mesh((4,), ("model",))
placement = runtime.Placement(mesh=mesh)
cfg = GRUConfig(input_dim=6, layer_dims=(16, 16), backend="auto",
                layer_matvec_modes=("rowwise", "cascade"))
params = init_params(gru.gru_stack_specs(cfg), jax.random.key(0))
xs = jax.random.normal(jax.random.key(1), (2, 7, 6))
h0s = gru.stack_h0(cfg, 2)
exe = runtime.compile(cfg, batch=2, seq=7, placement=placement,
                      mode="prefill")
assert exe.sequence_backend == "pallas_sharded"
sp = exe.prepare(params)
assert sp.placed is not None
for arr in sp.placed[0].values():      # placement happened eagerly
    assert isinstance(arr.sharding, jax.sharding.NamedSharding), arr.sharding
n_prep = prim_names(lambda p, h, x: exe.sequence(p, h, x), sp, h0s, xs)
n_raw = prim_names(lambda p, h, x: exe.sequence(p, h, x), params, h0s, xs)
assert "device_put" not in n_prep, sorted(n_prep)
assert "device_put" in n_raw
# distinct meshes (same shapes) compile distinct executables; the same
# key hits the memoized object (checked BEFORE the calibration install
# below — installing a cost model bumps the epoch on purpose)
mesh2 = jax.make_mesh((2,), ("model",))
e2 = runtime.compile(cfg, batch=2, seq=7,
                     placement=runtime.Placement(mesh=mesh2),
                     mode="prefill")
assert e2 is not exe
assert exe is runtime.compile(cfg, batch=2, seq=7, placement=placement,
                              mode="prefill")
# decode: force the sharded step via calibration, same assertions
runtime.set_cost_model(runtime.CostModel.from_entries(
    [{"backend": b, "op": "decode", "depth": 2, "batch": 2,
      "hidden_dim": 16, "p50_us": 5.0 if b == "sharded_decode" else 50.0}
     for b in ("xla", "pallas_fused", "pallas_chain", "sharded_decode",
               "pallas_sharded")]))
ed = runtime.compile(cfg, batch=2, placement=placement, mode="decode")
assert ed.decode_backend == "sharded_decode"
nd_prep = prim_names(lambda p, h, x: ed.decode(p, h, x), sp, h0s, xs[:, 0])
nd_raw = prim_names(lambda p, h, x: ed.decode(p, h, x), params, h0s,
                    xs[:, 0])
assert "device_put" not in nd_prep, sorted(nd_prep)
assert "device_put" in nd_raw
# prepared == raw, bitwise (placement moves work, not numerics)
f_prep, _ = exe.sequence(sp, h0s, xs)
f_raw, _ = exe.sequence(params, h0s, xs)
for a, b in zip(f_prep, f_raw):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
for a, b in zip(ed.decode(sp, h0s, xs[:, 0]),
                ed.decode(params, h0s, xs[:, 0])):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
print("PASS")
""", timeout=560)