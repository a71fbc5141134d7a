"""Property-based fuzz of the recurrent executor's dispatch matrix.

Random draws over the FULL request space — CELL FAMILY (gru/slstm: the
``(family, backend)`` registry namespaces), depth 1-4, uniform/hetero
``layer_dims``, rowwise/cascade mode mixes, mask on/off, mesh/none,
backend pin vs auto, prefill vs decode — must always:

* resolve (``compile()`` never raises: ``xla`` is universally legal, so
  an illegal preference falls through instead of erroring),
* resolve LEGALLY (the chosen backend's declared ``Capabilities`` cover
  the request — the silent-capability-gap failure mode the executor
  exists to eliminate),
* run correctly (``allclose`` vs the family's registered reference — the
  oracle is drawn with the family, never hardcoded to GRU), and
* honor the bitwise mask contract wherever the executable CLAIMS
  ``mask_exact`` (padded+masked == unpadded at identical batch shapes).

The hypothesis draws are derandomized — a fixed seed profile, so CI is
deterministic; the pinned ``test_dispatch_case_pinned`` corners run
alongside them.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st
from _q8 import q8_stack_decode, q8_stack_finals
from repro.configs.base import GRUConfig
from repro.core import cells, gru, runtime
from repro.core.params import init_params

TOL = dict(rtol=3e-5, atol=3e-6)
DEC_TOL = dict(rtol=1e-4, atol=1e-5)
# q8 draws compare against the quantize-dequantize twin oracle, which
# accumulates the kernels' int32 sums exactly at these sizes — so the
# q8 tolerance is TIGHTER than the f32 one, not looser.
Q8_TOL = dict(rtol=1e-6, atol=1e-6)
B, T, X, PAD = 2, 5, 5, 3
DIM_POOL = (8, 12, 16)
BACKENDS = ("auto", "xla", "pallas", "pallas_fused", "pallas_chain",
            "sharded", "pallas_sharded", "sharded_decode",
            "pallas_fused_q8", "pallas_chain_q8")
# per-family backend pools: the sLSTM namespace registers xla +
# pallas_fused; pins on GRU-only names still belong in its pool — they
# must FALL THROUGH to a legal (slstm, ·) backend, never resolve across
# the family boundary or error
FAMILY_BACKENDS = {
    "gru": BACKENDS,
    "slstm": ("auto", "xla", "pallas", "pallas_fused", "pallas_chain",
              "pallas_fused_q8"),
}


@functools.lru_cache(maxsize=None)
def _mesh_placement():
    """One shared single-device mesh: a stable Placement so executables
    memoize across examples (multi-device dispatch runs in the multidev
    suites; the capability/dispatch logic is device-count-agnostic)."""
    from jax.sharding import Mesh
    return runtime.Placement(mesh=Mesh(np.array(jax.devices()[:1]),
                                       ("model",)))


@functools.lru_cache(maxsize=None)
def _case_params(dims: tuple, modes: tuple, backend: str,
                 family: str = "gru"):
    cfg = GRUConfig(input_dim=X, layer_dims=dims, backend=backend,
                    layer_matvec_modes=modes, family=family)
    if family == "gru":
        specs = gru.gru_stack_specs(cfg)
    else:
        specs = {"cells": cells.get_family(family).stack_specs(cfg)}
    params = init_params(specs, jax.random.key(0))
    return cfg, params


@functools.lru_cache(maxsize=None)
def _data():
    xs = jax.random.normal(jax.random.key(1), (B, T, X))
    xs_pad = jnp.pad(xs, ((0, 0), (PAD, 0), (0, 0)))
    mask = jnp.broadcast_to(jnp.arange(T + PAD)[None, :] >= PAD,
                            (B, T + PAD))
    return xs, xs_pad, mask


def _assert_capabilities_cover(backend_name: str, *, op: str, masked: bool,
                               hetero: bool, mesh,
                               family: str = "gru") -> None:
    """The dispatch contract: the resolved backend's declared caps cover
    the request — looked up in the FAMILY's registry namespace (a name
    resolving outside it would be the cross-family dispatch bug)."""
    spec = runtime.backends(family)[backend_name]
    c = spec.caps
    if op == "decode":
        assert c.decode and spec.decode_fn is not None, backend_name
    else:
        assert c.sequence and spec.sequence_fn is not None, backend_name
        assert not masked or c.supports_mask, backend_name
    assert not hetero or c.supports_hetero_dims, backend_name
    # a mesh-REQUIRING backend must never resolve without a mesh
    assert not (c.supports_mesh and mesh is None), backend_name


def check_dispatch_case(depth: int, dims: tuple, modes: tuple, masked: bool,
                        mesh_on: bool, backend: str, mode: str,
                        family: str = "gru") -> None:
    """One cell of the dispatch matrix, end to end."""
    assert len(dims) == len(modes) == depth
    fam = cells.get_family(family)
    cfg, params = _case_params(dims, modes, backend, family)
    xs, xs_pad, mask = _data()
    h0s = fam.state0(cfg, B)
    cell_p = fam.normalize(params, cfg)
    hetero = any(d != dims[0] for d in dims)
    placement = _mesh_placement() if mesh_on else None
    mesh = placement.mesh if mesh_on else None
    ref, _ = fam.reference(cell_p, h0s, xs)

    # 1. always resolves, and resolves legally
    p = runtime.compile(cfg, batch=B, seq=T + PAD if masked else T,
                        placement=placement, mask=masked, mode=mode)
    if mode == "decode":
        assert p.decode_backend is not None
        _assert_capabilities_cover(p.decode_backend, op="decode",
                                   masked=False, hetero=hetero, mesh=mesh,
                                   family=family)
        tol = DEC_TOL
        if p.decode_backend.endswith("_q8"):
            # a q8 pin resolved to the int8 datapath: its oracle is the
            # backend's own quantize-dequantize twin, not the f32 stack
            ref = h0s
            for t in range(T):
                ref = q8_stack_decode(p.decode_backend, cell_p, ref,
                                      xs[:, t], cfg)
            tol = Q8_TOL
        hs = h0s
        for t in range(T):
            hs = p.decode(params, hs, xs[:, t])
        for a, b in zip(hs, ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), **tol)
        return
    assert p.sequence_backend is not None
    _assert_capabilities_cover(p.sequence_backend, op="sequence",
                               masked=masked, hetero=hetero, mesh=mesh,
                               family=family)
    tol = TOL
    if p.sequence_backend.endswith("_q8"):
        ref = q8_stack_finals(p.sequence_backend, cell_p, h0s, xs, cfg)
        tol = Q8_TOL

    # 2. runs correctly against the dense oracle
    if not masked:
        finals, _ = p.sequence(params, h0s, xs)
    else:
        finals, _ = p.sequence(params, h0s, xs_pad, mask=mask)
        if p.mask_exact:
            # 3. the claimed bitwise mask contract, held to bitwise
            un = runtime.compile(cfg, batch=B, seq=T, placement=placement,
                                 mode=mode)
            f_un, _ = un.sequence(params, h0s, xs)
            for a, b in zip(f_un, finals):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(finals, ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), **tol)


# ---------------------------------------------------------------------------
# the property: random draws over the whole request space
# ---------------------------------------------------------------------------

@settings(max_examples=40, deadline=None, derandomize=True)
@given(st.data())
def test_dispatch_matrix_property(data):
    """Any (family, depth, dims, modes, mask, mesh, backend, mode) draw
    resolves legally (Capabilities coverage inside the family's registry
    namespace) and matches the family's reference oracle (bitwise where
    mask-exactness is claimed). ``derandomize=True`` pins the example
    sequence — the CI run is deterministic."""
    family = data.draw(st.sampled_from(sorted(FAMILY_BACKENDS)),
                       label="family")
    depth = data.draw(st.integers(min_value=1, max_value=4), label="depth")
    uniform = data.draw(st.booleans(), label="uniform")
    if uniform:
        h = data.draw(st.sampled_from(DIM_POOL), label="hidden")
        dims = (h,) * depth
    else:
        dims = tuple(data.draw(
            st.lists(st.sampled_from(DIM_POOL), min_size=depth,
                     max_size=depth), label="dims"))
    modes = tuple(data.draw(
        st.lists(st.sampled_from(("rowwise", "cascade")), min_size=depth,
                 max_size=depth), label="modes"))
    masked = data.draw(st.booleans(), label="masked")
    mesh_on = data.draw(st.booleans(), label="mesh")
    backend = data.draw(st.sampled_from(FAMILY_BACKENDS[family]),
                        label="backend")
    mode = data.draw(st.sampled_from(("prefill", "decode")), label="mode")
    check_dispatch_case(depth, dims, modes, masked, mesh_on, backend, mode,
                        family)


# ---------------------------------------------------------------------------
# pinned corners: run even without hypothesis (the shim skips the property)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("depth,dims,modes,masked,mesh_on,backend,mode", [
    # the new backend family, pinned by exact name, with and without mesh
    (2, (16, 16), ("rowwise", "cascade"), False, True, "pallas_sharded",
     "prefill"),
    (2, (16, 8), ("cascade", "rowwise"), True, True, "pallas_sharded",
     "prefill"),
    (3, (16, 8, 12), ("rowwise", "cascade", "rowwise"), False, True,
     "pallas_sharded", "decode"),
    (1, (16,), ("rowwise",), False, False, "pallas_sharded", "prefill"),
    # mesh-requiring pins without a mesh fall through, never error
    (2, (12, 12), ("cascade", "cascade"), True, False, "sharded", "prefill"),
    (2, (12, 12), ("rowwise", "rowwise"), False, False, "sharded_decode",
     "decode"),
    # hetero + pallas family falls to the chain; depth-4 uniform + mesh
    (3, (16, 8, 12), ("rowwise", "rowwise", "cascade"), True, False,
     "pallas", "prefill"),
    (4, (8, 8, 8, 8), ("rowwise", "cascade", "rowwise", "cascade"), True,
     True, "auto", "prefill"),
    (4, (8, 12, 16, 8), ("cascade",) * 4, False, True, "auto", "decode"),
    # q8 exact-name pins (bypass the accuracy gate): uniform fused —
    # plain, masked prefill (bitwise contract), decode; hetero chain
    (2, (12, 12), ("rowwise", "rowwise"), False, False, "pallas_fused_q8",
     "prefill"),
    (2, (12, 12), ("rowwise", "rowwise"), True, False, "pallas_fused_q8",
     "prefill"),
    (1, (16,), ("rowwise",), False, False, "pallas_fused_q8", "decode"),
    (2, (16, 8), ("rowwise", "rowwise"), False, False, "pallas_chain_q8",
     "decode"),
    # a fused_q8 pin on a hetero stack is illegal for the pinned backend:
    # it must fall through to a legal f32 backend, never error
    (2, (16, 8), ("rowwise", "rowwise"), False, False, "pallas_fused_q8",
     "prefill"),
])
def test_dispatch_case_pinned(depth, dims, modes, masked, mesh_on, backend,
                              mode):
    check_dispatch_case(depth, dims, modes, masked, mesh_on, backend, mode)


@pytest.mark.parametrize("depth,dims,modes,masked,mesh_on,backend,mode", [
    # the second family's fused kernel: plain, masked-bitwise, decode
    (1, (16,), ("rowwise",), False, False, "pallas_fused", "prefill"),
    (2, (16, 16), ("rowwise", "rowwise"), True, False, "pallas_fused",
     "prefill"),
    (3, (8, 8, 8), ("rowwise",) * 3, False, False, "pallas", "decode"),
    # hetero dims: fused is illegal in the slstm namespace too -> xla
    (2, (16, 8), ("rowwise", "rowwise"), True, False, "auto", "prefill"),
    # GRU-only names pinned under slstm fall through inside the family
    # namespace (never resolve a (gru, ·) backend, never error)
    (2, (16, 16), ("rowwise", "rowwise"), False, False, "pallas_chain",
     "decode"),
    (1, (16,), ("rowwise",), False, False, "pallas_fused_q8", "prefill"),
    # a mesh without any (slstm, ·) mesh backend resolves replicated
    (1, (16,), ("rowwise",), False, True, "auto", "prefill"),
])
def test_dispatch_case_pinned_slstm(depth, dims, modes, masked, mesh_on,
                                    backend, mode):
    check_dispatch_case(depth, dims, modes, masked, mesh_on, backend, mode,
                        family="slstm")
