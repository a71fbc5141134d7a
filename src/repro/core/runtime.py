"""One recurrent-stack executor: a two-stage compile/execute API over
capability-dispatched backends, keyed by ``(cell family, backend)``.

The paper's core idea is a single workload-distribution framework that maps
GRU matvecs onto whichever compute fabric is available (AIE rows vs. the PL
cascade) — and, crucially, that weights are placed on the fabric ONCE and
every subsequent inference runs against resident rows. This module is that
framework's TPU translation, split the same way the hardware flow is:

* ``compile(cfg, batch=..., seq=..., placement=...) -> GRUExecutable`` —
  the ahead-of-time step. Resolves WHERE the stack runs (a ``Placement``:
  host, or a mesh + sharding rule) and WHICH backend serves each op, from
  a cost model that prefers *measured* per-shape latency over the static
  preference table. Executables are memoized: the same key returns the
  SAME object, so its callables are jit-stable.
* ``prepare(params, cfg, placement) -> StackParams`` — the weight-placement
  step. All device placement happens HERE, once: for a mesh placement the
  sharded backends' gate-major reshapes and ``device_put``s run up front
  (``StackParams.placed``), and the fused kernels' stacked weight views are
  built once (``StackParams.stacked``) — a traced execute call touches no
  weight-placement ops at all.
* ``executable.sequence/prefill/decode(...)`` — the execute stage: pure
  compute against placement-resident params.

CELL FAMILIES: the executor is not GRU-specific. ``cfg.family`` names a
registered :class:`repro.core.cells.CellFamily` (default ``"gru"``), and
every lookup here — the backend registry, ``compile()``'s selection,
``prepare()``'s weight views, the CostModel's measured rows — is keyed by
``(family, backend)``. Backends register under their family
(``BackendSpec.family``, default ``"gru"`` so the original registrations
are unchanged); an unknown ``cfg.family`` raises the typed
:class:`repro.core.cells.UnknownCellFamily` from ``compile()``. The
second in-tree family is sLSTM (``repro.core.slstm`` +
``repro.kernels.slstm_cell``): ``(slstm, xla)`` scan fallback at static
cost 30 and the fused ``(slstm, pallas_fused)`` kernels at cost 10, both
mask-exact, no mesh backends (a provided mesh falls through to the
replicated backends). A family's runtime state is a FLAT tuple of
per-layer leaves (GRU: one ``h`` per layer; sLSTM: ``c, n, m, h`` per
layer) — the ``h0s``/``hs`` arguments below are that tuple.

Capability table for ``family="gru"`` (see ``BackendSpec``; ``cost`` is
the STATIC dispatch fallback, lower = faster; a loaded :class:`CostModel`
replaces these numbers with measured per-(depth, batch, H) latency
whenever every legal candidate is covered):

===============  ====  ======  ====  ==========  ======  ========  ========
backend          mask  hetero  mesh  return_all  decode  sequence  cost
===============  ====  ======  ====  ==========  ======  ========  ========
pallas_fused     yes   no      no    yes         yes     yes       10
pallas_chain     yes   yes     no    yes         yes     yes       20
xla              yes   yes     no    yes         yes     yes       30
sharded          yes   yes     REQ   yes         no      yes       5
pallas_sharded   yes   yes     REQ   yes         yes     yes       4 / 190*
sharded_decode   n/a   yes     REQ   n/a         yes     no        200
pallas_fused_q8  yes   no      no    yes         yes     yes       150 (+)
pallas_chain_q8  yes   yes     no    yes         yes     yes       160 (+)
===============  ====  ======  ====  ==========  ======  ========  ========

(+) the ``*_q8`` backends are the int8 datapath (int8 weight rows, int32
accumulation, dequant folded into the bias add — see
``repro.kernels.gru_sequence.kernel``). They are DOUBLY gated: a backend
ending in ``_q8`` is a dispatch candidate only when ``cfg.quant ==
"int8"`` AND the recorded accuracy-harness artifact
(``repro/quant/accuracy.py`` -> ``BENCH_quant_accuracy.json``, installed
like the cost model via :func:`load_quant_accuracy` /
``$REPRO_GRU_QUANT_ACC``) reports ``passed`` — an uncalibrated or failing
artifact means q8 is never auto-selected. An EXACT backend-name pin
(``cfg.backend == "pallas_fused_q8"``) bypasses both gates (explicit
opt-in, e.g. the parity tests and the calibration benchmark itself). On
top of that their static costs sit above ``UNCALIBRATED_GATE_COST``:
measured-only backends, picked by ``auto`` only where a calibration shows
them faster per shape.

(*) ``pallas_sharded`` carries a per-op static cost (``cost`` for
sequence work, ``decode_cost`` for decode): under a mesh it is the
statically PREFERRED sequence backend (the fused shard kernels beat the
XLA scan between the same collectives), while its decode — like
``sharded_decode`` — stays statically dispreferred behind the replicated
single-host backends until a calibration measures it faster per shape.

* ``mask``: a (B, T) length mask streams through the backend (bucketed
  left-padded prefill stays bitwise-identical to unpadded — every sequence
  backend here claims ``mask_exact``). Decode steps carry no time axis, so
  the column does not apply to ``sharded_decode``.
* ``hetero``: heterogeneous ``cfg.layer_dims`` (the fused kernel needs one
  uniform VMEM block shape; the chain runs one kernel per layer instead of
  raising or silently degrading).
* ``mesh`` = REQ: the backend *requires* a mesh. Providing a mesh is an
  explicit request to use it for SEQUENCE work (shard_map backends win
  outright). Decode is latency-bound: by static cost it stays on a
  replicated single-host backend (per-step collectives usually dominate),
  but ``sharded_decode`` (one persistent shard_map step over pre-sharded
  weights) is a full candidate — a calibration file that measures it
  faster flips the choice per shape.

Dispatch: ``cfg.backend`` is a preference — ``"xla"`` (default) and
``"pallas"`` pin their family when legal, an exact backend name (e.g.
``"pallas_chain"``, ``"sharded_decode"``) pins that one backend, and
``"auto"`` picks purely by cost: measured (CostModel) when available for
every legal candidate, else the static table. An illegal preference falls
through to the cheapest legal backend — never an error as long as ANY
backend can serve the call.

Cost calibration: ``benchmarks/decode_latency.py --emit-costs`` writes
``BENCH_backend_costs.json``; :func:`load_cost_model` /
:func:`set_cost_model` install it (or it is picked up automatically from
``$REPRO_GRU_COSTS`` / ``./BENCH_backend_costs.json``). A missing or
corrupt file, or one calibrated on another platform, degrades to the
static table — selection is then identical to the pre-CostModel executor.

Legacy surface: ``plan()`` (one-shot resolve) and the ``ExecPlan`` name
are deprecated shims over ``compile()``/``GRUExecutable`` — same memoized
objects, bitwise-equal results, one DeprecationWarning per process.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import math
import os
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import GRUConfig
from repro.core import cells as cell_families
from repro.core import gru as gru_core
from repro.core.cells import UnknownCellFamily  # noqa: F401 (re-export)
from repro.core.params import QuantStackParams, quantize_gru_cells


# ---------------------------------------------------------------------------
# placement: WHERE a stack runs (resolved at compile/prepare time)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Placement:
    """Where weights live and execution happens.

    ``mesh=None`` is the host placement (single-device, replicated).
    With a mesh, ``axis`` names the mesh axis the sharded backends
    partition over (U output rows for rowwise layers, the contraction dim
    for cascade layers — the rule itself is per-layer via
    ``cfg.layer_matvec_modes``). Hashable: it is part of the executable
    cache key, so distinct meshes compile distinct executables.
    """
    mesh: object = None
    axis: str = "model"

    @property
    def is_host(self) -> bool:
        return self.mesh is None


HOST = Placement()


def _as_placement(p) -> Placement:
    """Normalize None | Mesh | Placement -> Placement."""
    if p is None:
        return HOST
    if isinstance(p, Placement):
        return p
    return Placement(mesh=p)


# ---------------------------------------------------------------------------
# backend registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Capabilities:
    """What a backend can legally execute (checked by ``compile()``)."""
    supports_mask: bool = False      # (B,T) length mask streams through
    supports_hetero_dims: bool = False   # per-layer hidden sizes may differ
    supports_mesh: bool = False      # True = REQUIRES a mesh (shard_map)
    return_all: bool = False         # can emit the last layer's full sequence
    decode: bool = False             # single-step serve path
    sequence: bool = True            # whole-sequence / prefill path
    mask_exact: bool = True          # masked+padded == unpadded, bitwise


@dataclasses.dataclass(frozen=True)
class BackendSpec:
    """One registered execution strategy.

    ``sequence_fn(sp, h0s, xs, *, cfg, return_all, mask, placement)``
    returns ``(flat per-layer finals tuple, last-layer states | None)``;
    ``decode_fn(sp, hs, x, *, cfg, placement)`` returns the flat new
    state tuple (see the family's state layout in ``repro.core.cells``).
    ``family`` names the :class:`repro.core.cells.CellFamily` this backend
    serves — the registry key is ``(family, name)``, so each family owns
    its own ``xla``/``pallas_fused``/... namespace. ``cost`` is the STATIC
    relative dispatch hint (lower = preferred), used whenever no measured
    cost covers the call; ``decode_cost`` optionally overrides it for
    decode selection (a backend may be the cheapest way to run a sequence
    yet the wrong default for a single latency-bound step —
    ``pallas_sharded``).
    """
    name: str
    caps: Capabilities
    cost: int
    sequence_fn: Optional[Callable] = None
    decode_fn: Optional[Callable] = None
    decode_cost: Optional[int] = None
    family: str = "gru"

    def static_cost(self, op: str) -> int:
        if op == "decode" and self.decode_cost is not None:
            return self.decode_cost
        return self.cost


_REGISTRY: Dict[Tuple[str, str], BackendSpec] = {}


def register_backend(spec: BackendSpec) -> None:
    _REGISTRY[(spec.family, spec.name)] = spec


def backends(family: str = "gru") -> Dict[str, BackendSpec]:
    """Snapshot of one family's registry (name -> spec), for
    introspection/tests. Defaults to the GRU family (the pre-registry
    call sites all meant that)."""
    _ensure_backends()
    return {name: spec for (fam, name), spec in _REGISTRY.items()
            if fam == family}


def _ensure_backends() -> None:
    """Make sure every family's kernels package had a chance to register
    its backends (they do so on import; compile() imports them on first
    use otherwise, so dispatch never depends on import order)."""
    if ("gru", "pallas_fused") not in _REGISTRY:
        from repro.kernels.gru_sequence import ops as seq_ops
        seq_ops.register_runtime_backends()
    if ("slstm", "xla") not in _REGISTRY:
        from repro.core import slstm as slstm_core
        slstm_core.register_runtime_backends()
    if ("slstm", "pallas_fused") not in _REGISTRY:
        from repro.kernels.slstm_cell import ops as slstm_ops
        slstm_ops.register_runtime_backends()


# ---------------------------------------------------------------------------
# canonical params: StackParams + prepare()
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class StackParams:
    """Canonical recurrent-stack parameters: the ONE layout every backend
    takes (any cell family — the gate width of ``w``/``u``/``b`` is the
    family's business).

    ``cells``: per-layer ``{"w","u","b"}`` dicts, layer 0 first.
    ``stacked``: the fused kernels' precomputed device-side weight stacks
    (``{"u","w_deep","b"}``) — present for uniform hidden sizes, ``None``
    for heterogeneous stacks (the fused backend doesn't apply there).
    ``placed``: the sharded backends' per-layer gate-major weight views,
    ``device_put`` onto ``placement.mesh`` up front — present only for a
    mesh placement. ``quant``: the q8 backends' int8 weight views
    (:class:`repro.core.params.QuantStackParams`) — present when the
    config requests quantization (``cfg.quant`` / a ``*_q8`` backend pin);
    scale computation and int8 casting happen HERE, never in a traced
    execute call. ``placement`` (aux data) records where ``placed``
    lives, so a matching ``prepare()`` is a free passthrough.
    """
    cells: tuple
    stacked: Optional[dict] = None
    placed: Optional[tuple] = None
    quant: Optional[QuantStackParams] = None
    placement: Placement = HOST

    def tree_flatten(self):
        return (self.cells, self.stacked, self.placed, self.quant), \
            (self.placement,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, placement=aux[0])

    @property
    def dims(self) -> Tuple[int, ...]:
        return tuple(c["u"].shape[0] for c in self.cells)


def _cfg_wants_quant(cfg) -> bool:
    """Whether this config's execution may route through a q8 backend
    (quant flag or an exact ``*_q8`` pin) — if so, prepare() builds the
    int8 views up front so no traced call quantizes weights."""
    return (getattr(cfg, "quant", "") == "int8"
            or str(getattr(cfg, "backend", "")).endswith("_q8"))


def prepare(params, cfg: GRUConfig, placement=None, *,
            want_stacked: bool = True,
            want_quant: Optional[bool] = None) -> StackParams:
    """One-time normalization of ANY accepted param layout to a
    placement-resident StackParams.

    Subsumes ``stack_cell_params`` (layout normalization),
    ``prepare_stacked_cells`` (fused-kernel weight stacking) and the model
    API's ``prepare_params`` (serving prep). Accepts ``StackParams``
    (passthrough; upgraded in place-of if the placement changed),
    ``{"cells": ...}``, ``{"cell": ...}``, a bare ``{w,u,b}`` cell, a
    per-layer sequence, and dicts already carrying a precomputed
    ``"stacked_cells"`` entry (reused, not recomputed). Do this ONCE
    outside the per-step jit so decode traces never restack weights.

    ``placement`` (a :class:`Placement`, a raw mesh, or None = host):
    with a mesh, ALL device placement happens here — the sharded backends'
    per-layer gate-major reshapes and ``device_put``s run now, so a traced
    execute call contains no weight placement (asserted by the test
    suite via jaxpr inspection). ``want_stacked=False`` skips the fused
    kernels' weight stacks (an executable whose resolved backends never
    read them passes it). ``want_quant`` (default: derived from
    ``cfg.quant`` / a ``*_q8`` backend pin) additionally builds the q8
    backends' int8 weight views — scale computation, rounding, and int8
    casting are placement-stage costs exactly like the reshapes, so a
    traced execute call contains no quantize ops either (jaxpr-asserted).

    Family-aware: ``cfg.family`` picks the :class:`~repro.core.cells.
    CellFamily` whose ``normalize``/``stacked_views`` hooks build the
    views, and the quant/sharded views are built only for families that
    support them (``supports_quant`` / ``supports_placement`` — GRU
    today). For ``family="gru"`` every view is built by exactly the same
    code as before the registry, so prepared params are bitwise-equal.
    """
    pl_ = _as_placement(placement)
    family = cell_families.get_family(cell_families.cfg_family(cfg))
    if not family.supports_placement:
        pl_ = HOST                       # no sharded views for this family
    if want_quant is None:
        want_quant = _cfg_wants_quant(cfg)
    want_quant = want_quant and family.supports_quant
    if isinstance(params, StackParams):
        quant = params.quant
        if want_quant and quant is None:
            quant = quantize_gru_cells(params.cells)
        if pl_.is_host or params.placement == pl_:
            if quant is params.quant:
                return params
            return StackParams(cells=params.cells, stacked=params.stacked,
                               placed=params.placed, quant=quant,
                               placement=params.placement)
        placed = _place_layers(params.cells, cfg, pl_)
        return StackParams(cells=params.cells, stacked=params.stacked,
                           placed=placed, quant=quant, placement=pl_)
    stacked = params.get("stacked_cells") if isinstance(params, dict) else None
    placed = params.get("placed_cells") if isinstance(params, dict) else None
    quant = params.get("quant_cells") if isinstance(params, dict) else None
    cells = family.normalize(params, cfg)
    dims = tuple(c["u"].shape[0] for c in cells)
    if (want_stacked and stacked is None and family.stacked_views is not None
            and all(d == dims[0] for d in dims)):
        stacked = family.stacked_views(cells)
    if want_quant and quant is None:
        quant = quantize_gru_cells(cells)
    if pl_.is_host:
        placed = None
    else:
        if placed is not None and not _placed_on(placed, pl_):
            placed = None                # stale views from another mesh
        if placed is None:
            # no pre-placed views for THIS mesh: place now (traced callers
            # pay this per call — the cost the compile/execute split moves
            # into prepare())
            placed = _place_layers(cells, cfg, pl_)
    return StackParams(cells=cells, stacked=stacked, placed=placed,
                       quant=quant, placement=HOST if pl_.is_host else pl_)


def _place_layers(cells, cfg: GRUConfig, pl_: Placement) -> tuple:
    from repro.core import rowparallel
    return rowparallel.prepare_sharded_layers(cells, cfg, mesh=pl_.mesh,
                                              axis=pl_.axis)


def _placed_on(placed, pl_: Placement) -> bool:
    """Best-effort check that pre-placed views actually live on this
    placement's mesh, so a dict prepared for mesh A is not fed into a
    shard_map over mesh B (which would silently re-transfer the weights
    inside the traced call). Concrete arrays expose their committed
    NamedSharding; tracers (an already-traced hot path) are trusted."""
    try:
        arr = next(iter(placed[0].values()))
        sh = arr.sharding
    except Exception:  # noqa: BLE001 - tracer or exotic layout: trust it
        return True
    from jax.sharding import NamedSharding
    if isinstance(sh, NamedSharding):
        return sh.mesh == pl_.mesh
    return True


# ---------------------------------------------------------------------------
# measured cost model (static table fallback)
# ---------------------------------------------------------------------------

class CostModel:
    """Measured per-backend latency, keyed (family, backend, op, depth,
    hidden) with linear interpolation over batch.

    Loaded from the ``BENCH_backend_costs.json`` artifact that
    ``benchmarks/decode_latency.py --emit-costs`` writes. Entries without
    a ``"family"`` column default to ``"gru"``, so pre-registry
    calibration artifacts keep loading and pricing exactly the same rows.
    Lookups outside the measured batch range clamp to the nearest measured
    batch (the relative backend order at the edge is the best available
    signal). ``lookup`` returns None for any bucket with no measurements;
    selection only trusts the model when EVERY legal candidate is covered
    (µs and static preference ints are not comparable units).
    """

    def __init__(self, table: Dict[tuple, List[tuple]], source: str = "",
                 error: Optional[str] = None):
        # accept legacy 4-tuple keys (backend, op, depth, hidden) — they
        # belong to the GRU family, same as artifact rows without a
        # "family" column
        self._table = {(k if len(k) == 5 else ("gru", *k)): v
                       for k, v in table.items()}
        self.source = source
        self.error = error

    def __len__(self) -> int:
        return sum(len(v) for v in self._table.values())

    @classmethod
    def from_entries(cls, entries, source: str = "") -> "CostModel":
        table: Dict[tuple, List[tuple]] = {}
        for e in entries:
            key = (str(e.get("family", "gru")), str(e["backend"]),
                   str(e.get("op", "decode")),
                   int(e["depth"]), int(e["hidden_dim"]))
            table.setdefault(key, []).append(
                (int(e["batch"]), float(e["p50_us"])))
        for v in table.values():
            v.sort()
        return cls(table, source=source)

    @classmethod
    def load(cls, path) -> "CostModel":
        """Tolerant load: a missing, unreadable, or schema-mismatched file
        yields an EMPTY model (every lookup misses -> static fallback).
        So does an artifact measured on another platform than the one
        this process runs on (its ``"device"``, as the calibration
        benchmark records ``jax.default_backend()``): CPU interpret-mode
        timings must never steer dispatch on a TPU, nor the reverse."""
        try:
            with open(path) as f:
                data = json.load(f)
            if data.get("bench") != "gru_backend_costs":
                raise ValueError("not a gru_backend_costs artifact")
            here = jax.default_backend()
            if data.get("device") != here:
                raise ValueError(f"calibrated on {data.get('device')!r}, "
                                 f"this process runs on {here!r}")
            return cls.from_entries(data["entries"], source=str(path))
        except Exception as e:  # noqa: BLE001 - degrade, never break dispatch
            return cls({}, source=str(path),
                       error=f"{type(e).__name__}: {e}")

    def merged(self, entries, source: str = "") -> "CostModel":
        """A NEW model: this model's table with ``entries`` folded in.

        The online-recalibration API (``repro.serve.autotune``): served
        per-step timings come back as calibration rows and REPLACE any
        existing measured point at the same (family, backend, op, depth,
        hidden, batch) — fresher measurements win; batches never measured
        before extend the curve. Malformed rows and non-finite or
        non-positive latencies are skipped (a ManualClock serving run
        measures dt == 0, which must never poison the table with
        "free" backends).

        Pure: ``self`` is untouched. Install the result via
        :func:`set_cost_model`, which bumps the cost epoch and evicts the
        executable cache — the epoch is part of every cache key, so plans
        priced under the old table are unreachable afterwards (see
        docs/runtime.md, "Recalibration and cost epochs").
        """
        table = {k: list(v) for k, v in self._table.items()}
        for e in entries:
            try:
                key = (str(e.get("family", "gru")), str(e["backend"]),
                       str(e.get("op", "decode")),
                       int(e["depth"]), int(e["hidden_dim"]))
                batch = int(e["batch"])
                us = float(e["p50_us"])
            except (KeyError, TypeError, ValueError):
                continue
            if batch < 1 or not math.isfinite(us) or us <= 0.0:
                continue
            pts = table.setdefault(key, [])
            pts[:] = [(b, c) for (b, c) in pts if b != batch]
            pts.append((batch, us))
            pts.sort()
        return CostModel(table,
                         source=source or (f"{self.source}+online"
                                           if self.source else "<online>"))

    def batch_points(self, backend: str, op: str = "decode", *, depth: int,
                     hidden: int, family: str = "gru") -> List[tuple]:
        """The raw measured ``(batch, p50_us)`` points of one curve,
        sorted by batch. This is the autotuner's view of the
        batch-latency curve: :meth:`lookup` clamps and interpolates,
        which would fabricate a flat marginal cost outside the measured
        range — wave-size selection needs to know where the measurements
        actually end."""
        return list(self._table.get((str(family), str(backend), str(op),
                                     int(depth), int(hidden)), ()))

    def lookup(self, backend: str, op: str, *, depth: int, batch: int,
               hidden: int, family: str = "gru") -> Optional[float]:
        pts = self._table.get((str(family), backend, op, int(depth),
                               int(hidden)))
        if not pts:
            return None
        if batch <= pts[0][0]:
            return pts[0][1]
        if batch >= pts[-1][0]:
            return pts[-1][1]
        for (b0, c0), (b1, c1) in zip(pts, pts[1:]):
            if b0 <= batch <= b1:
                return c0 + (batch - b0) / (b1 - b0) * (c1 - c0)
        return None  # pragma: no cover - unreachable on a sorted table


_COST_MODEL: Optional[CostModel] = None
_COST_MODEL_LOADED = False
_COST_EPOCH = 0  # part of the executable cache key: new model, new plans


def set_cost_model(model: Optional[CostModel]) -> None:
    """Install a calibration model (None re-arms the lazy default load).
    Bumps the cost epoch, so already-memoized executables are not reused
    with stale costs — and evicts them: keys from older epochs can never
    be returned again, so keeping them would only leak in a long-lived
    server that periodically reloads calibration."""
    global _COST_MODEL, _COST_MODEL_LOADED, _COST_EPOCH
    _COST_MODEL = model
    _COST_MODEL_LOADED = model is not None
    _COST_EPOCH += 1
    _EXEC_CACHE.clear()


def load_cost_model(path) -> CostModel:
    """Load ``path`` (tolerantly) and install it. Returns the model."""
    model = CostModel.load(path)
    set_cost_model(model)
    return model


def cost_epoch() -> int:
    """The current cost/gate epoch. Part of every executable cache key:
    :func:`set_cost_model` and :func:`set_quant_accuracy` bump it (and
    evict the cache), so executables priced under an older table or gate
    state are unreachable afterwards. Observability for the online
    recalibration loop (``repro.serve.autotune``) and its tests."""
    return _COST_EPOCH


def cost_model() -> CostModel:
    """The active calibration model. On first use, loads
    ``$REPRO_GRU_COSTS`` (default ``./BENCH_backend_costs.json``) if
    present; otherwise an empty model (pure static dispatch)."""
    global _COST_MODEL, _COST_MODEL_LOADED
    if not _COST_MODEL_LOADED:
        path = os.environ.get("REPRO_GRU_COSTS", "BENCH_backend_costs.json")
        _COST_MODEL = (CostModel.load(path) if os.path.exists(path)
                       else CostModel({}, source=path))
        _COST_MODEL_LOADED = True
    return _COST_MODEL


# ---------------------------------------------------------------------------
# quant accuracy gate (the q8 backends' dispatch-eligibility record)
# ---------------------------------------------------------------------------

class QuantAccuracy:
    """The recorded result of the q8 accuracy harness
    (``python -m repro.quant.accuracy`` -> ``BENCH_quant_accuracy.json``):
    max/mean logit error vs the f32 oracle and classification parity on
    the jet-tagging eval set. Gates q8 auto-dispatch: only a loaded,
    error-free artifact with ``passed: true`` opens the gate — a missing,
    corrupt, or failing artifact means ``auto`` never selects a ``*_q8``
    backend (exact-name pins still work: explicit opt-in)."""

    def __init__(self, data: Optional[dict] = None, source: str = "",
                 error: Optional[str] = None):
        self.data = dict(data or {})
        self.source = source
        self.error = error

    @property
    def passed(self) -> bool:
        return self.error is None and bool(self.data.get("passed"))

    @classmethod
    def load(cls, path) -> "QuantAccuracy":
        """Tolerant load: a missing, unreadable, or schema-mismatched file
        yields a CLOSED gate (q8 stays pin-only), never an exception."""
        try:
            with open(path) as f:
                data = json.load(f)
            if data.get("bench") != "gru_quant_accuracy":
                raise ValueError("not a gru_quant_accuracy artifact")
            return cls(data, source=str(path))
        except Exception as e:  # noqa: BLE001 - degrade, never break dispatch
            return cls({}, source=str(path), error=f"{type(e).__name__}: {e}")


_QUANT_ACC: Optional[QuantAccuracy] = None
_QUANT_ACC_LOADED = False


def set_quant_accuracy(report: Optional[QuantAccuracy]) -> None:
    """Install an accuracy report (None re-arms the lazy default load).
    Bumps the cost epoch like :func:`set_cost_model`: gate flips change
    which backends are legal, so memoized executables must not outlive
    them."""
    global _QUANT_ACC, _QUANT_ACC_LOADED, _COST_EPOCH
    _QUANT_ACC = report
    _QUANT_ACC_LOADED = report is not None
    _COST_EPOCH += 1
    _EXEC_CACHE.clear()


def load_quant_accuracy(path) -> QuantAccuracy:
    """Load ``path`` (tolerantly) and install it. Returns the report."""
    report = QuantAccuracy.load(path)
    set_quant_accuracy(report)
    return report


def quant_accuracy() -> QuantAccuracy:
    """The active accuracy report. On first use, loads
    ``$REPRO_GRU_QUANT_ACC`` (default ``./BENCH_quant_accuracy.json``) if
    present; otherwise a closed gate."""
    global _QUANT_ACC, _QUANT_ACC_LOADED
    if not _QUANT_ACC_LOADED:
        path = os.environ.get("REPRO_GRU_QUANT_ACC",
                              "BENCH_quant_accuracy.json")
        _QUANT_ACC = (QuantAccuracy.load(path) if os.path.exists(path)
                      else QuantAccuracy({}, source=path,
                                         error="missing artifact"))
        _QUANT_ACC_LOADED = True
    return _QUANT_ACC


def quant_gate_open() -> bool:
    """True when the recorded accuracy artifact admits q8 auto-dispatch."""
    return quant_accuracy().passed


def backend_dtype(name: Optional[str]) -> str:
    """The numeric format a backend's recurrent matvecs run in — what a
    server reports as its served dtype."""
    return "int8" if name and name.endswith("_q8") else "float32"


# ---------------------------------------------------------------------------
# built-in backends: xla scan + sharded shard_map programs
# ---------------------------------------------------------------------------

def _xla_sequence(sp, h0s, xs, *, cfg, return_all, mask, placement):
    return gru_core.gru_stack_sequence_xla(sp.cells, h0s, xs, cfg=cfg,
                                           return_all=return_all, mask=mask)


def _xla_decode(sp, hs, x, *, cfg, placement):
    return gru_core.gru_stack_decode_xla(sp.cells, hs, x, cfg=cfg)


def _sharded_sequence(sp, h0s, xs, *, cfg, return_all, mask, placement,
                      step_impl: str = "xla"):
    """The shard_map sequence program; ``step_impl="pallas"`` is the
    `pallas_sharded` backend — same placed weight views, same collectives,
    per-shard step bodies swapped for the Pallas shard kernels
    (bitwise-equal to `sharded` at identical shard shapes)."""
    from repro.core import rowparallel
    sp = prepare(sp, cfg, placement, want_stacked=False)
    out = rowparallel.gru_stack_sequence_sharded_prepared(
        sp.placed, h0s, xs, mesh=placement.mesh, cfg=cfg,
        axis=placement.axis, return_all=return_all, mask=mask,
        step_impl=step_impl)
    if return_all:
        return out
    return out, None


def _sharded_decode(sp, hs, x, *, cfg, placement, step_impl: str = "xla"):
    from repro.core import rowparallel
    sp = prepare(sp, cfg, placement, want_stacked=False)
    return rowparallel.gru_stack_decode_sharded_prepared(
        sp.placed, hs, x, mesh=placement.mesh, cfg=cfg, axis=placement.axis,
        step_impl=step_impl)


_pallas_sharded_sequence = functools.partial(_sharded_sequence,
                                             step_impl="pallas")
_pallas_sharded_decode = functools.partial(_sharded_decode,
                                           step_impl="pallas")


register_backend(BackendSpec(
    name="xla",
    caps=Capabilities(supports_mask=True, supports_hetero_dims=True,
                      supports_mesh=False, return_all=True, decode=True,
                      sequence=True),
    cost=30,
    sequence_fn=_xla_sequence, decode_fn=_xla_decode))

register_backend(BackendSpec(
    name="sharded",
    caps=Capabilities(supports_mask=True, supports_hetero_dims=True,
                      supports_mesh=True, return_all=True, decode=False,
                      sequence=True),
    cost=5,
    sequence_fn=_sharded_sequence, decode_fn=None))

register_backend(BackendSpec(
    name="pallas_sharded",
    caps=Capabilities(supports_mask=True, supports_hetero_dims=True,
                      supports_mesh=True, return_all=True, decode=True,
                      sequence=True),
    # statically the PREFERRED mesh sequence backend (cost 4 < sharded's
    # 5): between the same collectives, the per-shard compute runs as
    # fused whole-block kernels instead of an XLA op soup. Its decode is
    # per-op dispreferred (decode_cost) for the same reason sharded_decode
    # is: one recurrent step is latency-bound and its collectives usually
    # dominate, so replicated decode wins unless a calibration measures
    # the kernel-in-shard_map step faster at this shape.
    cost=4, decode_cost=190,
    sequence_fn=_pallas_sharded_sequence, decode_fn=_pallas_sharded_decode))

register_backend(BackendSpec(
    name="sharded_decode",
    caps=Capabilities(supports_mask=False, supports_hetero_dims=True,
                      supports_mesh=True, return_all=False, decode=True,
                      sequence=False),
    # statically DISpreferred: one recurrent step is latency-bound and its
    # per-step collectives usually dominate — replicated decode wins unless
    # a calibration file MEASURES the sharded step faster at this shape.
    cost=200,
    sequence_fn=None, decode_fn=_sharded_decode))


# ---------------------------------------------------------------------------
# compile(): capability filtering + (measured | static) cost choice
# ---------------------------------------------------------------------------

class NoCapableBackend(ValueError):
    """No registered backend can legally serve the requested call."""


@dataclasses.dataclass(frozen=True, eq=False)
class GRUExecutable:
    """A compiled GRU workload: resolved placement + backends + jit-stable
    callables.

    ``sequence(params, h0s, xs, *, return_all=False, mask=None)`` returns
    ``(per-layer finals, last-layer states | None)``; ``prefill`` is the
    finals-only view of the same backend; ``decode(params, hs, x)`` returns
    the per-layer new states. ``params`` may be any layout ``prepare``
    accepts — pass ``executable.prepare(params)`` output on hot paths so
    the traced calls are pure compute against placement-resident weights.
    ``cost_source`` records whether backend choice came from measured
    calibration (``"measured"``) or the static table (``"static"``).
    """
    cfg: GRUConfig
    batch: Optional[int]
    seq: Optional[int]
    masked: bool
    placement: Placement
    mode: str
    sequence_backend: Optional[str]
    decode_backend: Optional[str]
    mask_exact: bool
    cost_source: str = "static"
    sequence: Callable = dataclasses.field(repr=False, default=None)
    prefill: Callable = dataclasses.field(repr=False, default=None)
    decode: Callable = dataclasses.field(repr=False, default=None)

    @property
    def mesh(self):
        return self.placement.mesh

    def prepare(self, params) -> StackParams:
        """Placement-resident params for THIS executable: device placement
        and weight stacking happen now, never inside the traced calls."""
        fam = cell_families.cfg_family(self.cfg)
        names = {self.sequence_backend, self.decode_backend}
        needs_mesh = any(s is not None and s.caps.supports_mesh
                         for s in (_REGISTRY.get((fam, n))
                                   for n in names if n))
        return prepare(params, self.cfg,
                       self.placement if needs_mesh else None,
                       want_stacked="pallas_fused" in names,
                       want_quant=any(n and n.endswith("_q8")
                                      for n in names))

    def describe(self) -> dict:
        return {"sequence_backend": self.sequence_backend,
                "decode_backend": self.decode_backend,
                "masked": self.masked, "mask_exact": self.mask_exact,
                "mesh": self.placement.mesh is not None,
                "axis": self.placement.axis, "mode": self.mode,
                "batch": self.batch, "seq": self.seq,
                "cost_source": self.cost_source}


def _hetero(cfg: GRUConfig) -> bool:
    dims = cfg.resolved_layer_dims
    return any(d != dims[0] for d in dims)


def _legal(spec: BackendSpec, *, op: str, masked: bool, hetero: bool,
           mesh, need_return_all: bool = False,
           cfg: Optional[GRUConfig] = None) -> bool:
    c = spec.caps
    if spec.name.endswith("_q8"):
        # the q8 datapath changes numerics: candidate only when the config
        # asked for it AND the accuracy artifact passed — or under an
        # exact-name pin (explicit opt-in bypasses both gates).
        if getattr(cfg, "backend", None) != spec.name:
            if getattr(cfg, "quant", "") != "int8" or not quant_gate_open():
                return False
    if op == "decode":
        if not c.decode or spec.decode_fn is None:
            return False
    else:
        if not c.sequence or spec.sequence_fn is None:
            return False
        if masked and not c.supports_mask:
            return False
        if need_return_all and not c.return_all:
            return False
    if hetero and not c.supports_hetero_dims:
        return False
    if c.supports_mesh and mesh is None:
        return False                      # shard_map backends need a mesh
    return True


# Static costs at or above this line mark a backend "measured-only": it is
# DEFINED to lose dispatch unless a calibration measures it faster, so a
# cost model that does not cover it (e.g. a q8 calibration that only ran
# the decode op) does not force the whole selection back to the static
# table. Candidates below the line keep PR 5's all-or-nothing contract —
# measured µs and static preference ints are not comparable units.
UNCALIBRATED_GATE_COST = 100


def _measured_costs(legal, cfg: GRUConfig, *, op: str,
                    batch: Optional[int]) -> Optional[Dict[str, float]]:
    """Measured µs per candidate, or None when the model cannot cover the
    call (unknown batch, heterogeneous dims, or an uncovered candidate —
    except measured-only candidates (static cost >=
    :data:`UNCALIBRATED_GATE_COST`), which are tolerated as uncovered and
    simply lose: per-op calibrations, like a q8 decode-only run, must not
    degrade every OTHER backend's measured dispatch to static)."""
    if batch is None or _hetero(cfg):
        return None
    model = cost_model()
    if not len(model):
        return None
    dims = cfg.resolved_layer_dims
    fam = cell_families.cfg_family(cfg)
    out = {}
    covered = 0
    for s in legal:
        us = model.lookup(s.name, op, depth=len(dims), batch=batch,
                          hidden=dims[0], family=fam)
        if us is None:
            if s.static_cost(op) >= UNCALIBRATED_GATE_COST:
                out[s.name] = float("inf")   # measured-only, unmeasured here
                continue
            return None
        covered += 1
        out[s.name] = us
    if not covered:
        return None                          # nothing actually measured
    return out


def _rank(spec: BackendSpec, cfg: GRUConfig, *, op: str, mesh,
          measured: Optional[float]) -> tuple:
    """Selection key, lexicographic: platform legality > mesh request
    (sequence ops: a provided mesh is an explicit ask for shard_map) >
    ``cfg.backend`` preference (family or exact name) > cost (measured µs
    when available, else the static table) > name (determinism)."""
    plat = 0
    if spec.name.startswith("pallas") and jax.default_backend() not in (
            "cpu", "tpu"):
        # the Pallas kernels target TPU (pltpu VMEM scratch) and run
        # interpret-mode on CPU; on any other platform they cannot lower,
        # so dispatch must never pick them over the XLA scan.
        plat = 1
    mesh_rank = 0
    if mesh is not None and op != "decode":
        mesh_rank = 0 if spec.caps.supports_mesh else 1
    pref = getattr(cfg, "backend", "xla")
    fam = 1
    if pref == spec.name:
        fam = 0                          # exact backend-name pin
    elif pref == "xla" and spec.name == "xla":
        fam = 0
    elif pref == "pallas" and spec.name.startswith("pallas"):
        fam = 0
    cost = float(spec.static_cost(op)) if measured is None else measured
    return (plat, mesh_rank, fam, cost, spec.name)


def _select(op: str, cfg: GRUConfig, *, masked: bool, placement: Placement,
            batch: Optional[int] = None,
            need_return_all: bool = False):
    """-> (winning spec | None, "measured" | "static"). Candidates are
    the requested family's backends only — families never cross."""
    hetero = _hetero(cfg)
    mesh = placement.mesh
    fam = cell_families.cfg_family(cfg)
    legal = [s for s in _REGISTRY.values()
             if s.family == fam
             and _legal(s, op=op, masked=masked, hetero=hetero, mesh=mesh,
                        need_return_all=need_return_all, cfg=cfg)]
    if not legal:
        return None, "static"
    measured = _measured_costs(legal, cfg, op=op, batch=batch)
    spec = min(legal, key=lambda s: _rank(
        s, cfg, op=op, mesh=mesh,
        measured=None if measured is None else measured[s.name]))
    return spec, ("measured" if measured is not None else "static")


_EXEC_CACHE: Dict[tuple, GRUExecutable] = {}

# Every backend call traces under this matmul precision: the cell-family
# configs are float32 end to end, and on a TPU an f32 matmul at default
# precision takes one bfloat16 pass, in XLA and in a Pallas kernel alike
# (on a TPU v5e that put served states 5e-3 to 2.4e-2 off a float32
# reference; at "highest" the fused kernels matched it exactly). It
# reaches every matmul a backend traces — the layer-0 input GEMM, the XLA
# scan's matvecs, the shard bodies and the kernels' own dots. The CPU
# computes float32 either way.
MATMUL_PRECISION = "highest"


def readout(h, head: dict):
    """Classifier head of every cell-family model: h (..., H) -> logits,
    at the same :data:`MATMUL_PRECISION` as the recurrence it reads."""
    return jnp.matmul(h, head["w"], precision=MATMUL_PRECISION) + head["b"]


def compile(cfg: GRUConfig, *, batch: Optional[int] = None,
            seq: Optional[int] = None, placement=None, mask: bool = False,
            mode: str = "serve") -> GRUExecutable:
    """Ahead-of-time resolve: the fastest legal backend(s) for a GRU
    workload at these shapes, on this placement.

    ``placement``: a :class:`Placement`, a raw mesh (wrapped with the
    default axis), or None (host). ``mask`` declares whether calls will
    carry a (B, T) length mask (the array itself is a run-time argument).
    ``mode``: ``"prefill"`` / ``"sequence"`` require a sequence backend,
    ``"decode"`` a decode backend, ``"serve"`` both. Executables are
    memoized — the same key (cfg, shapes, placement, cost epoch) returns
    the SAME object, so its callables are stable across calls and jit
    caches keyed on them never retrace; distinct placements (e.g. two
    different meshes) compile distinct executables.

    ``cfg.family`` selects the cell family's backend namespace; an
    unregistered family raises the typed
    :class:`~repro.core.cells.UnknownCellFamily` (never a silent
    degrade to another family's backends).
    """
    _ensure_backends()
    cell_families.get_family(cell_families.cfg_family(cfg))  # typed check
    pl_ = _as_placement(placement)
    masked = bool(mask)
    key = (cfg, batch, seq, pl_, masked, mode, _COST_EPOCH)
    hit = _EXEC_CACHE.get(key)
    if hit is not None:
        return hit

    seq_spec, seq_src = _select("sequence", cfg, masked=masked,
                                placement=pl_, batch=batch)
    # a finals-only backend may win the primary selection; return_all=True
    # calls then fall through to the cheapest fully-capable backend instead
    # of failing inside the backend (the silent-capability-gap failure mode
    # this module exists to eliminate). Both specs are fixed at compile
    # time, so the callables stay jit-stable.
    if seq_spec is not None and seq_spec.caps.return_all:
        seq_spec_ra = seq_spec
    else:
        seq_spec_ra, _ = _select("sequence", cfg, masked=masked,
                                 placement=pl_, batch=batch,
                                 need_return_all=True)
    dec_spec, dec_src = _select("decode", cfg, masked=False, placement=pl_,
                                batch=batch)
    if mode in ("prefill", "sequence", "serve") and seq_spec is None:
        raise NoCapableBackend(
            f"no sequence backend for family="
            f"{cell_families.cfg_family(cfg)!r} cfg.backend={cfg.backend!r} "
            f"mask={mask} dims={cfg.resolved_layer_dims} mesh={pl_.mesh}")
    if mode in ("decode", "serve") and dec_spec is None:
        raise NoCapableBackend(
            f"no decode backend for family="
            f"{cell_families.cfg_family(cfg)!r} cfg.backend={cfg.backend!r} "
            f"dims={cfg.resolved_layer_dims}")

    def run_sequence(params, h0s, xs, *, return_all=False, mask=None):
        if mask is not None and not masked:
            raise ValueError("executable was compiled with mask=False; "
                             "re-compile with mask=True to pass a length "
                             "mask")
        spec = seq_spec if not return_all else seq_spec_ra
        if spec is None:
            raise NoCapableBackend(
                f"no return_all-capable sequence backend for "
                f"cfg.backend={cfg.backend!r} mask={mask is not None} "
                f"dims={cfg.resolved_layer_dims} mesh={pl_.mesh}")
        sp = prepare(params, cfg,
                     pl_ if spec.caps.supports_mesh else None,
                     want_stacked=spec.name == "pallas_fused",
                     want_quant=spec.name.endswith("_q8"))
        with jax.default_matmul_precision(MATMUL_PRECISION):
            return spec.sequence_fn(sp, tuple(h0s), xs, cfg=cfg,
                                    return_all=return_all, mask=mask,
                                    placement=pl_)

    def run_prefill(params, h0s, xs, *, mask=None):
        return run_sequence(params, h0s, xs, mask=mask)[0]

    def run_decode(params, hs, x):
        sp = prepare(params, cfg,
                     pl_ if dec_spec.caps.supports_mesh else None,
                     want_stacked=dec_spec.name == "pallas_fused",
                     want_quant=dec_spec.name.endswith("_q8"))
        with jax.default_matmul_precision(MATMUL_PRECISION):
            return dec_spec.decode_fn(sp, tuple(hs), x, cfg=cfg,
                                      placement=pl_)

    relevant = ([seq_src] if mode in ("prefill", "sequence") else
                [dec_src] if mode == "decode" else [seq_src, dec_src])
    exe = GRUExecutable(
        cfg=cfg, batch=batch, seq=seq, masked=masked, placement=pl_,
        mode=mode,
        sequence_backend=seq_spec.name if seq_spec else None,
        decode_backend=dec_spec.name if dec_spec else None,
        mask_exact=seq_spec.caps.mask_exact if seq_spec else True,
        cost_source="measured" if "measured" in relevant else "static",
        sequence=run_sequence, prefill=run_prefill,
        decode=run_decode if dec_spec else None)
    _EXEC_CACHE[key] = exe
    return exe


def clear_cache() -> None:
    """Drop all memoized executables (tests; not needed in serving)."""
    _EXEC_CACHE.clear()


# ---------------------------------------------------------------------------
# compile-and-run conveniences (the legacy entry points shim onto these)
# ---------------------------------------------------------------------------

def sequence(params, h0s, xs, *, cfg: GRUConfig, return_all: bool = False,
             mask=None, mesh=None):
    """Run a depth-L stack over xs (B,T,X) with the compiled backend.
    Returns (per-layer finals, last-layer states | None)."""
    exe = compile(cfg, batch=xs.shape[0] if xs.ndim >= 3 else None,
                  seq=xs.shape[-2], placement=mesh, mask=mask is not None,
                  mode="sequence")
    return exe.sequence(params, h0s, xs, return_all=return_all, mask=mask)


def decode(params, hs, x, *, cfg: GRUConfig, mesh=None):
    """One serve step through the stack with the compiled backend.
    Returns the per-layer new hidden states."""
    exe = compile(cfg, batch=x.shape[0], placement=mesh, mode="decode")
    return exe.decode(params, hs, x)


# ---------------------------------------------------------------------------
# deprecated one-shot surface: plan() / ExecPlan
# ---------------------------------------------------------------------------

def plan(cfg: GRUConfig, *, batch: Optional[int] = None,
         seq: Optional[int] = None, mesh=None, mask: bool = False,
         mode: str = "serve") -> GRUExecutable:
    """DEPRECATED one-shot resolve — thin shim over :func:`compile` (the
    two-stage compile/execute API). Returns the SAME memoized executable
    ``compile`` would, so results are bitwise-identical; warns once per
    process."""
    gru_core._warn_deprecated("runtime.plan")
    return compile(cfg, batch=batch, seq=seq, placement=mesh, mask=mask,
                   mode=mode)


def __getattr__(name: str):
    if name == "ExecPlan":
        # deprecated class name: plans ARE executables now
        gru_core._warn_deprecated("runtime.ExecPlan")
        return GRUExecutable
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
