"""Cell-family registry: the protocol that opens the executor to non-GRU
recurrences.

The paper's workload-distribution scheme (row-parallel matvecs, fused
per-step compute, latency-first dispatch) is not GRU-specific — the same
structure serves any gated recurrence. This module is the seam: a
:class:`CellFamily` describes everything the executor
(:mod:`repro.core.runtime`) needs to compile/prepare/serve one recurrence
family — parameter specs, state layout, step/reference math, and which
prepare()-time weight views exist — and backends register against a
``(family, backend)`` key instead of assuming GRU. Adding a family (mLSTM,
SSM, ConvGRU, ...) is a registration, not a fork.

State convention: a stack's runtime state is a FLAT tuple of per-layer
leaves, layer-major — ``state_leaves`` arrays per layer, each ``(B, H)``,
with ``h_leaf`` indexing the readout hidden state within a layer's group.
GRU has one leaf per layer (``h``); sLSTM has four (``c, n, m, h`` — cell,
normalizer, exponential-gate stabilizer, hidden). A flat tuple of same-rank
arrays keeps every executor signature (``sequence_fn(sp, state, xs, ...)``),
the serving engine's slot-scatter, and the model cache specs identical
across families.

Families self-register on import of their home module;
:func:`ensure_families` imports the in-tree ones so lookups never depend on
import order. :func:`get_family` raises the typed :class:`UnknownCellFamily`
for anything unregistered — serving surfaces route through it so an unknown
``cfg.family`` fails loudly instead of silently degrading.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional

__all__ = [
    "CellFamily", "UnknownCellFamily", "register_family", "get_family",
    "is_cell_family", "families", "ensure_families", "cfg_family",
]


class UnknownCellFamily(KeyError):
    """``cfg.family`` names no registered cell family (typed: serving
    surfaces catch/raise this instead of silently degrading)."""

    def __init__(self, name: str, known=()):
        super().__init__(name)
        self.family = name
        self.known = tuple(sorted(known))

    def __str__(self) -> str:
        return (f"unknown cell family {self.family!r}; registered families: "
                f"{list(self.known)}")


@dataclasses.dataclass(frozen=True)
class CellFamily:
    """One recurrence family, as the executor sees it.

    ``gates``: gate columns per hidden unit — each layer's ``w`` is
    ``(X, gates*H)``, ``u`` is ``(H, gates*H)``, ``b`` is ``(gates*H,)``
    (3 for GRU's z/r/h, 4 for sLSTM's z/i/f/o).
    ``state_leaves``/``state_names``/``h_leaf``: the flat per-layer state
    layout (see module docstring).
    ``cell_specs(input_dim, hidden_dim)`` / ``stack_specs(cfg)``: parameter
    pytree specs (:class:`repro.core.params.Spec`).
    ``init_state(cfg, batch, dtype)``: the flat initial-state tuple.
    ``state_dtype``: the dtype the classifier's state is carried in, or
    None for the features' dtype (sLSTM: float32, so the normalizer and
    the stabilizer never drop to a narrower feature dtype).
    ``normalize(params, cfg)``: any accepted param layout -> per-layer
    ``({"w","u","b"}, ...)`` cells tuple.
    ``reference(cells, state0, xs, *, return_all, mask)``: the dense fp32
    oracle — ``(flat finals, last-layer h sequence | None)``. Every
    backend registered under this family is tested against it.
    ``stacked_views(cells)``: the fused kernels' prepare()-time weight
    stacks (None: no fused backend registered).
    ``supports_quant`` / ``supports_placement``: whether prepare() may
    build int8 weight views / mesh-sharded weight views for this family
    (GRU-only today; a capability of the family, not of one backend).
    """
    name: str
    gates: int
    state_leaves: int
    state_names: tuple
    h_leaf: int
    cell_specs: Callable = dataclasses.field(repr=False, default=None)
    stack_specs: Callable = dataclasses.field(repr=False, default=None)
    init_state: Callable = dataclasses.field(repr=False, default=None)
    normalize: Callable = dataclasses.field(repr=False, default=None)
    reference: Callable = dataclasses.field(repr=False, default=None)
    stacked_views: Optional[Callable] = dataclasses.field(repr=False,
                                                          default=None)
    supports_quant: bool = False
    supports_placement: bool = False
    state_dtype: Optional[str] = None

    def state0(self, cfg, batch: int, dtype=None):
        """Flat initial-state tuple for a depth-L stack (layer-major)."""
        if dtype is None:
            return self.init_state(cfg, batch)
        return self.init_state(cfg, batch, dtype)


_FAMILIES: Dict[str, CellFamily] = {}


def register_family(family: CellFamily) -> None:
    _FAMILIES[family.name] = family


def ensure_families() -> None:
    """Import the in-tree families so registration never depends on import
    order (mirrors ``runtime._ensure_backends`` for backends)."""
    if "slstm" not in _FAMILIES:
        from repro.core import slstm  # noqa: F401  (registers on import)


def families() -> Dict[str, CellFamily]:
    """Snapshot of the registry (name -> family), for introspection/tests."""
    ensure_families()
    return dict(_FAMILIES)


def get_family(name: str) -> CellFamily:
    ensure_families()
    fam = _FAMILIES.get(name)
    if fam is None:
        raise UnknownCellFamily(name, known=_FAMILIES)
    return fam


def is_cell_family(name) -> bool:
    """True when ``name`` is a registered recurrence family (i.e. the
    executor can compile it and the engine serves it through the
    bucketed-prefill/fixed-slot decode wave path)."""
    ensure_families()
    return name in _FAMILIES


def cfg_family(cfg) -> str:
    """The family a config compiles under (missing/empty field -> "gru",
    the pre-registry default — old configs keep exactly their behavior)."""
    return getattr(cfg, "family", "gru") or "gru"


# ---------------------------------------------------------------------------
# the GRU family: the paper's cell, registered like any other
# ---------------------------------------------------------------------------

def _gru_family() -> CellFamily:
    from repro.core import gru as gru_core

    def stacked_views(cells):
        from repro.kernels.gru_sequence import ops as seq_ops
        return seq_ops.prepare_stacked_cells(cells)

    def reference(cells, state0, xs, *, return_all=False, mask=None):
        return gru_core.gru_stack_reference(cells, tuple(state0), xs,
                                            return_all=return_all, mask=mask)

    return CellFamily(
        name="gru",
        gates=3,
        state_leaves=1,
        state_names=("h",),
        h_leaf=0,
        cell_specs=gru_core.gru_cell_specs,
        stack_specs=gru_core.gru_stack_specs,
        init_state=gru_core.stack_h0,
        normalize=gru_core.stack_cell_params,
        reference=reference,
        stacked_views=stacked_views,
        supports_quant=True,
        supports_placement=True,
    )


register_family(_gru_family())
