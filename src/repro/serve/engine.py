"""Batched serving engine: bucketed prefill + continuous-batching decode.

Design point mirrors the paper: the figure of merit is PER-STEP LATENCY of
the sequential decode path (batch can be 1); throughput comes from batching
aligned requests WITHOUT ever paying a recompile on the hot path.

Compile-once discipline (the ROADMAP's re-jit item):

* **Prompt-length buckets** — prompts are left-padded up to the next
  power of two (>= ``bucket_min``), so prefill jits once per bucket, not
  once per distinct prompt length. Padding is made semantics-exact by a
  per-slot length mask threaded to the recurrent core (False timesteps
  freeze the hidden state), so a bucketed prompt yields bitwise the same
  state as its unpadded original.
* **Fixed batch slots** (GRU waves) — the batch axis is always padded to
  ``max_batch`` slots (empty slots carry zero features and are masked
  out), so BOTH prefill and decode see one static batch shape: the decode
  step compiles exactly once per engine lifetime.
* **Keyed decode cache** — ``_get_decode`` is keyed by the decode input's
  batch shape (the donated-cache jit used to be keyed on nothing, so a
  wave with a different batch size silently retraced against it).

Continuous batching (GRU waves): ``generate`` accepts MORE requests than
``max_batch``. The overflow queues; whenever slots' requests finish
(EOS or budget), the slots are retired mid-wave and queued requests are
admitted into them. ALL requests admitted at one step share ONE bucketed
prefill (batch padded to the slot shape, so no new compilation) whose
rows are scattered into the freed slots of the live wave cache in one
device-side update — when several slots free simultaneously the admit
cost stays one prefill, not one per request. Finished streams therefore
free capacity immediately instead of padding the wave to the slowest
request.

Autotuning: an attached :class:`repro.serve.autotune.AutoTuner` closes
the loop from measured serving back into these knobs — wave size from
the measured batch-latency curve, the prompt-bucket ladder from the
observed length distribution (``bucket_ladder`` replaces the power-of-
two rule in ``_bucket_for``), and online CostModel recalibration. All
retuning happens at WAVE BOUNDARIES only (``_maybe_retune``): a tuning
decision may invalidate the jit caches (``_invalidate_jits``), which
must never happen under a live wave — the compile-once discipline holds
mid-wave by construction. Decisions are reported in
``latency_stats()["autotune"]``; see docs/serving.md ("Autotuning").

GRU execution dispatches through the executor (``repro.core.runtime``)
via its compile/execute API: params are prepared ONCE against the ctx's
placement (weight stacking and — under a mesh — device placement happen
at engine construction, never on the hot path), and the engine records
the compiled executable's chosen backend per prefill
(``prefill_backends``) and PER DECODE STEP (``decode_backends``, aligned
with ``step_times``). Decode attribution is keyed by the decode jit each
step ran under and frozen at that jit's trace time (the trace embeds the
backend; later cost-model changes don't retrace it), so ``latency_stats``
attributes every step to the backend that ACTUALLY ran — including when
continuous-batching admits change the decode key — rather than the one
resolved once at wave start. The attribution strings are executor
backend names, the ``pallas_sharded`` family (fused shard kernels inside
the shard_map, selectable per shape once a calibration measures the
sharded step faster) included.

Cell families: the wave path is not GRU-specific — ``generate`` routes
EVERY registered cell family (``repro.core.cells``: gru, slstm, ...)
through the same bucketed-prefill/fixed-slot machinery; the family's flat
state tuple flows leaf-by-leaf through the cache scatter, so sLSTM's
four-leaf (c, n, m, h) state rides the exact slot plumbing GRU's one-leaf
state does. A ``cfg.family`` that is neither a registered cell family nor
a known LM family raises the typed ``UnknownCellFamily`` instead of
silently degrading to the token path.

The cell families (the paper's own models) serve FEATURE VECTORS instead
of tokens: a request's ``prompt`` is a float (S, X) feature window, and each
decode step pushes one more feature vector (the request's ``stream`` if
provided, else free-running on the last observed features) and emits the
running class prediction. Per step that is exactly one pass through the
depth-L recurrence — with ``cfg.gru.backend == "pallas"`` a single fused
pallas_call (see ``repro.kernels.gru_sequence``) — the paper's latency
figure of merit, measured by ``latency_stats`` (p50/p99 tail bounds, not
just means: the paper's constraint is a deadline).
"""
from __future__ import annotations

from collections import deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ModelConfig
from repro.core import cells as cell_families
from repro.core.cells import UnknownCellFamily
from repro.distributed.fault_tolerance import Clock, SystemClock
from repro.distributed.sharding import ShardCtx
from repro.models import api as mapi

# Host spans of one ``gru_wave_step``, written into the profiler's trace
# (no cost beyond an enter/exit while no trace is being taken). The step
# span is the parent; the others are its children, in this order, and an
# admit-free step has no admit, prefill or scatter span (docs/serving.md,
# "Engine spans").
SPAN_STEP = "engine.step"
SPAN_ADMIT = "engine.admit"        # queue pop, slots, bucket, prompt staging
SPAN_PREFILL = "engine.prefill"    # the prefill dispatch (``prefill_times``)
SPAN_SCATTER = "engine.scatter"    # admitted rows into the wave cache
SPAN_FEED = "engine.feed"          # next-feature staging, per slot
SPAN_DECODE = "engine.decode"      # the decode dispatch
SPAN_READOUT = "engine.readout"    # the step's one wait: class download
SPAN_RETIRE = "engine.retire"      # classes out, lanes retired, drain check
STEP_SPANS = (SPAN_ADMIT, SPAN_PREFILL, SPAN_SCATTER, SPAN_FEED,
              SPAN_DECODE, SPAN_READOUT, SPAN_RETIRE)


@dataclass
class Request:
    prompt: np.ndarray               # (S,) int32 tokens | (S, X) float features
    max_new_tokens: int = 16
    eos_id: int = -1                 # -1 = never
    stream: Optional[np.ndarray] = None  # gru: (>=max_new, X) decode features
    out: List[int] = field(default_factory=list)
    done: bool = False
    # request-lifecycle timestamps (engine clock), for queue-wait and
    # end-to-end latency accounting; t_submit may be pre-stamped by a
    # front-door router so the wait includes fleet-level queueing
    t_submit: Optional[float] = None
    t_admit: Optional[float] = None
    t_finish: Optional[float] = None


def _pct(xs, q: float) -> float:
    """Percentile with honest empties: no history -> NaN, never 0.0 (an
    engine that served nothing must not report a perfect p99 — a 0.0
    there can silently pass ratio-based CI gates)."""
    return float(np.percentile(np.asarray(xs), q)) if len(xs) else float("nan")


def _mean(xs) -> float:
    return float(np.mean(np.asarray(xs))) if len(xs) else float("nan")


def bucket_len(S: int, minimum: int = 8) -> int:
    """Next power of two >= max(S, minimum): the prefill jit key."""
    b = max(minimum, 1)
    while b < S:
        b *= 2
    return b


@dataclass
class _Slot:
    """One live decode lane of a GRU wave."""
    req: Request
    last_feat: np.ndarray            # free-running fallback feature vector
    step: int = 0                    # per-request decode step (stream index)


@dataclass
class _GruWave:
    """Resumable continuous-batching state: the wave a stepwise caller
    (``gru_wave_step``) advances one decode step at a time."""
    slots: List[Optional[_Slot]]
    nxt: np.ndarray                  # (max_batch, X) next-feature staging
    key: tuple                       # decode jit key (max_batch, X)
    pending: deque = field(default_factory=deque)
    cache: Optional[dict] = None     # None until the first admit prefills


class ServeEngine:
    def __init__(self, cfg: ModelConfig, params, ctx: ShardCtx = ShardCtx(),
                 max_batch: int = 8, bucket_min: int = 8,
                 clock: Optional[Clock] = None, tuner=None):
        self.cfg = cfg
        self.ctx = ctx
        self.max_batch = max_batch
        self.bucket_min = bucket_min
        self.clock = clock or SystemClock()
        # optional feedback loop (repro.serve.autotune.AutoTuner): observes
        # prompts + warm step timings and retunes wave size / bucket ladder
        # / cost rows — only ever applied at wave boundaries (_maybe_retune)
        self.tuner = tuner
        # autotuned prefill ladder: None = the static power-of-two ladder;
        # else a small fixed ascending tuple of bucket lengths (jit keys)
        self.bucket_ladder: Optional[tuple] = None
        self.api = mapi.get_api(cfg)
        prep = getattr(self.api, "prepare_params", None)
        self.params = prep(params, cfg, ctx) if prep else params
        self._prefill_jit = {}           # keyed by prompt-length bucket
        self._decode_jit = {}            # keyed by decode batch shape
        self._decode_plan_backends = {}  # backend traced into each decode
                                         # jit (frozen at trace time)
        self._decode_warm = set()        # keys whose compile step has passed
        self._prefill_plan_backends = {} # backend traced into each prefill
                                         # bucket jit (frozen at trace time)
        self._prefill_cold = set()       # post-retune buckets whose first
                                         # (compile) timing is excluded
        self._scatter_jit = {}           # keyed by admit-batch size
        self._jit_gen = 0                # bumped per _invalidate_jits call
        self._wave: Optional[_GruWave] = None
        self.step_times: List[float] = []
        self.prefill_times: List[float] = []
        self.prefill_backends: List[str] = []   # executor choice per prefill
        self.decode_backend: Optional[str] = None    # latest resolved
        self.decode_backends: List[str] = []    # per recorded step (aligned
                                                # with step_times)
        self.queue_waits: List[float] = []      # per request: submit -> admit
        self.e2e_times: List[float] = []        # per request: submit -> finish
        self.device_waits = 0                   # waits on the device by the
                                                # wave path: one per decode step
        # device state arrays the wave cache holds, layers x the family's
        # state leaves (each one more array every prefill returns and every
        # scatter and decode takes and gives back); 0 off the wave path
        self.state_arrays = (len(self.api.cache_specs(cfg, max_batch)["h"])
                             if cell_families.is_cell_family(cfg.family)
                             else 0)

    # -- jit caches ---------------------------------------------------------

    def _get_decode(self, batch_shape: tuple):
        """Decode step jit, keyed by the new-input batch shape; returns
        ``(classes, cache)``: the argmax of the step's logits (int32, first
        index on ties) is computed inside the program, so the classes are
        the only result the host downloads. The cache is donated, so an
        unkeyed entry reused at a different batch shape would silently
        retrace; the key makes the compile-once contract checkable (see
        test_serve_engine_decode_cache_keyed_by_batch)."""
        if batch_shape not in self._decode_jit:
            def gru_decode(params, cache, tok):
                logits, cache = self.api.decode_step(params, self.cfg, cache,
                                                     tok, self.ctx)
                return jnp.argmax(logits, -1).astype(jnp.int32), cache
            self._decode_jit[batch_shape] = jax.jit(gru_decode,
                                                    donate_argnums=(1,))
        return self._decode_jit[batch_shape]

    def _get_prefill(self, S: int):
        if S not in self._prefill_jit:
            def gru_prefill(params, batch):
                return self.api.prefill(params, self.cfg, batch, self.ctx)
            self._prefill_jit[S] = jax.jit(gru_prefill)
            if self._jit_gen > 0:
                # a jit (re)created after a mid-serve retune: its first
                # call recompiles, and that compile is a tuning cost —
                # excluded from the percentiles exactly like the
                # per-decode-jit rule (_record_prefill). First-EVER bucket
                # compiles (gen 0) stay included: cold-start is part of
                # the prefill story.
                self._prefill_cold.add(S)
        return self._prefill_jit[S]

    def _get_scatter(self, k: int):
        """Admit-k cache scatter: copy rows 0..k-1 of a freshly prefilled
        cache into the ``k`` freed slots of the live wave cache
        (device-side, one trace per admit-batch size k <= max_batch)."""
        if k not in self._scatter_jit:
            def gru_scatter(cache, fresh, slots_):
                return {"h": tuple(h.at[slots_].set(f[:k]) for h, f in
                                   zip(cache["h"], fresh["h"])),
                        "pos": cache["pos"]}
            self._scatter_jit[k] = jax.jit(gru_scatter)
        return self._scatter_jit[k]

    # -- LM waves -----------------------------------------------------------

    def generate(self, requests: Sequence[Request]) -> List[Request]:
        """Serve a wave of requests. Cell-family waves (gru, slstm, any
        registered recurrence) run bucketed continuous batching and accept
        any number of requests; LM waves are a single padded/aligned batch
        of at most ``max_batch``. An unregistered family raises
        :class:`UnknownCellFamily` — never a silent fall-through to the
        token path."""
        reqs = list(requests)
        if cell_families.is_cell_family(self.cfg.family):
            return self._generate_gru(reqs)
        if self.cfg.family in ("audio", "vlm"):
            raise NotImplementedError("wave serving is LM/cell-family-only; "
                                      "use the model API directly for other "
                                      "families")
        if self.cfg.family not in mapi._FAMS:
            raise UnknownCellFamily(self.cfg.family,
                                    known=cell_families.families())
        assert len(reqs) <= self.max_batch
        B = len(reqs)
        now = self.clock.now()
        for r in reqs:
            if r.t_submit is None:
                r.t_submit = now
        S = max(len(r.prompt) for r in reqs)
        toks = np.zeros((B, S), np.int32)
        for i, r in enumerate(reqs):
            toks[i, S - len(r.prompt):] = r.prompt      # left-pad alignment
        prefill = self._get_prefill(S)
        t0 = self.clock.now()
        logits, cache = prefill(self.params, {"tokens": jnp.asarray(toks)})
        logits.block_until_ready()
        self._record_prefill(S, self.clock.now() - t0)
        now = self.clock.now()
        for r in reqs:
            r.t_admit = now
            self.queue_waits.append(now - r.t_submit)
        max_new = max(r.max_new_tokens for r in reqs)
        next_tok = jnp.argmax(logits, -1).astype(jnp.int32)
        key = tuple(next_tok.shape)
        decode = self._get_decode(key)
        finished = np.zeros(B, bool)
        for _ in range(max_new):
            t0 = self.clock.now()
            decoded, cache = decode(self.params, cache, next_tok)
            decoded.block_until_ready()
            self._record_step(key, self.clock.now() - t0)
            tok_np = np.asarray(next_tok)
            for i, r in enumerate(reqs):
                if not finished[i]:
                    r.out.append(int(tok_np[i]))
                    if (int(tok_np[i]) == r.eos_id
                            or len(r.out) >= r.max_new_tokens):
                        finished[i] = True
                        self._finish(r)
            if finished.all():
                break
            next_tok = decoded
        for r in reqs:
            if not r.done:
                self._finish(r)
        return reqs

    def _finish(self, r: Request) -> None:
        """Mark a request complete and record its end-to-end latency."""
        r.done = True
        r.t_finish = self.clock.now()
        if r.t_submit is not None:
            self.e2e_times.append(r.t_finish - r.t_submit)

    # -- GRU waves: bucketed continuous batching ----------------------------

    def _gru_prefill_batch(self, prompts: List[np.ndarray], Sb: int):
        """Left-pad prompts into the FIXED (max_batch, Sb, X) slot shape with
        an exactness mask; rows beyond len(prompts) are empty (fully
        masked)."""
        X = self.cfg.gru.input_dim
        Bs = self.max_batch
        feats = np.zeros((Bs, Sb, X), np.float32)
        mask = np.zeros((Bs, Sb), bool)
        for i, p in enumerate(prompts):
            feats[i, Sb - p.shape[0]:] = p
            mask[i, Sb - p.shape[0]:] = True
        return feats, mask

    def _bucket_for(self, S: int) -> int:
        """The prefill bucket (jit key) a prompt of length ``S`` pads to:
        the autotuned quantile ladder when one is installed (smallest rung
        >= S; prompts above the top rung double from it, so the key space
        stays a small fixed set), else the static power-of-two ladder."""
        if self.bucket_ladder:
            for b in self.bucket_ladder:
                if S <= b:
                    return b
            return bucket_len(S, minimum=self.bucket_ladder[-1] * 2)
        return bucket_len(S, self.bucket_min)

    def _prefill_backend_for(self, Sb: int) -> Optional[str]:
        """The executor backend the prefill jit for bucket ``Sb`` traced
        with — resolved once at first use and frozen, mirroring
        ``_decode_backend_for``: the jitted prefill embeds the backend
        chosen in its trace-time cost epoch, so attribution must not
        follow later cost-model changes (a retune that DOES change the
        resolution also invalidates the jits, clearing this map)."""
        if Sb not in self._prefill_plan_backends:
            compiler = getattr(self.api, "executable", None)
            # mirrors the compile key gru_lm.prefill resolves for this
            # call: the engine always sends the slot-shaped batch WITH a
            # mask, so (batch, seq, masked=True) is the key the model uses
            self._prefill_plan_backends[Sb] = (
                None if compiler is None
                else compiler(self.cfg, batch=self.max_batch, seq=Sb,
                              masked=True, mode="prefill",
                              mesh=self.ctx.mesh).sequence_backend)
        return self._prefill_plan_backends[Sb]

    def _record_prefill(self, Sb: int, dt: float) -> None:
        """Record one prefill latency. A bucket's first-EVER compile is
        included (cold-start is part of the prefill story), but a jit
        (re)created after a retune invalidation has its first (compile)
        call excluded — same rule as the per-decode-jit exclusion, so
        mid-serve retunes can't poison the steady-state percentiles."""
        if Sb in self._prefill_cold:
            self._prefill_cold.discard(Sb)
            return
        self.prefill_times.append(dt)

    def _gru_prefill(self, Sb: int, feats: np.ndarray, mask: np.ndarray):
        """One bucketed prefill of a staged slot batch (bucket ``Sb``, from
        ``_gru_prefill_batch``); returns cache. Nothing waits on the
        device here: the host reads no prefill result, so the cache flows
        on to the scatter and the decode, which the device runs in order.
        ``prefill_times`` records what the host pays for the call: the
        copies of features and mask and the dispatch."""
        backend = self._prefill_backend_for(Sb)
        if backend is not None:          # record the executor's choice
            self.prefill_backends.append(backend)
        prefill = self._get_prefill(Sb)
        t0 = self.clock.now()
        _, cache = prefill(self.params, {"features": jnp.asarray(feats),
                                         "mask": jnp.asarray(mask)})
        self._record_prefill(Sb, self.clock.now() - t0)
        return cache

    def _make_slot(self, r: Request) -> _Slot:
        X = self.cfg.gru.input_dim
        p = np.asarray(r.prompt, np.float32).reshape(-1, X)
        return _Slot(req=r, last_feat=p[-1])

    def _generate_gru(self, reqs: List[Request]) -> List[Request]:
        if not reqs:
            return []
        self.gru_wave_begin(reqs)
        while self.gru_wave_active():
            self.gru_wave_step()
        self._wave = None
        for r in reqs:
            if not r.done:                              # pragma: no cover
                r.done = True
        return reqs

    # -- stepwise wave API (the fleet router's drive surface) ---------------
    #
    # ``generate`` is a closed loop: begin + step-until-idle. A front-door
    # router (``repro.serve.fleet``) needs finer control — advance each
    # replica ONE decode step per scheduler tick, enqueue new requests into
    # a live wave, and cancel a lane (hedging first-wins, retry-on-death) —
    # so the continuous-batching loop is exposed as begin/enqueue/step/
    # cancel. All four preserve the compile-once discipline: the same
    # bucketed prefills, the same fixed-slot decode jit.

    def gru_wave_begin(self, requests: Sequence[Request] = ()) -> None:
        """Start a fresh continuous-batching wave (cell families only).
        A wave boundary: the attached tuner (if any) may retune here,
        before any slot shape is traced for this wave."""
        if not cell_families.is_cell_family(self.cfg.family):
            raise UnknownCellFamily(self.cfg.family,
                                    known=cell_families.families())
        self._maybe_retune()
        X = self.cfg.gru.input_dim
        Bs = self.max_batch
        self._wave = _GruWave(slots=[None] * Bs,
                              nxt=np.zeros((Bs, X), np.float32),
                              key=(Bs, X))
        self.gru_wave_enqueue(requests)

    def gru_wave_enqueue(self, requests: Sequence[Request]) -> None:
        """Queue requests into the live wave (FIFO admission; they enter
        slots as capacity frees). Starts a wave if none is live."""
        if self._wave is None:
            self.gru_wave_begin(())
        now = self.clock.now()
        X = self.cfg.gru.input_dim
        for r in requests:
            if r.t_submit is None:
                r.t_submit = now
            if self.tuner is not None:
                self.tuner.observe_prompt(
                    np.asarray(r.prompt).reshape(-1, X).shape[0])
            self._wave.pending.append(r)

    def gru_wave_active(self) -> int:
        """Live lanes + queued requests still owed work by this wave."""
        w = self._wave
        if w is None:
            return 0
        return sum(s is not None for s in w.slots) + len(w.pending)

    def gru_work_remaining(self) -> tuple:
        """(requests, decode tokens) still owed — the router's measured
        queue-depth signal for expected-service-time routing."""
        w = self._wave
        if w is None:
            return 0, 0
        toks = sum(max(1, s.req.max_new_tokens - len(s.req.out))
                   for s in w.slots if s is not None)
        toks += sum(max(1, r.max_new_tokens) for r in w.pending)
        return self.gru_wave_active(), toks

    def bucket_warm(self, prompt_len: int) -> bool:
        """Whether this engine has already compiled the prefill bucket a
        prompt of ``prompt_len`` lands in (router bucket-affinity)."""
        return self._bucket_for(prompt_len) in self._prefill_jit

    # -- autotune surface (repro.serve.autotune) ----------------------------
    #
    # The tuner never mutates the engine directly: it calls these
    # boundary-safe mutators from maybe_retune(), which the engine itself
    # only invokes between waves (_maybe_retune). That split is what keeps
    # the no-mid-wave-retrace invariant enforceable in one place.

    def _maybe_retune(self) -> None:
        """Run the attached tuner if (and only if) no wave work is live —
        a retune may invalidate every jit cache, which must never happen
        under a wave mid-decode (the donated decode cache and the frozen
        backend attribution both assume trace stability for the wave's
        lifetime)."""
        if self.tuner is None:
            return
        if self._wave is not None and self.gru_wave_active() > 0:
            return
        self.tuner.maybe_retune(self)

    def _invalidate_jits(self) -> None:
        """Drop every shape-dependent jit (prefill buckets, decode steps,
        admit scatters) plus the frozen backend attributions, so the next
        call re-traces against the CURRENT wave size and cost epoch.
        Only wave-boundary retunes call this. The warm/cold markers reset
        with the jits: each re-created jit's first (compile) step is
        excluded from the percentiles again (_record_step /
        _record_prefill)."""
        self._prefill_jit.clear()
        self._decode_jit.clear()
        self._scatter_jit.clear()
        self._decode_plan_backends.clear()
        self._prefill_plan_backends.clear()
        self._decode_warm.clear()
        self._prefill_cold.clear()
        self._jit_gen += 1

    def apply_wave_size(self, n: int) -> None:
        """Resize the decode slot count (tuner decision). Every jit here
        is batch-shaped — prefill pads to ``max_batch`` rows, decode and
        scatter trace the slot axis — so the caches are invalidated; a
        drained wave object is dropped so the next enqueue builds slots
        at the new size. Callable only between waves (enforced by
        _maybe_retune being the sole caller path)."""
        n = int(n)
        if n < 1 or n == self.max_batch:
            return
        self.max_batch = n
        self._invalidate_jits()
        if self._wave is not None and self.gru_wave_active() == 0:
            self._wave = None

    def apply_bucket_ladder(self, ladder) -> None:
        """Install an autotuned prefill-bucket ladder (ascending lengths;
        empty/None restores the power-of-two ladder). Existing bucket
        jits stay valid — old buckets simply stop being chosen for new
        admits, and identical rungs keep hitting their compiled jits —
        but the generation marker bumps: NEW bucket jits born from this
        retune compile mid-serve, and their first call is excluded from
        the percentiles like any other post-retune jit (_get_prefill)."""
        ladder = tuple(int(b) for b in (ladder or ()))
        ladder = ladder or None
        if ladder != self.bucket_ladder:
            self.bucket_ladder = ladder
            self._jit_gen += 1

    def refresh_executables(self) -> bool:
        """After a cost-model epoch bump: re-resolve the executor choice
        for every live jit key and invalidate ONLY if some resolution
        changed. The live jits froze their trace-time backend, so when
        the refreshed table confirms those choices a recalibration costs
        zero retraces; when it disagrees, serving the now-known-slower
        backend would be worse than one boundary recompile."""
        compiler = getattr(self.api, "executable", None)
        if compiler is None:
            return False
        changed = False
        for key, frozen in self._decode_plan_backends.items():
            fresh = compiler(self.cfg, batch=key[0], mode="decode",
                             mesh=self.ctx.mesh).decode_backend
            if fresh != frozen:
                changed = True
                break
        if not changed:
            for Sb, frozen in self._prefill_plan_backends.items():
                fresh = compiler(self.cfg, batch=self.max_batch, seq=Sb,
                                 masked=True, mode="prefill",
                                 mesh=self.ctx.mesh).sequence_backend
                if fresh != frozen:
                    changed = True
                    break
        if changed:
            self._invalidate_jits()
        return changed

    def gru_wave_cancel(self, request: Request) -> bool:
        """Drop a request from the live wave (queued or mid-decode): the
        fleet's first-wins hedge cancellation and retry requeue both land
        here. The lane frees immediately; the stale cache row is inert
        (masked slots' outputs are never read). Returns False if the
        request is not in this wave (e.g. it just finished)."""
        w = self._wave
        if w is None:
            return False
        for i, r in enumerate(w.pending):
            if r is request:
                del w.pending[i]
                return True
        for j, s in enumerate(w.slots):
            if s is not None and s.req is request:
                w.slots[j] = None
                return True
        return False

    def gru_wave_step(self) -> List[Request]:
        """Advance the wave ONE decode step: admit queued requests into
        every empty slot (ALL admits share ONE bucketed prefill + one
        scatter), run one fused decode step over the fixed slots, retire
        finished lanes. Returns the requests that finished this step.

        The prefill, the scatter and the decode are dispatched back to
        back; the step waits on the device once, for the classes
        (``device_waits``)."""
        with TraceAnnotation(SPAN_STEP):
            w = self._wave
            if w is None:
                return []
            X = self.cfg.gru.input_dim
            empty = [j for j, s in enumerate(w.slots) if s is None]
            if empty and w.pending:
                with TraceAnnotation(SPAN_ADMIT):
                    k = min(len(empty), len(w.pending))
                    admits = [self._make_slot(w.pending.popleft())
                              for _ in range(k)]
                    now = self.clock.now()
                    for s in admits:
                        s.req.t_admit = now
                        if s.req.t_submit is not None:
                            self.queue_waits.append(now - s.req.t_submit)
                    prompts = [np.asarray(s.req.prompt,
                                          np.float32).reshape(-1, X)
                               for s in admits]
                    Sb = self._bucket_for(max(p.shape[0] for p in prompts))
                    feats, mask = self._gru_prefill_batch(prompts, Sb)
                with TraceAnnotation(SPAN_PREFILL):
                    fresh = self._gru_prefill(Sb, feats, mask)
                with TraceAnnotation(SPAN_SCATTER):
                    if w.cache is None:
                        # first cohort: the prefilled cache IS the wave
                        # cache (row i belongs to slot i; surplus rows are
                        # fully masked)
                        w.cache = fresh
                    else:
                        w.cache = self._get_scatter(k)(
                            w.cache, fresh, jnp.asarray(empty[:k], jnp.int32))
                    for j, s in zip(empty[:k], admits):
                        w.slots[j] = s
            if not any(s is not None for s in w.slots):
                return []
            with TraceAnnotation(SPAN_FEED):
                for j, s in enumerate(w.slots):
                    if s is None:
                        w.nxt[j] = 0.0
                        continue
                    r = s.req
                    w.nxt[j] = (r.stream[s.step] if r.stream is not None
                                and s.step < len(r.stream) else s.last_feat)
            # attribution is frozen per decode-jit key AT TRACE TIME
            # (_decode_backend_for): the jitted step embeds whichever
            # backend the executor resolved when it first traced, and later
            # cost-model epoch bumps do NOT retrace it — so a fresh
            # compile() mid-wave could only MIS-attribute. Steps are
            # recorded under the key they ran with; if admits ever change
            # the decode key (live-batch resizing), the new key resolves its
            # own backend on first use. A step's time runs from the decode
            # dispatch to the classes in hand, so on an admitting step it
            # also holds the device time of this step's prefill and scatter.
            with TraceAnnotation(SPAN_DECODE):
                decode = self._get_decode(w.key)
                t0 = self.clock.now()
                classes, w.cache = decode(self.params, w.cache,
                                          jnp.asarray(w.nxt))
            with TraceAnnotation(SPAN_READOUT):
                cls = np.asarray(classes)
                self.device_waits += 1
                self._record_step(w.key, self.clock.now() - t0,
                                  self._decode_backend_for(w.key))
            with TraceAnnotation(SPAN_RETIRE):
                finished = []
                for j, s in enumerate(w.slots):
                    if s is None:
                        continue
                    r = s.req
                    r.out.append(int(cls[j]))
                    s.step += 1
                    if (int(cls[j]) == r.eos_id
                            or len(r.out) >= r.max_new_tokens):
                        self._finish(r)
                        w.slots[j] = None               # retire mid-wave
                        finished.append(r)
                if not w.pending and all(s is None for s in w.slots):
                    # the wave just drained: a boundary. The tuner may
                    # retune now (possibly invalidating jits / resizing
                    # slots) — the next enqueue starts a fresh wave against
                    # the new configuration.
                    self._maybe_retune()
            return finished

    # -- stats --------------------------------------------------------------

    def _decode_backend_for(self, key: tuple) -> Optional[str]:
        """The executor backend the decode jit for ``key`` traced with —
        resolved ONCE per key at first use (i.e. at trace time, in the
        same cost-model epoch) and frozen thereafter, because the jitted
        step itself never retraces on epoch bumps. This is what makes
        ``decode_backends`` attribution reflect the backend that ACTUALLY
        ran, not whatever a fresh compile() would pick today. Also tracks
        the latest choice on ``self.decode_backend``."""
        if key not in self._decode_plan_backends:
            compiler = getattr(self.api, "executable", None)
            self._decode_plan_backends[key] = (
                None if compiler is None
                else compiler(self.cfg, batch=key[0], mode="decode",
                              mesh=self.ctx.mesh).decode_backend)
        backend = self._decode_plan_backends[key]
        if backend is not None:
            self.decode_backend = backend
        return backend

    def _record_step(self, key: tuple, dt: float,
                     backend: Optional[str] = None) -> None:
        """Record one decode-step latency, excluding each decode jit's
        FIRST call (its compile) so the tail percentiles reflect steady
        state, not compilation — per key, since every batch shape compiles
        separately. ``backend`` attributes the step to the executor
        backend that actually ran it (``decode_backends`` stays aligned
        with ``step_times``)."""
        if key in self._decode_warm:
            self.step_times.append(dt)
            self.decode_backends.append(backend)
            if self.tuner is not None and backend is not None:
                # warm steps only: compile steps must not become cost rows
                g = self.cfg.gru
                self.tuner.observe_step(
                    dt, batch=key[0], backend=backend,
                    depth=g.resolved_num_layers,
                    hidden=g.resolved_layer_dims[0],
                    family=cell_families.cfg_family(g))
        else:
            self._decode_warm.add(key)

    def latency_stats(self) -> Dict[str, float]:
        """Per-step decode latency distribution (tail-bound view: the
        paper's constraint is a deadline, not an average) plus prefill
        timings and ``decode_backend_steps`` (recorded steps per executor
        backend — attribution follows the backend each step's decode jit
        actually traced with). Compile
        steps are excluded per decode-jit key at record time; prefill
        timings INCLUDE each bucket's compile (cold-start cost is part of
        the prefill story). Empty histories report NaN, never 0.0 — an
        engine that served nothing has no percentiles (``steps`` /
        ``requests`` / ``prefills`` say how much history backs each
        number). ``device_waits`` counts the wave path's waits on the
        device: one per decode step; ``state_arrays`` is the number of
        device arrays the wave cache's state is made of."""
        ts = self.step_times
        pf = self.prefill_times
        qw = self.queue_waits
        ee = self.e2e_times
        per_backend: Dict[str, int] = {}
        for b in self.decode_backends:
            if b is not None:
                per_backend[b] = per_backend.get(b, 0) + 1
        from repro.core import runtime
        # the autotune decision trail: current tuned shape + every applied
        # decision with the measurement that justified it (always present;
        # enabled=False for untuned engines, so consumers need no getattr)
        autotune = {"enabled": self.tuner is not None,
                    "wave_size": self.max_batch,
                    "bucket_ladder": (list(self.bucket_ladder)
                                      if self.bucket_ladder else None)}
        if self.tuner is not None:
            autotune.update(self.tuner.stats())
        return {"decode_backend_steps": per_backend,
                "autotune": autotune,
                # per-REQUEST latencies (engine clock): queue wait is
                # submit -> slot admission, e2e is submit -> finish — the
                # router's depth-aware routing signal and the fleet
                # benchmark's honest p99 (per-step decode percentiles alone
                # hide queueing delay entirely)
                "requests": len(self.e2e_times),
                "queue_wait_mean_s": _mean(qw),
                "queue_wait_p50_s": _pct(qw, 50),
                "queue_wait_p99_s": _pct(qw, 99),
                "e2e_mean_s": _mean(ee),
                "e2e_p50_s": _pct(ee, 50),
                "e2e_p99_s": _pct(ee, 99),
                # the datapath precision the latest resolved decode backend
                # serves (int8 for the *_q8 backends, float32 otherwise)
                "served_dtype": runtime.backend_dtype(self.decode_backend),
                "mean_s": _mean(ts),
                "p50_s": _pct(ts, 50),
                "p90_s": _pct(ts, 90),
                "p99_s": _pct(ts, 99),
                "max_s": float(max(ts)) if ts else float("nan"),
                "steps": len(ts),
                "prefill_mean_s": _mean(pf),
                "prefill_p99_s": _pct(pf, 99),
                "prefills": len(self.prefill_times),
                "device_waits": self.device_waits,
                "state_arrays": self.state_arrays}
