"""Decide ``correct``: what the window served against the plain reference.

``gap``: for every request answered in the window or still being served
at its close, and every class it was served, the reference's logits at
that position (its prompt and the feature vectors it had consumed) give
the gap by which the served class's logit lies below the reference's best;
``gap`` is the widest. Zero when every class is the reference's argmax,
small where a class flipped on a near-tie, large where the served path is
wrong.

``state_med`` and ``state_max``: the recurrent state the engine holds in
its decode slots when the window closes, every layer, against the
reference's: each live request's after the feature vectors it has
consumed, and the final state of each request the last step answered,
which its freed slot still holds. Each state's widest error; ``state_med``
is their median, ``state_max`` the widest. Classes alone do not show a
lower precision (its logits move by about 1e-5, which flips no class in a
window's worth); the state does. The median moves when all or half of the
slots go wrong, the widest when one does (an admission written to the
wrong row, one slot left out of a step).

The control (the reference at the next lower precision, in the program's
place) is read the same ways by ``control_gap`` and by ``state_errs`` at
that precision.
"""
from __future__ import annotations

import numpy as np

BLOCK = 8192                 # reference rows per call
# the numbers a cell's limits file may name (bench/limits/<cell>.json)
NUMBERS = ("gap", "state_med", "state_max", "short")


def reference_logits(ref, params, pool, indices, precision: str):
    """{pool index: (positions, classes) reference logits} for ``indices``,
    computed in blocks of rows so that it fits beside anything else."""
    import jax
    idx = np.asarray(sorted(indices), np.int64)
    fn = jax.jit(ref.logits, static_argnums=(2, 3))
    size = min(BLOCK, len(pool))      # one shape per mix, so it is cached
    out = {}
    for a in range(0, len(idx), size):
        blk = idx[a:a + size]
        rows = np.zeros((size,) + pool.feats.shape[1:], np.float32)
        rows[:len(blk)] = pool.feats[blk]
        got = np.asarray(fn(params, rows, pool.prompt_len, precision))
        for k, j in enumerate(blk):
            out[int(j)] = got[k, :int(pool.served[j])]
    return out


def reference_states(ref, params, pool, items, precision: str):
    """Every layer's reference state of each ``(pool index, steps)`` item,
    shape (len(items), L, H), in blocks of rows like the logits."""
    import jax
    fn = jax.jit(ref.states, static_argnums=(3,))
    size = min(BLOCK, len(pool))
    out = []
    for a in range(0, len(items), size):
        blk = items[a:a + size]
        rows = np.zeros((size,) + pool.feats.shape[1:], np.float32)
        lengths = np.zeros((size,), np.int32)
        for k, (j, n) in enumerate(blk):
            rows[k], lengths[k] = pool.feats[j], n
        got = np.asarray(fn(params, rows, lengths, precision))
        out.append(np.swapaxes(got, 0, 1)[:len(blk)])
    return np.concatenate(out) if out else np.zeros((0,))


def state_pairs(ref, params, pool, rows, live: dict, last) -> list:
    """``(slot, (pool index, steps))`` pairs to compare: each live request
    (``live``: id of the request -> pool index) with its own slot, and each
    request the window's last step answered (``last``: (pool index, n)),
    whose final state its freed slot still holds (no step has run since),
    with the slot nearest to it."""
    S = pool.prompt_len
    pairs, free = [], []
    for k, (req, _) in enumerate(rows):
        j = live.get(id(req)) if req is not None else None
        if j is not None:
            pairs.append((k, (j, S + len(req.out))))
        elif req is None:
            free.append(k)
    done = sorted({(j, S + n) for j, n in last})
    if free and done:
        want = reference_states(ref, params, pool, done, "highest")
        want = want.reshape(len(done), -1).astype(np.float64)
        have = np.stack([rows[k][1].reshape(-1) for k in free]).astype(
            np.float64)
        d2 = ((want * want).sum(1)[:, None] + (have * have).sum(1)[None]
              - 2 * want @ have.T)
        pairs += [(free[int(b)], t) for t, b in zip(done, d2.argmin(1))]
    return pairs


def state_errs(ref, params, pool, rows, pairs, precision="highest"):
    """Each pair's widest |slot state - reference state| over its layers
    and units; with another ``precision`` than the configuration's, the
    control's (its states in the slots' place)."""
    items = [t for _, t in pairs]
    want = reference_states(ref, params, pool, items, "highest")
    have = (np.stack([rows[k][1] for k, _ in pairs])
            if precision == "highest"
            else reference_states(ref, params, pool, items, precision))
    return np.abs(have - want).reshape(len(pairs), -1).max(1)


def state_stats(ref, params, pool, rows, pairs, precision="highest"):
    """``{"state_med": ..., "state_max": ...}`` over ``pairs``: the median
    and the widest of each state's widest error (infinite where no state
    was compared)."""
    if not pairs:
        return {"state_med": float("inf"), "state_max": float("inf")}
    e = state_errs(ref, params, pool, rows, pairs, precision)
    return {"state_med": float(np.median(e)), "state_max": float(e.max())}


def compared(w) -> list:
    """(pool index, classes) of the window's requests whose classes are
    compared: every answered one and every one being served at the close."""
    return list(w.outs()) + [(j, list(r.out)) for r, j in w.inflight
                             if r.out]


def served_gap(items, ref_logits) -> float:
    """Widest gap, over every class served (``items``: pool index and the
    classes served to it), between the reference's best logit and the
    served class's."""
    worst = 0.0
    for j, out in items:
        lg = ref_logits[j][:len(out)]
        got = lg[np.arange(len(out)), np.asarray(out, np.int64)]
        worst = max(worst, float(np.max(lg.max(-1) - got)))
    return worst


def control_gap(items, ref_logits, low_logits) -> float:
    """The same gap for the classes the lower-precision reference puts
    first, at every position the run served."""
    worst = 0.0
    for j, out in items:
        lg, lo = ref_logits[j][:len(out)], low_logits[j][:len(out)]
        got = lg[np.arange(len(out)), lo.argmax(-1)]
        worst = max(worst, float(np.max(lg.max(-1) - got)))
    return worst


def counts(w) -> dict:
    """``short``: answered requests served another number of classes than
    they asked for."""
    return {"short": sum(c != n for c, n in zip(w.count, w.n))}
