"""Plain float32 reference of the jet-tagging sLSTM stack and its weights.

Independent of the program under test: the cell equations are written out
here from the sLSTM of xLSTM (Beck et al. 2024, arXiv:2405.04517, section
2.2), for one head (the source's block-diagonal recurrent matrix is then
dense). Per step, with pre-activations ``a~ = x W_a + h_{t-1} R_a + b_a``:

    z_t = tanh(z~)                cell input
    i_t = exp(i~)                 exponential input gate (log i_t = i~)
    f_t = sigmoid(f~)             forget gate
    o_t = sigmoid(o~)             output gate
    m_t = max(log f_t + m_{t-1}, i~)               stabilizer
    c_t = exp(log f_t + m_{t-1} - m_t) c_{t-1} + exp(i~ - m_t) z_t
    n_t = exp(log f_t + m_{t-1} - m_t) n_{t-1} + exp(i~ - m_t)
    h_t = o_t c_t / n_t

from ``c_0 = n_0 = h_0 = 0`` and ``m_0 = -inf`` (the program starts ``m``
at -1e30, which gives the same first step: its forget term is exactly 0).
Gate columns follow the program's weight layout ``[z, i, f, o]``, a layout
convention and not math. The one departure of the program checked against
this: it divides by ``max(n_t, 1e-6)``, which never binds, since one of
the two exponentials in ``n_t`` is ``exp(0) = 1`` and both terms are
positive, so ``n_t >= 1`` from the first step on.

``precision`` selects how every matrix product is computed, as in
``gru.py``: ``"highest"`` is float32 (``lax.Precision.HIGHEST``), the
configuration's stated precision; ``"high"`` is three bfloat16 passes
(a_hi*b_hi + a_hi*b_lo + a_lo*b_hi, accumulated in float32), written out
so that it means the same on every backend. The latter is the
lower-precision control.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

GATES = 4                    # [z, i, f, o]
LEAVES = 4                   # (c, n, m, h) per layer


def _mm(a, b, precision: str):
    if precision == "highest":
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    if precision == "high":
        (ah, al), (bh, bl) = _split(a), _split(b)
        # one product over the three pairs side by side, so no compiler
        # pass can fold them back into fewer passes
        return jnp.matmul(jnp.concatenate([ah, ah, al], -1),
                          jnp.concatenate([bh, bl, bh], 0),
                          preferred_element_type=jnp.float32)
    raise ValueError(f"unknown precision {precision!r}")


def _split(v):
    """v = hi + lo with hi its top 8 significand bits (exact in bfloat16,
    cut by masking the bits) and lo the rest, rounded to bfloat16."""
    bits = jax.lax.bitcast_convert_type(v, jnp.uint32)
    hi = jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                      jnp.float32)
    return hi.astype(jnp.bfloat16), (v - hi).astype(jnp.bfloat16)


def layer_inputs(sizes: dict) -> list:
    X, H, L = sizes["input_dim"], sizes["hidden_dim"], sizes["num_layers"]
    return [X] + [H] * (L - 1)


def init(sizes: dict, key) -> dict:
    """Weights for ``sizes`` from one key, in one jitted call on the default
    device: per layer w (in, 4H), u (H, 4H), b (4H,); a head w (H, C), b (C,).
    Biases are drawn too (not zero), so a path that drops one shows."""
    H, C = sizes["hidden_dim"], sizes["num_classes"]
    ins = layer_inputs(sizes)

    def make(key):
        ks = iter(jax.random.split(key, 3 * len(ins) + 2))
        cells = [{"w": jax.random.normal(next(ks), (d, GATES * H))
                  / np.sqrt(d),
                  "u": jax.random.normal(next(ks), (H, GATES * H))
                  / np.sqrt(H),
                  "b": 0.1 * jax.random.normal(next(ks), (GATES * H,))}
                 for d in ins]
        head = {"w": jax.random.normal(next(ks), (H, C)) / np.sqrt(H),
                "b": 0.1 * jax.random.normal(next(ks), (C,))}
        return {"cells": cells, "head": head}

    return jax.jit(make)(key)


def _cell(p, state, x, precision):
    """One step of one layer: state (c, n, m, h) -> the new one."""
    c, n, m, h = state
    pre = _mm(x, p["w"], precision) + _mm(h, p["u"], precision) + p["b"]
    zt, it, ft, ot = jnp.split(pre, GATES, axis=-1)
    z = jnp.tanh(zt)
    log_f = jax.nn.log_sigmoid(ft)
    o = jax.nn.sigmoid(ot)
    m_new = jnp.maximum(log_f + m, it)
    keep = jnp.exp(log_f + m - m_new)
    write = jnp.exp(it - m_new)
    c_new = keep * c + write * z
    n_new = keep * n + write
    return c_new, n_new, m_new, o * c_new / n_new


def _state0(params, N):
    H = params["cells"][0]["u"].shape[0]
    zero = jnp.zeros((N, H), jnp.float32)
    return tuple((zero, zero, jnp.full((N, H), -jnp.inf, jnp.float32), zero)
                 for _ in params["cells"])


def logits(params: dict, xs, first: int, precision: str = "highest"):
    """xs (N, T, X) feature sequences, every step live -> class logits after
    each of steps ``first``..T-1, shape (N, T - first, C): the logits a
    served request reads once it has consumed xs[:, :t + 1]."""

    def step(states, x):
        new = []
        for p, s in zip(params["cells"], states):
            s = _cell(p, s, x, precision)
            new.append(s)
            x = s[3]
        return tuple(new), _mm(x, params["head"]["w"], precision) \
            + params["head"]["b"]

    _, out = jax.lax.scan(step, _state0(params, xs.shape[0]),
                          jnp.swapaxes(xs, 0, 1))
    return jnp.swapaxes(out[first:], 0, 1)


def states(params: dict, xs, lengths, precision: str = "highest"):
    """Every layer's state after the first ``lengths[i]`` steps of row i
    of ``xs`` (N, T, X): shape (4L, N, H), the leaves (c, n, m, h) of
    layer 0, then of layer 1, and so on (the program's flat order)."""
    N, T = xs.shape[:2]

    def step(states, tx):
        t, x = tx
        live = (t < lengths)[:, None]
        new = []
        for p, s in zip(params["cells"], states):
            got = _cell(p, s, x, precision)
            new.append(tuple(jnp.where(live, a, b) for a, b in zip(got, s)))
            x = got[3]
        return tuple(new), None

    final, _ = jax.lax.scan(step, _state0(params, N),
                            (jnp.arange(T), jnp.swapaxes(xs, 0, 1)))
    return jnp.stack([leaf for s in final for leaf in s])
