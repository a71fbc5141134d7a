"""Plain float32 reference of the jet-tagging GRU stack and its weights.

Independent of the program under test: the cell equations are written out
here (update gate z, reset gate r, candidate with the reset applied to the
hidden state before the recurrent product, gate columns ordered [z, r, h]),
as in the paper's section 2 and the GRU of Cho et al. (2014). Weights are
drawn here from the run's seed; the harness hands the same arrays to the
program.

``precision`` selects how every matrix product is computed:
``"highest"`` is float32 (``lax.Precision.HIGHEST``), the configuration's
stated precision; ``"high"`` is three bfloat16 passes (a_hi*b_hi + a_hi*b_lo
+ a_lo*b_hi, accumulated in float32), written out so that it means the same
on every backend (XLA's own ``HIGH`` is float32 on the CPU). The latter is
the lower-precision control.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


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
    cut by masking the bits, which no compiler treats as a no-op round
    trip) and lo the rest, rounded to bfloat16."""
    bits = jax.lax.bitcast_convert_type(v, jnp.uint32)
    hi = jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                      jnp.float32)
    return hi.astype(jnp.bfloat16), (v - hi).astype(jnp.bfloat16)


def layer_inputs(sizes: dict) -> list:
    X, H, L = sizes["input_dim"], sizes["hidden_dim"], sizes["num_layers"]
    return [X] + [H] * (L - 1)


def init(sizes: dict, key) -> dict:
    """Weights for ``sizes`` from one key, in one jitted call on the default
    device: per layer w (in, 3H), u (H, 3H), b (3H,); a head w (H, C), b (C,).
    Biases are drawn too (not zero), so a path that drops one shows."""
    H, C = sizes["hidden_dim"], sizes["num_classes"]
    ins = layer_inputs(sizes)

    def make(key):
        ks = iter(jax.random.split(key, 3 * len(ins) + 2))
        cells = [{"w": jax.random.normal(next(ks), (d, 3 * H)) / np.sqrt(d),
                  "u": jax.random.normal(next(ks), (H, 3 * H)) / np.sqrt(H),
                  "b": 0.1 * jax.random.normal(next(ks), (3 * H,))}
                 for d in ins]
        head = {"w": jax.random.normal(next(ks), (H, C)) / np.sqrt(H),
                "b": 0.1 * jax.random.normal(next(ks), (C,))}
        return {"cells": cells, "head": head}

    return jax.jit(make)(key)


def _cell(p, h, x, precision):
    H = h.shape[-1]
    u = p["u"]
    xz, xr, xh = jnp.split(_mm(x, p["w"], precision) + p["b"], 3, axis=-1)
    z = jax.nn.sigmoid(xz + _mm(h, u[:, :H], precision))
    r = jax.nn.sigmoid(xr + _mm(h, u[:, H:2 * H], precision))
    ht = jnp.tanh(xh + _mm(r * h, u[:, 2 * H:], precision))
    return (1.0 - z) * h + z * ht


def logits(params: dict, xs, first: int, precision: str = "highest"):
    """xs (N, T, X) feature sequences, every step live -> class logits after
    each of steps ``first``..T-1, shape (N, T - first, C): the logits a
    served request reads once it has consumed xs[:, :t + 1]."""
    H = params["cells"][0]["u"].shape[0]
    N = xs.shape[0]

    def step(hs, x):
        new = []
        for p, h in zip(params["cells"], hs):
            x = _cell(p, h, x, precision)
            new.append(x)
        return tuple(new), _mm(x, params["head"]["w"], precision) \
            + params["head"]["b"]

    h0 = tuple(jnp.zeros((N, H), jnp.float32) for _ in params["cells"])
    _, out = jax.lax.scan(step, h0, jnp.swapaxes(xs, 0, 1))
    return jnp.swapaxes(out[first:], 0, 1)


def states(params: dict, xs, lengths, precision: str = "highest"):
    """Every layer's state after the first ``lengths[i]`` steps of row i
    of ``xs`` (N, T, X): shape (L, N, H)."""
    H = params["cells"][0]["u"].shape[0]
    N, T = xs.shape[:2]

    def step(hs, tx):
        t, x = tx
        live = (t < lengths)[:, None]
        new = []
        for p, h in zip(params["cells"], hs):
            x = _cell(p, h, x, precision)
            new.append(jnp.where(live, x, h))
        return tuple(new), None

    h0 = tuple(jnp.zeros((N, H), jnp.float32) for _ in params["cells"])
    hs, _ = jax.lax.scan(step, h0, (jnp.arange(T), jnp.swapaxes(xs, 0, 1)))
    return jnp.stack(hs)
