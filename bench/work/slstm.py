"""Operations and bytes of the sLSTM stack's served work, from its shapes.

The counts of ``gru.py`` with the sLSTM's four gate columns (4H: z, i,
f, o) and its four state leaves per layer (c, n, m, h). Counts are of the
work that was served, whatever implements it: live rows and real prompt
steps only, never a padded bucket or an empty slot. A multiply-add is two
operations; elementwise gate math (the exponentials and the stabilizer)
is not counted. Bytes are float32 and count each weight once per call and
each row's inputs and outputs once.
"""
from __future__ import annotations

F32 = 4
GATES = 4                    # gate columns per hidden unit
LEAVES = 4                   # state leaves per layer


def _dims(sizes: dict):
    X, H = sizes["input_dim"], sizes["hidden_dim"]
    return X, H, sizes["num_layers"], sizes["num_classes"]


def model_flops_per_step(sizes: dict) -> int:
    """One served step of one row through the whole model: every layer's
    input and recurrent products and the classifier head."""
    X, H, L, C = _dims(sizes)
    G = GATES * H
    ins = X * G + (L - 1) * H * G
    return 2 * (ins + L * H * G + H * C)


def head_flops(sizes: dict) -> int:
    X, H, L, C = _dims(sizes)
    return 2 * H * C


def prefill_model_flops(sizes: dict, rows: int, steps: int) -> int:
    """A prefill of ``rows`` live rows over ``steps`` live row-steps: the
    head runs once per row, on its last state."""
    return (steps * (model_flops_per_step(sizes) - head_flops(sizes))
            + rows * head_flops(sizes))


def _recurrent_flops_per_step(sizes: dict) -> int:
    """What the fused kernels compute per row-step: every layer's U
    product and the deep layers' input products (layer 0's input
    projection and the head run outside the kernels)."""
    X, H, L, C = _dims(sizes)
    G = GATES * H
    return 2 * (L * H * G + (L - 1) * H * G)


def _kernel_weight_bytes(sizes: dict) -> int:
    X, H, L, C = _dims(sizes)
    G = GATES * H
    return F32 * (L * H * G + (L - 1) * H * G + L * G)


def decode_kernel(sizes: dict, calls: int, rows: int) -> tuple:
    """(flops, bytes) of ``calls`` fused decode steps that served ``rows``
    live row-steps in all: per row the layer-0 projection (4H) in, every
    layer's four state leaves in and out."""
    X, H, L, C = _dims(sizes)
    flops = rows * _recurrent_flops_per_step(sizes)
    nbytes = (calls * _kernel_weight_bytes(sizes)
              + rows * F32 * (GATES * H + 2 * LEAVES * L * H))
    return flops, nbytes


def sequence_kernel(sizes: dict, calls: int, rows: int, steps: int) -> tuple:
    """(flops, bytes) of ``calls`` fused sequence (prefill) kernels that
    served ``rows`` live rows over ``steps`` live row-steps in all: per
    step the layer-0 projection (4H) in and the last layer's h out, per
    row every layer's four initial and four final state leaves."""
    X, H, L, C = _dims(sizes)
    flops = steps * _recurrent_flops_per_step(sizes)
    nbytes = (calls * _kernel_weight_bytes(sizes)
              + steps * F32 * (GATES * H + H)
              + rows * F32 * 2 * LEAVES * L * H)
    return flops, nbytes
