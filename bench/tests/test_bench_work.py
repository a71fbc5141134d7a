"""Operation and byte counts against hand counts."""
import pytest

from harness import roofline, spec

GRU = spec.family_module("work", "gru")


# a three-layer stack of H=32: the counts hold for any depth and width
DEEP = {"input_dim": 5, "hidden_dim": 32, "num_layers": 3, "num_classes": 5}


def _sizes(name):
    return DEEP if name == "deep" else spec.load_cell(f"{name}.bulk").config


@pytest.mark.parametrize("name,flops", [
    # (X*3H + H*3H + H*C) multiply-adds: (5*60 + 20*60 + 20*5) * 2
    ("gru-jet", 3200),
    # (5*96 + 32*96) + 2 * (32*96 + 32*96) + 32*5 multiply-adds, times 2
    ("deep", 32000),
])
def test_model_flops_per_session_step(name, flops):
    assert GRU.model_flops_per_step(_sizes(name)) == flops


def test_decode_kernel_counts_live_rows_and_weights_once():
    s = _sizes("gru-jet")                      # H=20, L=1
    flops, nbytes = GRU.decode_kernel(s, calls=2, rows=8)
    assert flops == 8 * 2 * 20 * 60
    weights = 4 * (20 * 60 + 60)               # U and b, once per call
    assert nbytes == 2 * weights + 8 * 4 * (60 + 2 * 20)


def test_sequence_kernel_counts_real_steps_not_the_bucket():
    s = _sizes("deep")                         # H=32, L=3
    flops, nbytes = GRU.sequence_kernel(s, calls=1, rows=3, steps=57)
    assert flops == 57 * 2 * (3 * 32 * 96 + 2 * 32 * 96)
    weights = 4 * (3 * 32 * 96 + 2 * 32 * 96 + 3 * 96)
    assert nbytes == weights + 57 * 4 * (96 + 32) + 3 * 4 * 2 * 3 * 32


def test_prefill_model_flops_runs_the_head_once_per_row():
    s = _sizes("gru-jet")
    assert GRU.prefill_model_flops(s, rows=2, steps=38) == \
        38 * (3200 - 200) + 2 * 200


def test_roofline_bound_names_the_limit():
    peaks = spec.peaks("TPU v5 lite")
    assert roofline.bound(8240, 8240, peaks)[1] == "memory"
    t, which = roofline.bound(197e12, 1.0, peaks)
    assert which == "compute" and t == pytest.approx(1.0)
