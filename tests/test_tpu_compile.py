"""Compile-only checks: the fused recurrent kernels at real widths, compiled
for a described TPU v5e chip (no chip attached).

Interpret mode on the CPU checks none of Mosaic's layout rules, so these
cases are what stands between a CPU-green tree and a kernel the chip's
compiler refuses: the masked sequence kernels (the engine's every prefill),
a decode batch that is not a multiple of 8 (B=300), the q8 and sLSTM
kernels, and one row-parallel shard kernel. Each case asserts the compiled
program really holds the kernel (``tpu_custom_call``).

The topology is described inside module fixtures, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.gru_sequence import kernel as gk
from repro.kernels.slstm_cell import kernel as sk

F32, I8 = jnp.float32, jnp.int8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described device cannot be read back from the
    # persistent cache without the chip; keep such compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _gru_decode(B, L=1, H=20):
    return gk.gru_stack_decode_kernel, [
        ((L, B, H), F32), ((B, 3 * H), F32), ((L, H, 3 * H), F32),
        ((max(L - 1, 1), H if L > 1 else 1, 3 * H), F32), ((L, 3 * H), F32)]


def _case(name):
    """-> (callable, [(shape, dtype), ...]) for one named compile case.
    Widths: gru-jet H=20 X=5 L=1, gru-jet-deep H=32 L=3, slstm-jet H=20."""
    T = 16
    if name == "decode_gru_jet_b8":
        return _gru_decode(8)
    if name == "decode_gru_jet_b300":
        return _gru_decode(300)
    if name == "masked_seq_gru_jet":
        B, H = 6, 20
        return gk.gru_sequence_kernel, [
            ((B, H), F32), ((T, B, 3 * H), F32), ((H, 3 * H), F32),
            ((3 * H,), F32), ((T, B), F32)]
    if name == "masked_stack_seq_gru_jet_deep":
        B, H, L = 8, 32, 3
        return gk.gru_stack_sequence_kernel, [
            ((L, B, H), F32), ((T, B, 3 * H), F32), ((L, H, 3 * H), F32),
            ((L - 1, H, 3 * H), F32), ((L, 3 * H), F32), ((T, B), F32)]
    if name == "q8_decode_gru_jet_deep":
        B, H, L = 8, 32, 3
        return gk.gru_stack_decode_q8_kernel, [
            ((L, B, H), F32), ((B, 3 * H), F32), ((L, 3 * H, H), I8),
            ((L, 3 * H), F32), ((L - 1, 3 * H, H), I8), ((L - 1, 3 * H), F32),
            ((L, 3 * H), F32)]
    if name == "slstm_decode":
        B, H = 8, 20
        st = ((1, B, H), F32)
        return sk.slstm_stack_decode_kernel, [
            st, st, st, st, ((B, 4 * H), F32), ((1, H, 4 * H), F32),
            ((1, 1, 4 * H), F32), ((1, 4 * H), F32)]
    if name == "slstm_masked_seq":
        B, H = 6, 20
        st = ((1, B, H), F32)
        return sk.slstm_stack_sequence_kernel, [
            st, st, st, st, ((T, B, 4 * H), F32), ((1, H, 4 * H), F32),
            ((1, 1, 4 * H), F32), ((1, 4 * H), F32), ((T, B), F32)]
    if name == "rowwise_shard_step_gru_jet_deep":
        B, H, Hl = 8, 32, 8                       # H/4 rows per shard
        return gk.gru_rowwise_shard_step, [
            ((B, H), F32), ((B, Hl), F32), ((B, 3 * Hl), F32),
            ((H, 3 * Hl), F32), ((3 * Hl,), F32)]
    raise KeyError(name)


@pytest.mark.parametrize("name", [
    "decode_gru_jet_b8", "decode_gru_jet_b300", "masked_seq_gru_jet",
    "masked_stack_seq_gru_jet_deep", "q8_decode_gru_jet_deep",
    "slstm_decode", "slstm_masked_seq", "rowwise_shard_step_gru_jet_deep"])
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = _case(name)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    call = jax.jit(functools.partial(fn, interpret=False))
    text = call.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
