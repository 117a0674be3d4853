"""The flash backward compiles for a TPU v5e at the operands the
benchmark's cells use, without a chip.

XLA:TPU and Mosaic are installed here and compile — not run — for a
described v5e, so a VMEM overrun of the one backward call (which keeps
a whole row of Q, dO and dQ in VMEM and asks for more than the
compiler's default scope at seq 8192) is found by tier-1 and not on the
chip.  Nothing here is a measurement.  The topology is described inside
a fixture: libtpu belongs to one process at a time, and a module that
loads it when imported breaks collection under several workers.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from horovod_tpu.ops import pallas_kernels as pk


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        described = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps libtpu away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an executable for a described chip cannot be read back from the
    # persistent cache without one: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield described
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _bwd(block, positions=False):
    def bwd(q, k, v, out, lse, g, *pos):
        qpos, kpos = pos if positions else (None, None)
        return pk._flash_bwd(q, k, v, out, lse, g, True,
                             q.shape[-1] ** -0.5, block, block, False,
                             qpos=qpos, kpos=kpos)
    return bwd


def _operands(rows, t, d, sharding, positions=False):
    x = jax.ShapeDtypeStruct((1, t, rows, d), jnp.bfloat16,
                             sharding=sharding)
    lse = jax.ShapeDtypeStruct((rows, 8, t), jnp.float32, sharding=sharding)
    pos = jax.ShapeDtypeStruct((t,), jnp.int32, sharding=sharding)
    return (x, x, x, x, lse, x) + ((pos, pos) if positions else ())


# (heads x batch, seq, head width): lm871m-s1024-b6 (and a chip of dp4),
# lm871m-s4096-b1, nemotron3nano-s8192-b1 after the key/value heads are
# repeated
CELLS = [(96, 1024, 128), (16, 4096, 128), (32, 8192, 128)]


@pytest.mark.parametrize("rows,t,d", CELLS)
def test_the_backward_compiles_at_a_cells_operand(one_chip, rows, t, d):
    text = jax.jit(_bwd(512)).lower(
        *_operands(rows, t, d, one_chip)).compile().as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 1                  # dq, dk and dv of one call
    assert "flash_bwd" in calls[0]
    assert calls[0].count(f"bf16[{rows},{t},{d}]") >= 7   # 4 in, 3 out


def test_the_ring_s_form_compiles_with_positions(one_chip):
    """sp=4 of seq 16k: a 4096-row shard, every block pair masked by
    global positions."""
    jax.jit(_bwd(512, positions=True)).lower(
        *_operands(16, 4096, 128, one_chip, positions=True)).compile()


def test_the_longest_row_served_compiles(one_chip):
    """seq 32,768 at bf16 and a head width of 128: 77 MiB by the
    kernel's own reckoning, under its cap and a v5e's 128 MiB."""
    need = pk._flash_bwd_vmem_bytes(32768, 128, 512, 512, 2)
    assert need <= pk._FLASH_BWD_VMEM_CAP
    jax.jit(_bwd(512)).lower(
        *_operands(2, 32768, 128, one_chip)).compile()


def test_forward_and_backward_are_two_calls_under_their_names(
        one_chip, monkeypatch):
    # the default backend here is the CPU: say TPU, as the chip would
    monkeypatch.setattr(pk, "_on_tpu", lambda: True)

    def loss(q, k, v):
        return pk.flash_attention(q, k, v, causal=True).astype(
            jnp.float32).sum()

    x = jax.ShapeDtypeStruct((1, 4096, 16, 128), jnp.bfloat16,
                             sharding=one_chip)
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x).compile().as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 2
    assert sum("flash_fwd" in ln for ln in calls) == 1
    assert sum("flash_bwd" in ln for ln in calls) == 1


def test_a_row_beyond_the_cap_raises_when_traced():
    """No dense fall-back and no knob: the shape is named."""
    args = _operands(2, 65536, 128, None)
    with pytest.raises(ValueError, match=r"flash attention backward: "
                       r"q\(1, 65536, 2, 128\) bfloat16 keeps a whole row "
                       r"of Q, dO and dQ in VMEM, \d+ MiB at seq 65536; "
                       r"the kernel serves up to 80 MiB"):
        jax.eval_shape(_bwd(512), *args)
