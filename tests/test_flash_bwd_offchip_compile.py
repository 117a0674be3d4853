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

import base64
import hashlib
import json
import re

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


def _operands(rows, t, d, sharding, positions=False, dv=None):
    x = jax.ShapeDtypeStruct((1, t, rows, d), jnp.bfloat16,
                             sharding=sharding)
    y = x if dv is None else jax.ShapeDtypeStruct(
        (1, t, rows, dv), jnp.bfloat16, sharding=sharding)
    lse = jax.ShapeDtypeStruct((rows, 8, t), jnp.float32, sharding=sharding)
    pos = jax.ShapeDtypeStruct((t,), jnp.int32, sharding=sharding)
    return (x, x, y, y, lse, y) + ((pos, pos) if positions else ())


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


# (heads x batch, seq, q / k width, v width) where a gradient adds up
# transposed: joyaiflash-s8192-b1, xing4-s4096-b1 (latent attention),
# granite4hmicro-s8192-b1 (64-wide heads)
TWO_WIDTHS = [(32, 8192, 192, 128), (32, 4096, 192, 128),
              (32, 8192, 64, 64)]


@pytest.mark.parametrize("rows,t,d,dv", TWO_WIDTHS)
def test_the_transposed_form_compiles_within_the_vmem_it_asks(
        one_chip, rows, t, d, dv):
    """The call asks for what its reckoning says (a compiler parameter:
    the scoped VMEM of the call) and compiles within it."""
    lowered = jax.jit(_bwd(512)).lower(
        *_operands(rows, t, d, one_chip, dv=dv))
    config = _config(lowered.as_text())
    need = pk._flash_bwd_vmem_bytes(t, d, 512, 512, 2, dv)
    assert config["scoped_memory_configs"][0]["size"] == max(need, 16 << 20)
    text = lowered.compile().as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 1 and "flash_bwd" in calls[0]
    assert calls[0].count(f"bf16[{rows},{t},{d}]") >= 4  # q, k, dq, dk
    assert calls[0].count(f"bf16[{rows},{t},{dv}]") >= 3  # v, dO, dv


# sha256 of the flash_bwd call's Mosaic program at [32, 4096, 128 | 128]
# as it was before any gradient added up transposed, printed without
# locations (the serialized body carries the file and lines it was
# traced from): at whole 128-lane tiles the call stays that program
PARENT_BWD_128 = \
    "12522fe85cc620f2c2a358e1c8190c26b9f9a3f63d5111d19ecb701155ece6de"


def _config(text):
    """The backend config of the lowered text's one Mosaic call."""
    (escaped,) = re.findall(r'backend_config = "([^"]*)"', text)
    return json.loads(re.sub(r"\\([0-9A-Fa-f]{2})",
                             lambda m: chr(int(m.group(1), 16)), escaped))


def _program(config):
    """The Mosaic module of a call's config, printed without locations."""
    from jax._src.interpreters import mlir
    from jaxlib.mlir import ir

    body = base64.b64decode(config["custom_call_config"]["body"])
    ctx = mlir.make_ir_context()
    ctx.allow_unregistered_dialects = True    # the serialized dialect
    with ctx:
        return ir.Module.parse(body).operation.get_asm(
            enable_debug_info=False)


def test_at_whole_lane_tiles_the_call_is_the_parent_s_program(one_chip):
    config = _config(jax.jit(_bwd(512)).lower(
        *_operands(32, 4096, 128, one_chip)).as_text())
    assert hashlib.sha256(_program(config).encode()).hexdigest() \
        == PARENT_BWD_128
    assert config["scoped_memory_configs"][0]["size"] == 16 << 20


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
