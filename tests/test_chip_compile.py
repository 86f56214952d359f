"""The Pallas kernels compile for a TPU v5e at real widths (qwen2.5-3b's
serving path; recurrentgemma-9b's recurrence).

Each test compiles one kernel for a described (not attached) v5e chip —
what the chip's compiler refuses (unaligned blocks, too much fast memory)
fails here at no chip time — and checks that the program holds the kernel
(``tpu_custom_call``). The topology is described inside a fixture, so no
worker touches the TPU library while collecting; where it cannot be
described, every test here skips.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_decode_attention import paged_decode_attention
from repro.kernels.rglru_scan import rglru_scan
from repro.kernels.spec_scan import accept_len

# qwen2.5-3b serving widths: 4 slots, 16 query / 2 KV heads of 128, a
# 2048-token cache in 16-token pages, 4 draft tokens per verify
B, H, K, HD, W, PAGE, SPEC = 4, 16, 2, 128, 2048, 16, 4


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to compile for
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep it out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _shape(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def _compiles_to_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_flash_attention_prefill_compiles(one_chip):
    S = 1024
    q = _shape(one_chip, (1, S, H, HD))
    _compiles_to_kernel(
        lambda q, k, v: flash_attention(q, k, v, interpret=False), q, q, q)


def test_decode_attention_compiles(one_chip):
    _compiles_to_kernel(
        lambda q, k, v, n: decode_attention(q, k, v, n, q_per_kv=H // K,
                                            interpret=False),
        _shape(one_chip, (B, 1, H, HD)), _shape(one_chip, (B, W, K, HD)),
        _shape(one_chip, (B, W, K, HD)), _shape(one_chip, (B,), jnp.int32))


def test_paged_decode_attention_compiles(one_chip):
    pages = 1 + 2 * B * (W // PAGE)
    pool = _shape(one_chip, (pages, PAGE, K, HD))
    _compiles_to_kernel(
        lambda q, k, v, bt, n: paged_decode_attention(
            q, k, v, bt, n, q_per_kv=H // K, interpret=False),
        _shape(one_chip, (B, 1, H, HD)), pool, pool,
        _shape(one_chip, (B, W // PAGE), jnp.int32),
        _shape(one_chip, (B,), jnp.int32))


def test_spec_accept_scan_compiles(one_chip):
    _compiles_to_kernel(
        lambda a, n: accept_len(a, n, interpret=False),
        _shape(one_chip, (B, SPEC + 1), bool),
        _shape(one_chip, (B,), jnp.int32))


def test_rglru_scan_compiles(one_chip):
    """recurrentgemma-9b's recurrence width, float32 as the model runs it."""
    x = _shape(one_chip, (1, 1024, 4096), jnp.float32)
    _compiles_to_kernel(lambda a, b: rglru_scan(a, b, interpret=False), x, x)
