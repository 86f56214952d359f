"""Pallas kernel validation: shape/dtype sweeps vs the kernels/ref.py oracles
(interpret=True executes the kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rglru_scan import rglru_scan
from repro.models.xlstm import mlstm_chunkwise

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,S,T,H,hd", [
    (1, 64, 64, 1, 32), (2, 100, 100, 3, 32), (1, 33, 129, 2, 64),
    (2, 256, 256, 2, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("window", [None, 17])
def test_flash_attention_sweep(B, S, T, H, hd, dtype, window):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = _rand(ks[0], (B, S, H, hd), dtype)
    k = _rand(ks[1], (B, T, H, hd), dtype)
    v = _rand(ks[2], (B, T, H, hd), dtype)
    got = flash_attention(q, k, v, window=window, block_q=32, block_kv=32)
    want = ref.attention(q.astype(jnp.float32), k.astype(jnp.float32),
                         v.astype(jnp.float32), window=window)
    assert jnp.max(jnp.abs(got.astype(jnp.float32) - want)) < TOL[dtype]


def test_flash_attention_matches_model_xla_path():
    from repro.models.attention import flash_attention as xla_flash
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = _rand(ks[0], (2, 80, 4, 32), jnp.float32)
    k = _rand(ks[1], (2, 80, 4, 32), jnp.float32)
    v = _rand(ks[2], (2, 80, 4, 32), jnp.float32)
    got = flash_attention(q, k, v, block_q=32, block_kv=32)
    xla = xla_flash(q, k, v, block_q=32, block_kv=32)
    assert jnp.max(jnp.abs(got - xla)) < 2e-5


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,W,K,G,hd", [
    (1, 64, 1, 1, 32), (3, 200, 2, 4, 32), (2, 128, 4, 2, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("vector_clen", [False, True])
def test_decode_attention_sweep(B, W, K, G, hd, dtype, vector_clen):
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = _rand(ks[0], (B, 1, K * G, hd), dtype)
    kc = _rand(ks[1], (B, W, K, hd), dtype)
    vc = _rand(ks[2], (B, W, K, hd), dtype)
    clen = (jnp.arange(B, dtype=jnp.int32) * (W // max(B, 1)) + W // 2 - 1
            if vector_clen else jnp.array(W - 1, jnp.int32))
    got = decode_attention(q, kc, vc, clen, q_per_kv=G, block_w=64)
    want = ref.decode_attention(q.astype(jnp.float32), kc.astype(jnp.float32),
                                vc.astype(jnp.float32), clen, q_per_kv=G)
    assert jnp.max(jnp.abs(got.astype(jnp.float32) - want)) < TOL[dtype]


def test_decode_attention_window_ring():
    B, W, K, G, hd = 2, 64, 2, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = _rand(ks[0], (B, 1, K * G, hd), jnp.float32)
    kc = _rand(ks[1], (B, W, K, hd), jnp.float32)
    vc = _rand(ks[2], (B, W, K, hd), jnp.float32)
    clen = jnp.array([70, 200], jnp.int32)       # wrapped ring
    got = decode_attention(q, kc, vc, clen, q_per_kv=G, window=24, block_w=32)
    want = ref.decode_attention(q, kc, vc, clen, q_per_kv=G, window=24)
    assert jnp.max(jnp.abs(got - want)) < 2e-5


# ---------------------------------------------------------------------------
# RG-LRU scan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,S,R", [(1, 64, 64), (2, 150, 100), (1, 257, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rglru_scan_sweep(B, S, R, dtype):
    a = jax.nn.sigmoid(_rand(jax.random.PRNGKey(4), (B, S, R), jnp.float32))
    bx = _rand(jax.random.PRNGKey(5), (B, S, R), jnp.float32)
    got, h = rglru_scan(a.astype(dtype), bx.astype(dtype), block_t=32, block_r=64)
    want, hw = ref.rglru_scan(a, bx)
    tol = 5e-6 if dtype == jnp.float32 else 5e-2
    assert jnp.max(jnp.abs(got.astype(jnp.float32) - want)) < tol


def test_rglru_kernel_matches_associative_scan_path():
    """The model's associative-scan path == the kernel's sequential path."""
    from repro.models.rglru import rglru_scan as assoc_path
    a = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(6), (2, 96, 64)))
    bx = jax.random.normal(jax.random.PRNGKey(7), (2, 96, 64))
    got, _ = rglru_scan(a, bx, block_t=32, block_r=64)

    def op(l, r):
        (al, bl), (ar, br) = l, r
        return al * ar, ar * bl + br
    _, want = jax.lax.associative_scan(op, (a, bx), axis=1), None
    aa, hh = jax.lax.associative_scan(op, (a, bx), axis=1)
    assert jnp.max(jnp.abs(got - hh)) < 1e-4


# ---------------------------------------------------------------------------
# mLSTM chunkwise: the model's XLA form on every platform (no Pallas kernel)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,S,H,hd,chunk", [
    (1, 32, 1, 32, 8), (2, 96, 2, 32, 32), (1, 100, 2, 64, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mlstm_chunk_sweep(B, S, H, hd, chunk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(8), 5)
    q = _rand(ks[0], (B, S, H, hd), dtype) * 0.5
    k = _rand(ks[1], (B, S, H, hd), dtype) * 0.5
    v = _rand(ks[2], (B, S, H, hd), dtype)
    ig = _rand(ks[3], (B, S, H), jnp.float32)
    fg = _rand(ks[4], (B, S, H), jnp.float32) + 2.0
    got, state = mlstm_chunkwise(q, k, v, ig, fg, chunk=chunk)
    want, want_state = ref.mlstm(q.astype(jnp.float32), k.astype(jnp.float32),
                                 v.astype(jnp.float32), ig, fg)
    tol = 2e-5 if dtype == jnp.float32 else 5e-2
    assert jnp.max(jnp.abs(got.astype(jnp.float32) - want)) < tol
    for a, b in zip(state, want_state):         # the carried (C, n, m)
        assert jnp.max(jnp.abs(a - b)) < 2e-5


# ---------------------------------------------------------------------------
# property-based: invariants under random shapes
# ---------------------------------------------------------------------------


@settings(deadline=None, max_examples=12)
@given(S=st.integers(8, 80), hd=st.sampled_from([16, 32]),
       window=st.one_of(st.none(), st.integers(4, 40)))
def test_flash_attention_property(S, hd, window):
    ks = jax.random.split(jax.random.PRNGKey(S * 31 + hd), 3)
    q = _rand(ks[0], (1, S, 2, hd), jnp.float32)
    k = _rand(ks[1], (1, S, 2, hd), jnp.float32)
    v = _rand(ks[2], (1, S, 2, hd), jnp.float32)
    got = flash_attention(q, k, v, window=window, block_q=16, block_kv=16)
    want = ref.attention(q, k, v, window=window)
    assert jnp.max(jnp.abs(got - want)) < 3e-5


# ---------------------------------------------------------------------------
# kernel routing: on a TPU the model takes the kernels; the kernel path must
# equal the XLA path END TO END (steered here onto the interpreted kernels)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ["qwen2.5-3b", "mixtral-8x22b",
                                  "recurrentgemma-9b"])
def test_kernel_path_model_parity(name, monkeypatch):
    from repro import kernels
    from repro.configs.registry import ARCHS
    from repro.models import Model
    from repro.models import transformer as tfm
    cfg = ARCHS[name].reduced(dtype="float32", param_dtype="float32")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, cfg.vocab_size)
    batch = model.make_batch(tok)
    ref_logits, _, _ = tfm.forward_logits(params, batch, cfg, mode="train")
    monkeypatch.setattr(kernels, "on_tpu", lambda: True)
    got_logits, _, _ = tfm.forward_logits(params, batch, cfg, mode="train")
    assert float(jnp.max(jnp.abs(got_logits - ref_logits))) < 3e-3
