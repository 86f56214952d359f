"""The plain float32 reference forward (models/reference.py) against the
serving model's forward and prefill, at reduced widths on the CPU."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs.registry import ARCHS
from repro.models import Model
from repro.models import transformer as tfm
from repro.models.reference import check_supported, reference_logits

DENSE = ["qwen2.5-3b", "mistral-nemo-12b", "granite-3-2b", "chatglm3-6b"]


def _setup(arch, dtype, num_layers=2):
    cfg = ARCHS[arch].reduced(dtype=dtype, param_dtype=dtype, vocab_size=512,
                              num_layers=num_layers)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 48), 0,
                             cfg.vocab_size)
    return cfg, model, params, tok


@pytest.mark.parametrize("arch", DENSE)
def test_reference_matches_float32_forward(arch):
    cfg, model, params, tok = _setup(arch, "float32")
    got, _, _ = tfm.forward_logits(params, model.make_batch(tok), cfg,
                                   mode="train")
    ref = reference_logits(params, tok, cfg)
    assert got.shape == ref.shape
    assert float(jnp.max(jnp.abs(got - ref))) < 1e-4


@pytest.mark.parametrize("arch", ["qwen2.5-3b", "mistral-nemo-12b"])
def test_bucketed_prefill_matches_reference(arch):
    """Right-padded prefill into a cache (the serving path) gives the
    reference logits on the valid prefix."""
    cfg, model, params, tok = _setup(arch, "float32")
    n = 40
    cache = model.init_cache(2, 64)
    logits, _ = model.prefill(params, model.make_batch(tok), cache,
                              length=jnp.int32(n))
    ref = reference_logits(params, tok[:, :n], cfg)
    assert float(jnp.max(jnp.abs(logits[:, :n] - ref))) < 1e-4


def test_bf16_forward_within_reference_tolerance():
    """bf16 weights and activations through 36 layers stay inside the
    agreement bound the chip smoke test holds the served path to."""
    cfg, model, params, tok = _setup("qwen2.5-3b", "bfloat16", num_layers=36)
    got, _, _ = tfm.forward_logits(params, model.make_batch(tok), cfg,
                                   mode="train")
    ref = reference_logits(params, tok, cfg)
    got = got.astype(jnp.float32)[..., :cfg.vocab_size]
    ref = ref[..., :cfg.vocab_size]
    rel = float(jnp.linalg.norm(got - ref) / jnp.linalg.norm(ref))
    top1 = float(jnp.mean(jnp.argmax(got, -1) == jnp.argmax(ref, -1)))
    assert rel < 0.05 and top1 >= 0.9, (rel, top1)


@pytest.mark.parametrize("arch", ["recurrentgemma-9b", "mixtral-8x22b",
                                  "xlstm-350m"])
def test_reference_refuses_other_families(arch):
    with pytest.raises(ValueError):
        check_supported(ARCHS[arch])
