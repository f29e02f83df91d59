"""Per-architecture smoke tests: reduced variant of each assigned family,
one forward/train step + one prefill->decode step on CPU; output shapes and
no-NaN assertions (deliverable f)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import REGISTRY, get_config
from repro.models import model as M
from repro.models.param import count_params, materialize


ARCHS = sorted(REGISTRY)


def make_batch(cfg, key, batch=2, seq=32):
    ks = jax.random.split(key, 3)
    out = {}
    if cfg.input_mode == "tokens":
        out["tokens"] = jax.random.randint(ks[0], (batch, seq), 0, cfg.vocab)
        total = seq
    elif cfg.input_mode == "embeds":
        out["embeds"] = 0.1 * jax.random.normal(
            ks[0], (batch, seq, cfg.d_model))
        total = seq
    else:  # multimodal: text tokens + stubbed patch embeds
        n_img = cfg.image_tokens
        out["tokens"] = jax.random.randint(
            ks[0], (batch, seq - n_img), 0, cfg.vocab)
        out["image_embeds"] = 0.1 * jax.random.normal(
            ks[1], (batch, n_img, cfg.d_model))
        total = seq
    labels = jax.random.randint(ks[2], (batch, total), 0, cfg.vocab)
    if cfg.input_mode == "multimodal":
        labels = labels.at[:, :cfg.image_tokens].set(-100)
    out["labels"] = labels
    return out


@pytest.fixture(scope="module")
def rng():
    return jax.random.PRNGKey(0)


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_smoke(arch, rng):
    cfg = get_config(arch, smoke=True)
    assert cfg.d_model <= 512 and cfg.n_layers <= 2
    defs = M.model_defs(cfg)
    params = materialize(defs, rng)
    batch = make_batch(cfg, rng)

    def loss_fn(p):
        loss, metrics = M.forward_train(p, cfg, batch, remat=True)
        return loss, metrics

    (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
    assert np.isfinite(float(loss)), f"{arch}: loss is not finite"
    for k, v in metrics.items():
        assert np.isfinite(float(v)), f"{arch}: metric {k} not finite"
    flat = jax.tree.leaves(grads)
    assert all(np.isfinite(np.asarray(g)).all() for g in flat), \
        f"{arch}: NaN/inf in grads"
    # every parameter must receive a gradient signal somewhere
    total = sum(float(jnp.abs(g).sum()) for g in flat)
    assert total > 0


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_then_decode(arch, rng):
    cfg = get_config(arch, smoke=True)
    defs = M.model_defs(cfg)
    params = materialize(defs, rng)
    batch, seq = 2, 32
    b = make_batch(cfg, rng, batch=batch, seq=seq)
    b.pop("labels")
    cache_len = 48
    logits, caches, node_losses, next_pos = M.prefill(
        params, cfg, b, cache_len)
    assert logits.shape == (batch, cfg.vocab)
    assert node_losses.shape == (batch, cfg.n_ramps + 1)
    assert np.isfinite(np.asarray(logits)).all()
    assert np.isfinite(np.asarray(node_losses)).all()
    assert (np.asarray(node_losses) >= 0).all()
    assert (np.asarray(node_losses) <= 1.0 + 1e-5).all()

    tok = jnp.argmax(logits, axis=-1)
    step_batch = ({"tokens": tok} if cfg.input_mode != "embeds"
                  else {"embeds": 0.1 * jax.random.normal(
                      rng, (batch, cfg.d_model))})
    logits2, caches2, nl2 = M.decode_step(params, cfg, step_batch, caches,
                                          next_pos)
    assert logits2.shape == (batch, cfg.vocab)
    assert nl2.shape == (batch, cfg.n_ramps + 1)
    assert np.isfinite(np.asarray(logits2)).all()
    # caches keep their shapes
    for c_old, c_new in zip(jax.tree.leaves(caches), jax.tree.leaves(caches2)):
        assert c_old.shape == c_new.shape


@pytest.mark.parametrize("arch", ARCHS)
def test_full_config_instantiates_abstractly(arch):
    """FULL configs are exercised via ShapeDtypeStruct only (no alloc)."""
    from repro.models.param import abstract
    cfg = get_config(arch, smoke=False)
    defs = M.model_defs(cfg)
    ab = abstract(defs)
    n = count_params(defs)
    assert n > 0
    # spot-check parameter counts are in the right ballpark (20% of spec)
    expected = {
        "qwen3-4b": 4.0e9, "qwen3-14b": 14.8e9, "granite-3-2b": 2.6e9,
        "mamba2-130m": 1.3e8, "starcoder2-3b": 3.0e9,
        "musicgen-large": 2.5e9, "phi-3-vision-4.2b": 4.2e9,
        "phi3.5-moe-42b-a6.6b": 42e9, "deepseek-v2-lite-16b": 16e9,
        # one chip's share: 8 of 64 routed experts per MoE layer
        "deepseek-v2-lite-ep8": 3.1e9,
        "hymba-1.5b": 1.7e9, "paper-ee-100m": 1.6e8,
    }[arch]
    assert 0.55 * expected < n < 1.6 * expected, (arch, n, expected)
