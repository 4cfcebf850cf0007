"""Per-architecture smoke tests (reduced configs) + decode consistency.

Every assigned arch: instantiate a REDUCED same-family config, run one
forward/loss step on CPU, assert output shapes + finite values; then run
prefill + decode_step and check the decode path agrees with the full
forward on the next-token logits.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ShapeConfig
from repro.configs.registry import ARCHS, get_arch
from repro.models import frontends, transformer
from repro.models.api import get_model

SMOKE_SHAPE = ShapeConfig("smoke", seq_len=16, global_batch=2, kind="train")

ARCH_NAMES = sorted(ARCHS)


def _smoke_cfg(name):
    return get_arch(name).reduced()


@pytest.fixture(scope="module")
def smoke_setups():
    out = {}
    for name in ARCH_NAMES:
        cfg = _smoke_cfg(name)
        model = get_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        batch = frontends.make_batch(cfg, SMOKE_SHAPE, jax.random.PRNGKey(1))
        out[name] = (cfg, model, params, batch)
    return out


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_forward_shapes_and_finite(smoke_setups, name):
    cfg, model, params, batch = smoke_setups[name]
    logits = model.forward(params, batch)
    assert logits.shape == (2, SMOKE_SHAPE.seq_len, cfg.vocab)
    assert bool(jnp.isfinite(logits).all())


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_loss_finite_and_reasonable(smoke_setups, name):
    cfg, model, params, batch = smoke_setups[name]
    loss = model.loss(params, batch)
    assert np.isfinite(float(loss))
    # random init => loss near ln(vocab)
    assert 0.2 * np.log(cfg.vocab) < float(loss) < 3 * np.log(cfg.vocab)


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_grads_exist_and_finite(smoke_setups, name):
    cfg, model, params, batch = smoke_setups[name]
    grads = jax.grad(lambda p: model.loss(p, batch))(params)
    flat = jax.tree.leaves(grads)
    assert all(bool(jnp.isfinite(g).all()) for g in flat)
    assert any(float(jnp.abs(g).max()) > 0 for g in flat)


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_decode_step_runs(smoke_setups, name):
    cfg, model, params, batch = smoke_setups[name]
    b = 2
    kwargs = {"enc_len": 8} if cfg.is_encdec else {}
    cache = model.init_cache(b, 32, **kwargs)
    tok = jnp.array([1, 2], jnp.int32)
    logits, cache2 = model.decode_step(params, cache, tok, jnp.int32(0))
    assert logits.shape == (b, cfg.vocab)
    assert bool(jnp.isfinite(logits).all())
    # cache must actually change
    changed = any(
        float(jnp.abs(a.astype(jnp.float32) - b_.astype(jnp.float32)).max()) > 0
        for a, b_ in zip(jax.tree.leaves(cache), jax.tree.leaves(cache2))
        if a.size)
    assert changed


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_prefill_decode_matches_forward(smoke_setups, name):
    """logits(prefill(tokens[:-1])) ~ logits(forward(tokens))[:, -2]  and
    one decode step after prefill ~ forward's last position."""
    cfg, model, params, batch = smoke_setups[name]
    full_logits = model.forward(params, batch)

    s = SMOKE_SHAPE.seq_len
    if cfg.frontend == "vision":
        cut = {"tokens": batch["tokens"][:, :-1], "embeds": batch["embeds"]}
    elif cfg.is_encdec:
        cut = {"tokens": batch["tokens"][:, :-1],
               "enc_embeds": batch["enc_embeds"]}
    else:
        cut = {"tokens": batch["tokens"][:, :-1]}

    # xlstm's forward uses the parallel quadratic mLSTM form while decode is
    # recurrent: bf16 accumulation-order noise dominates there (the f32 math
    # equivalence is asserted tightly in tests/test_ssm.py).
    tol = 0.1 if cfg.family == "ssm" else 2e-2

    last_logits, cache = model.prefill(params, cut, s)
    np.testing.assert_allclose(np.asarray(last_logits),
                               np.asarray(full_logits[:, -2]),
                               rtol=tol, atol=tol)

    tok = batch["tokens"][:, -1]
    step_logits, _ = model.decode_step(params, cache, tok, jnp.int32(s - 1))
    np.testing.assert_allclose(np.asarray(step_logits),
                               np.asarray(full_logits[:, -1]),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize(
    "name", [n for n in ARCH_NAMES
             if get_model(_smoke_cfg(n)).init.func is transformer.init_params])
def test_jitted_init_matches_eager(name):
    """The jitted transformer init gives the eager init's values, bit for
    bit, leaf by leaf."""
    cfg = _smoke_cfg(name)
    key = jax.random.PRNGKey(0)
    jitted = transformer.init_params(cfg, key)
    with jax.disable_jit():
        eager = transformer.init_params(cfg, key)
    assert jax.tree.structure(jitted) == jax.tree.structure(eager)
    for a, b in zip(jax.tree.leaves(jitted), jax.tree.leaves(eager)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_gemma3_local_global_pattern():
    from repro.models import transformer as T
    cfg = get_arch("gemma3-27b")
    w = T.layer_windows(cfg)
    assert len(w) == 62
    assert (w == 0).sum() == 10          # global layers
    assert (w == 1024).sum() == 52       # local layers
    # pattern: 5 local then 1 global
    assert list(w[:6]) == [1024] * 5 + [0]


def test_xlstm_block_pattern():
    from repro.models import xlstm as X
    cfg = get_arch("xlstm-350m")
    flags = X.layer_is_slstm(cfg)
    assert flags.sum() == 3              # 24 layers, every 8th
    assert flags[7] and flags[15] and flags[23]


def test_window_attention_ignores_far_context():
    """A local-attention arch must be insensitive to tokens outside the
    window (the property long_500k relies on)."""
    cfg = _smoke_cfg("gemma3-27b")
    cfg = dataclasses.replace(cfg, local_ratio=1_000_000, window=4)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    b1 = frontends.make_batch(cfg, SMOKE_SHAPE, jax.random.PRNGKey(1))
    b2 = {**b1, "tokens": b1["tokens"].at[:, 0].set(
        (b1["tokens"][:, 0] + 7) % cfg.vocab)}
    l1 = model.forward(params, b1)
    l2 = model.forward(params, b2)
    # token 0 is outside the window of the last position at every layer
    np.testing.assert_allclose(np.asarray(l1[:, -1]), np.asarray(l2[:, -1]),
                               rtol=1e-5, atol=1e-5)


def test_quant_decode_matches_dense():
    """BDI-compressed KV decode (the LCP bandwidth path) vs exact decode."""
    from repro.models import transformer as T
    cfg = get_arch("yi-6b").reduced()
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    b, s = 2, 12
    toks = jax.random.randint(jax.random.PRNGKey(1), (b, s), 1, cfg.vocab)

    dense = model.init_cache(b, 16)
    quant = T.init_quant_cache(cfg, b, 16)
    for t in range(s):
        ld, dense = model.decode_step(params, dense, toks[:, t],
                                      jnp.int32(t))
        lq, quant = T.decode_step_quant(cfg, params, quant, toks[:, t],
                                        jnp.int32(t))
    # int8 KV is lossy; logits must track closely
    np.testing.assert_allclose(np.asarray(lq), np.asarray(ld),
                               rtol=0.1, atol=0.15)
