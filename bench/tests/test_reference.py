"""The dense architecture's plain reference (``bench/arch/llama.py``)
against the program's own full-sequence forward
(``repro.models.transformer.forward``) at a tiny size, in float32."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.arch import llama
from repro.models import transformer

DIMS = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
        "head_dim": 16, "d_ff": 96, "vocab": 300, "qkv_bias": False,
        "rope_theta": 10000.0, "norm_eps": 1e-5}


@pytest.mark.parametrize("bias", [False, True])
def test_reference_matches_program_forward(bias):
    d = dict(DIMS, qkv_bias=bias)
    seed = 2 ** 31 + 5
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          llama.model(d, seed))
    if bias:
        assert "b" in params["blocks"]["attn"]["wk"]
    toks = np.random.default_rng(0).integers(1, d["vocab"], 40).tolist()
    with jax.default_matmul_precision("highest"):
        want = transformer.forward(llama.arch_config("tiny", d), params,
                                   {"tokens": jnp.asarray([toks])},
                                   remat=False)[0]
    rows = list(range(40))
    got = llama.logits_at(d, seed, [toks], [rows])[0]
    np.testing.assert_allclose(got, np.asarray(want), atol=2e-4, rtol=2e-4)


def test_one_layer_rebuilds_the_stacked_weights():
    d = dict(DIMS, qkv_bias=True)
    full = llama.model(d, 9)
    for li in range(d["n_layers"]):
        one = llama.one_layer(d, 9, li)
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a[li]), np.asarray(b)), full["blocks"], one)
    out = llama.outer_params(d, 9)
    np.testing.assert_array_equal(np.asarray(out["lm_head"]["w"]),
                                  np.asarray(full["lm_head"]["w"]))


def test_control_rounds_to_float8():
    toks = list(range(1, 30))
    a = llama.logits_at(DIMS, 3, [toks], [list(range(29))])[0]
    b = llama.logits_at(DIMS, 3, [toks], [list(range(29))],
                        precision="fp8")[0]
    assert np.abs(a - b).max() > 1e-2


def test_serving_tree_is_the_program_layout():
    d = dict(DIMS, qkv_bias=True)
    ours = jax.tree.structure(llama.model(d, 0))
    theirs = jax.tree.structure(jax.eval_shape(
        transformer.init_params, llama.arch_config("tiny", d),
        jax.random.PRNGKey(0)))
    assert ours == theirs
