"""A Llama-style dense decoder, as the program serves it (``family="dense"``
of ``repro.models.transformer``): RMSNorm with a learned scale, rotary
embedding on interleaved pairs, causal grouped-query attention (query head
h reads KV head h // (H / K)) with an optional QKV bias, SwiGLU MLP, untied
LM head.  Yi is one (``model_type`` ``llama``); Qwen2 is another
(``qwen2.py`` re-exports this module).

The harness finds this module by a configuration's ``model_type`` and
calls only the names below; ``bench/harness/spec.py`` states the contract.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.harness import reference, weights
from bench.harness.reference import Q_BLOCK, lin, mm, rmsnorm, rope
from repro.configs.base import ArchConfig

_EMBED, _HEAD, _FINAL = 100, 101, 102


def dims_of(config: dict) -> dict:
    """The sizes the model is run at, from the configuration file's own
    (Hugging Face) keys."""
    d, h = config["hidden_size"], config["num_attention_heads"]
    return {"n_layers": config["num_hidden_layers"], "d_model": d,
            "n_heads": h, "n_kv_heads": config["num_key_value_heads"],
            "head_dim": config.get("head_dim", d // h),
            "d_ff": config["intermediate_size"],
            "vocab": config["vocab_size"],
            "qkv_bias": bool(config.get("attention_bias", False)),
            "rope_theta": float(config["rope_theta"]),
            "norm_eps": float(config["rms_norm_eps"])}


def arch_config(name: str, d: dict) -> ArchConfig:
    """The program's configuration of the model."""
    return ArchConfig(name=name, family="dense",
                      n_layers=d["n_layers"], d_model=d["d_model"],
                      n_heads=d["n_heads"], n_kv_heads=d["n_kv_heads"],
                      d_ff=d["d_ff"], vocab=d["vocab"],
                      head_dim=d["head_dim"], qkv_bias=d["qkv_bias"],
                      rope_theta=d["rope_theta"], norm_eps=d["norm_eps"])


# ---------------------------------------------------------------------------
# seeded weights, in the program's tree layout
# ---------------------------------------------------------------------------

def layer(dims: dict, key, li) -> dict:
    """Layer ``li``'s parameters."""
    d, h, kv, dh, f = (dims["d_model"], dims["n_heads"], dims["n_kv_heads"],
                       dims["head_dim"], dims["d_ff"])

    def k(i):
        return weights.leaf_key(key, i, li)

    normal = weights.normal
    attn = {"wq": {"w": normal(k(1), (d, h, dh), d ** -0.5)},
            "wk": {"w": normal(k(2), (d, kv, dh), d ** -0.5)},
            "wv": {"w": normal(k(3), (d, kv, dh), d ** -0.5)},
            "wo": {"w": normal(k(4), (h, dh, d), (h * dh) ** -0.5)}}
    if dims["qkv_bias"]:
        attn["wq"]["b"] = normal(k(5), (h, dh), 0.25)
        attn["wk"]["b"] = normal(k(6), (kv, dh), 0.25)
        attn["wv"]["b"] = normal(k(7), (kv, dh), 0.25)
    return {"ln1": {"w": weights.norm(k(8), d)},
            "ln2": {"w": weights.norm(k(9), d)},
            "attn": attn,
            "ffn": {"gate": {"w": normal(k(10), (d, f), d ** -0.5)},
                    "up": {"w": normal(k(11), (d, f), d ** -0.5)},
                    "down": {"w": normal(k(12), (f, d), f ** -0.5)}}}


def outer(dims: dict, key) -> dict:
    """Embedding, final norm and LM head."""
    d, v = dims["d_model"], dims["vocab"]
    return {"embed": {"w": weights.normal(jax.random.fold_in(key, _EMBED),
                                          (v, d), 1.0)},
            "final_norm": {"w": weights.norm(jax.random.fold_in(key, _FINAL),
                                             d)},
            "lm_head": {"w": weights.normal(jax.random.fold_in(key, _HEAD),
                                            (d, v), d ** -0.5)}}


def _model(dims: dict, key) -> dict:
    blocks = jax.vmap(lambda li: layer(dims, key, li))(
        jnp.arange(dims["n_layers"]))
    return dict(outer(dims, key), blocks=blocks)


model = weights.on_device(_model)    # (dims, seed): layers stacked [L, ...]
one_layer = weights.on_device(layer)          # (dims, seed, li)
outer_params = weights.on_device(outer)       # (dims, seed)


# ---------------------------------------------------------------------------
# the plain float32 reference
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("eps", "theta", "precision"))
def block(p, x, *, eps, theta, precision):
    """One decoder layer over one sequence: x f32 [T, D] -> [T, D]."""
    t = x.shape[0]
    pos = jnp.arange(t)
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    a = p["attn"]
    h = rmsnorm(p["ln1"]["w"], x, eps)
    q = rope(lin(a["wq"], h, "td,dhe->the", precision), pos, theta)
    k = rope(lin(a["wk"], h, "td,dke->tke", precision), pos, theta)
    v = lin(a["wv"], h, "td,dke->tke", precision)
    n_h, n_kv, dh = q.shape[1], k.shape[1], q.shape[2]
    q = q.reshape(t, n_kv, n_h // n_kv, dh) / jnp.sqrt(jnp.float32(dh))

    def attend(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK)
        s = mm(qb, k, "qkgd,tkd->kgqt", precision)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(pos[None, None, None, :] <= qpos[None, None, :, None],
                      s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        return mm(w, v, "kgqt,tkd->qkgd", precision)

    ctx = jax.lax.map(attend, jnp.arange(t // Q_BLOCK))
    ctx = ctx.reshape(t, n_h, dh)
    x = x + mm(ctx, a["wo"]["w"], "the,heD->tD", precision)
    h = rmsnorm(p["ln2"]["w"], x, eps)
    f = p["ffn"]
    g = jax.nn.silu(lin(f["gate"], h, "td,df->tf", precision))
    u = lin(f["up"], h, "td,df->tf", precision)
    return x + mm(g * u, f["down"]["w"], "tf,fd->td", precision)


def logits_at(dims: dict, seed: int, seqs: list[list[int]],
              rows: list[list[int]], precision: str = "f32") -> list:
    """Logits [len(rows[i]), V] at positions ``rows[i]`` of each token
    sequence ``seqs[i]``, in float32 (or the float8 control)."""
    eps = dims["norm_eps"]
    return reference.by_layer(
        outer_params(dims, seed), lambda li: one_layer(dims, seed, li),
        dims["n_layers"],
        functools.partial(block, eps=eps, theta=dims["rope_theta"],
                          precision=precision),
        seqs, rows, eps=eps, precision=precision)


# ---------------------------------------------------------------------------
# model operations per token, for the whole step's share of the peak
# ---------------------------------------------------------------------------
# A token costs two operations per matrix parameter it passes (the LM head
# only where logits are made: decode, not prefill) and ``4 * n * H * D``
# per layer for attention over its ``n`` visible positions.

def layer_params(dims: dict) -> int:
    d, h, kv, dh, f = (dims["d_model"], dims["n_heads"], dims["n_kv_heads"],
                       dims["head_dim"], dims["d_ff"])
    return d * h * dh + 2 * d * kv * dh + h * dh * d + 3 * d * f


def decode_flops(ctx: int, dims: dict) -> float:
    """One decode token whose context (itself included) is ``ctx``."""
    lp = layer_params(dims)
    return (2.0 * (dims["n_layers"] * lp + dims["d_model"] * dims["vocab"])
            + dims["n_layers"] * 4.0 * ctx * dims["n_heads"]
            * dims["head_dim"])


def prefill_flops(n: int, pos_sum: int, dims: dict) -> float:
    """``n`` prompt tokens whose 0-based positions sum to ``pos_sum``."""
    lp = layer_params(dims)
    return (2.0 * n * dims["n_layers"] * lp
            + dims["n_layers"] * 4.0 * (pos_sum + n) * dims["n_heads"]
            * dims["head_dim"])
