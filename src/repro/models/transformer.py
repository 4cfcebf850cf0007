"""Decoder-only LM assembly for the dense / MoE / MLA families.

Covers: yi-6b/9b, qwen2.5-14b (QKV bias), gemma3-27b (5:1 local:global),
internvl2-76b (vision-stub), deepseek-v2-lite (MLA + MoE), arctic-480b
(MoE + dense residual).

Structure: per-layer params are stacked [L, ...] and the block runs under
``lax.scan`` with per-layer remat, so the HLO stays O(1) in depth.  The 5:1
local:global pattern scans cleanly because the per-layer window is a traced
scalar; decode keeps *two* cache pools — ring buffers (window W) for local
layers and full-length buffers for global layers — selected per layer with
``lax.cond`` (DESIGN.md: this is what makes long_500k cache sizes sane).
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.distributed import axes as AX
from repro.distributed.axes import DP, MODEL, shard

from . import attention as A
from . import layers as L
from . import moe as M


# ---------------------------------------------------------------------------
# Layer pattern helpers
# ---------------------------------------------------------------------------

def layer_windows(cfg: ArchConfig) -> np.ndarray:
    """Per-layer attention window (0 = global)."""
    if cfg.local_ratio <= 0:
        return np.zeros(cfg.n_layers, np.int32)
    w = np.full(cfg.n_layers, cfg.window, np.int32)
    # every (ratio+1)-th layer is global (gemma3: 5 local then 1 global)
    w[cfg.local_ratio::cfg.local_ratio + 1] = 0
    return w


def cache_slots(cfg: ArchConfig) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(is_global [L], slot_id [L], counts (n_global, n_local))."""
    wins = layer_windows(cfg)
    is_global = wins == 0
    slot = np.zeros(cfg.n_layers, np.int32)
    slot[is_global] = np.arange(is_global.sum())
    slot[~is_global] = np.arange((~is_global).sum())
    return is_global, slot, (int(is_global.sum()), int((~is_global).sum()))


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _init_block(cfg: ArchConfig, key) -> dict:
    ka, kf, kn1, kn2 = jax.random.split(key, 4)
    p: dict[str, Any] = {
        "ln1": L.init_rmsnorm(cfg.d_model),
        "ln2": L.init_rmsnorm(cfg.d_model),
    }
    if cfg.attn_kind == "mla":
        p["attn"] = A.init_mla(ka, cfg.d_model, cfg.n_heads,
                               cfg.kv_lora_rank, cfg.qk_nope_dim,
                               cfg.qk_rope_dim, cfg.v_head_dim)
    else:
        p["attn"] = A.init_gqa(ka, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                               cfg.head_dim, bias=cfg.qkv_bias)
    if cfg.is_moe:
        p["ffn"] = M.init_moe(kf, cfg.d_model, cfg.d_ff_expert,
                              cfg.n_experts, cfg.n_shared_experts)
        if cfg.moe_dense_residual:
            p["dense_ffn"] = L.init_mlp(jax.random.fold_in(kf, 1),
                                        cfg.d_model, cfg.d_ff)
    else:
        p["ffn"] = L.init_mlp(kf, cfg.d_model, cfg.d_ff)
    return p


@functools.partial(jax.jit, static_argnums=0)
def init_params(cfg: ArchConfig, key) -> dict:
    """Random params, jitted: run eagerly, the vmapped init holds several
    f32 copies of each stacked [L, ...] weight at once (about 19.7 GB
    for yi-6b), where the compiled init needs no temporaries.  The
    values are bit-identical to the eager init's."""
    ke, kb, kh = jax.random.split(key, 3)
    layer_keys = jax.random.split(kb, cfg.n_layers)
    blocks = jax.vmap(lambda k: _init_block(cfg, k))(layer_keys)
    return {
        "embed": L.init_embed(ke, cfg.vocab, cfg.d_model),
        "blocks": blocks,
        "final_norm": L.init_rmsnorm(cfg.d_model),
        "lm_head": L.init_lm_head(kh, cfg.d_model, cfg.vocab),
    }


# ---------------------------------------------------------------------------
# Block forward (full sequence)
# ---------------------------------------------------------------------------

def _ffn_apply(cfg: ArchConfig, bp: dict, h: jax.Array) -> jax.Array:
    if cfg.is_moe:
        y = M.moe_ffn(bp["ffn"], h, top_k=cfg.top_k, n_experts=cfg.n_experts,
                      capacity_factor=cfg.capacity_factor)
        if cfg.moe_dense_residual:
            y = y + L.mlp(bp["dense_ffn"], h)
        return y
    return L.mlp(bp["ffn"], h)


def _block_forward(cfg: ArchConfig, bp: dict, x: jax.Array,
                   positions: jax.Array, window: jax.Array) -> jax.Array:
    h = L.rmsnorm(bp["ln1"], x, cfg.norm_eps)
    if cfg.attn_kind == "mla":
        attn = A.mla_forward(bp["attn"], h, positions, cfg.qk_nope_dim,
                             cfg.qk_rope_dim, cfg.rope_theta)
    else:
        attn = A.gqa_forward(bp["attn"], h, positions, window=window,
                             theta=cfg.rope_theta)
    x = x + attn
    h = L.rmsnorm(bp["ln2"], x, cfg.norm_eps)
    return x + _ffn_apply(cfg, bp, h)


def _embed_inputs(cfg: ArchConfig, params: dict, batch: dict) -> jax.Array:
    x = L.embed(params["embed"], batch["tokens"])
    if cfg.n_frontend_embeds and "embeds" in batch:
        x = jnp.concatenate([batch["embeds"].astype(x.dtype), x], axis=1)
    return shard(x, DP, None, None)


def _hidden(cfg: ArchConfig, params: dict, batch: dict,
            remat: bool = True) -> jax.Array:
    x = _embed_inputs(cfg, params, batch)
    s = x.shape[1]
    positions = jnp.arange(s, dtype=jnp.int32)
    wins = jnp.asarray(layer_windows(cfg))

    def body(x, xs):
        bp, w = xs
        x = AX.shard_seq(x)
        return _block_forward(cfg, bp, x, positions, w), None

    if remat:
        body = jax.checkpoint(body,
                              policy=jax.checkpoint_policies.nothing_saveable)
    x, _ = jax.lax.scan(body, x, (params["blocks"], wins))
    return L.rmsnorm(params["final_norm"], x, cfg.norm_eps)


def forward(cfg: ArchConfig, params: dict, batch: dict,
            remat: bool = True) -> jax.Array:
    """Teacher-forcing forward -> logits [B, S, V]."""
    logits = L.lm_logits(params["lm_head"], _hidden(cfg, params, batch,
                                                    remat))
    return shard(logits, DP, None, MODEL)


def loss_fn(cfg: ArchConfig, params: dict, batch: dict) -> jax.Array:
    x = _hidden(cfg, params, batch)
    return L.chunked_cross_entropy(params["lm_head"], x, batch["targets"],
                                   batch.get("loss_mask"))


# ---------------------------------------------------------------------------
# Decode caches
# ---------------------------------------------------------------------------

class DecodeCache(NamedTuple):
    full_k: jax.Array    # [n_global, B, T, K, Dh]
    full_v: jax.Array
    ring_k: jax.Array    # [n_local, B, W, K, Dh]
    ring_v: jax.Array
    mla_c: jax.Array     # [L, B, T, r]          (MLA archs; else size-0)
    mla_kr: jax.Array    # [L, B, T, dr]


class QuantDecodeCache(NamedTuple):
    """BDI-compressed decode cache (all-global GQA archs): int8 deltas +
    per-(token, head) f32 base/scale — the LCP §5.5.1 bandwidth-reduction
    optimization at serve_step level: HBM reads ~halve vs bf16."""
    kd: jax.Array    # int8 [L, B, T, K, Dh]
    kb: jax.Array    # f32  [L, B, T, K]
    ks: jax.Array    # f32  [L, B, T, K]
    vd: jax.Array    # int8 [L, B, T, K, Dh]
    vb: jax.Array    # f32  [L, B, T, K]
    vs: jax.Array    # f32  [L, B, T, K]


def init_quant_cache(cfg: ArchConfig, batch: int, max_len: int
                     ) -> QuantDecodeCache:
    _, _, (n_g, n_l) = cache_slots(cfg)
    assert n_l == 0 and cfg.attn_kind == "gqa", \
        "compressed cache: all-global GQA archs only"
    k, dh = cfg.n_kv_heads, cfg.head_dim
    lyr = cfg.n_layers
    return QuantDecodeCache(
        kd=jnp.zeros((lyr, batch, max_len, k, dh), jnp.int8),
        kb=jnp.zeros((lyr, batch, max_len, k), jnp.float32),
        ks=jnp.ones((lyr, batch, max_len, k), jnp.float32),
        vd=jnp.zeros((lyr, batch, max_len, k, dh), jnp.int8),
        vb=jnp.zeros((lyr, batch, max_len, k), jnp.float32),
        vs=jnp.ones((lyr, batch, max_len, k), jnp.float32),
    )


def _quant_vec(x: jax.Array):
    """Single-base BDI over the last dim: x [..., Dh] -> (i8, base, scale)."""
    from repro.core.bdi_value import _pow2_scale
    base = x[..., 0].astype(jnp.float32)
    r = x.astype(jnp.float32) - base[..., None]
    scale = _pow2_scale(jnp.max(jnp.abs(r), axis=-1), 127.0)
    d = jnp.clip(jnp.round(r / scale[..., None]), -127, 127).astype(jnp.int8)
    return d, base, scale


def decode_step_quant(cfg: ArchConfig, params: dict, cache: QuantDecodeCache,
                      token: jax.Array, t: jax.Array
                      ) -> tuple[jax.Array, QuantDecodeCache]:
    """decode_step over the BDI-compressed KV cache (dequant fused into
    attention; compression of the new token's K/V on the write path)."""
    x = L.embed(params["embed"], token[:, None])
    x = shard(x, DP, None, None)
    idx = jnp.arange(cfg.n_layers)
    xs = (params["blocks"], idx)

    def body(carry, layer):
        x, cch = carry
        bp, i = layer
        h = L.rmsnorm(bp["ln1"], x, cfg.norm_eps)
        q = L.linear(bp["attn"]["wq"], h)
        k_new = L.linear(bp["attn"]["wk"], h)
        v_new = L.linear(bp["attn"]["wv"], h)
        b, _, hh, dh = q.shape
        pos_t = jnp.asarray(t, jnp.int32)[None]
        cos, sin = L.rope_angles(pos_t, dh, cfg.rope_theta)
        q = L.apply_rope(q, cos[None, :, None, :], sin[None, :, None, :])
        k_new = L.apply_rope(k_new, cos[None, :, None, :],
                             sin[None, :, None, :])

        kd_n, kb_n, ks_n = _quant_vec(k_new[:, 0])        # [B, K, *]
        vd_n, vb_n, vs_n = _quant_vec(v_new[:, 0])
        upd = lambda a, v: jax.lax.dynamic_update_slice_in_dim(  # noqa: E731
            a, v[:, None].astype(a.dtype), t, axis=1)
        cch = cch._replace(
            kd=cch.kd.at[i].set(upd(cch.kd[i], kd_n)),
            kb=cch.kb.at[i].set(upd(cch.kb[i], kb_n)),
            ks=cch.ks.at[i].set(upd(cch.ks[i], ks_n)),
            vd=cch.vd.at[i].set(upd(cch.vd[i], vd_n)),
            vb=cch.vb.at[i].set(upd(cch.vb[i], vb_n)),
            vs=cch.vs.at[i].set(upd(cch.vs[i], vs_n)))

        kk = (cch.kd[i].astype(jnp.float32) * cch.ks[i][..., None]
              + cch.kb[i][..., None])                      # [B, T, K, Dh]
        vv = (cch.vd[i].astype(jnp.float32) * cch.vs[i][..., None]
              + cch.vb[i][..., None])
        kh = kk.shape[2]
        qg = q.reshape(b, kh, hh // kh, dh)
        scores = jnp.einsum("bkgd,btkd->bkgt", qg.astype(jnp.float32), kk)
        scores = scores / jnp.sqrt(jnp.float32(dh))
        tidx = jnp.arange(kk.shape[1])
        scores = jnp.where((tidx <= t)[None, None, None, :], scores,
                           jnp.float32(-1e30))
        w = jax.nn.softmax(scores, axis=-1)
        ctx = jnp.einsum("bkgt,btkd->bkgd", w, vv).astype(x.dtype)
        y = A._proj_out(bp["attn"], ctx.reshape(b, 1, hh, dh))
        x = x + y
        h = L.rmsnorm(bp["ln2"], x, cfg.norm_eps)
        return (x + _ffn_apply(cfg, bp, h), cch), None

    (x, cache), _ = jax.lax.scan(body, (x, cache), xs)
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = L.lm_logits(params["lm_head"], x)[:, 0]
    return shard(logits, DP, MODEL), cache


def init_cache(cfg: ArchConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> DecodeCache:
    _, _, (n_g, n_l) = cache_slots(cfg)
    k, dh = cfg.n_kv_heads, cfg.head_dim
    w = max(cfg.window, 1)
    if cfg.attn_kind == "mla":
        return DecodeCache(
            full_k=jnp.zeros((0,), dtype), full_v=jnp.zeros((0,), dtype),
            ring_k=jnp.zeros((0,), dtype), ring_v=jnp.zeros((0,), dtype),
            mla_c=jnp.zeros((cfg.n_layers, batch, max_len, cfg.kv_lora_rank),
                            dtype),
            mla_kr=jnp.zeros((cfg.n_layers, batch, max_len, cfg.qk_rope_dim),
                             dtype))
    return DecodeCache(
        full_k=jnp.zeros((n_g, batch, max_len, k, dh), dtype),
        full_v=jnp.zeros((n_g, batch, max_len, k, dh), dtype),
        ring_k=jnp.zeros((n_l, batch, min(w, max_len), k, dh), dtype),
        ring_v=jnp.zeros((n_l, batch, min(w, max_len), k, dh), dtype),
        mla_c=jnp.zeros((0,), dtype), mla_kr=jnp.zeros((0,), dtype))


def _upd(arr: jax.Array, idx: jax.Array, val: jax.Array) -> jax.Array:
    return jax.lax.dynamic_update_index_in_dim(arr, val.astype(arr.dtype),
                                               idx, axis=0)


def decode_step(cfg: ArchConfig, params: dict, cache: DecodeCache,
                token: jax.Array, t: jax.Array
                ) -> tuple[jax.Array, DecodeCache]:
    """One decode step. token [B] int32; t scalar position. -> logits [B, V]."""
    x = L.embed(params["embed"], token[:, None])
    x = shard(x, DP, None, None)
    is_g, slots, _ = cache_slots(cfg)
    xs = (params["blocks"], jnp.asarray(is_g), jnp.asarray(slots),
          jnp.asarray(layer_windows(cfg)))

    if cfg.attn_kind == "mla":
        def body(carry, layer):
            x, c, kr = carry
            bp, _, slot, _ = layer
            h = L.rmsnorm(bp["ln1"], x, cfg.norm_eps)
            y, c_l, kr_l = A.mla_decode(bp["attn"], h, c[slot], kr[slot], t,
                                        cfg.qk_nope_dim, cfg.qk_rope_dim,
                                        cfg.rope_theta)
            x = x + y
            h = L.rmsnorm(bp["ln2"], x, cfg.norm_eps)
            x = x + _ffn_apply(cfg, bp, h)
            return (x, _upd(c, slot, c_l), _upd(kr, slot, kr_l)), None

        (x, c, kr), _ = jax.lax.scan(body, (x, cache.mla_c, cache.mla_kr), xs)
        cache = cache._replace(mla_c=c, mla_kr=kr)
    else:
        def body(carry, layer):
            x, fk, fv, rk, rv = carry
            bp, g, slot, w = layer
            h = L.rmsnorm(bp["ln1"], x, cfg.norm_eps)

            def global_branch(_):
                y, k2, v2 = A.gqa_decode(bp["attn"], h, fk[slot], fv[slot], t,
                                         ring=False, theta=cfg.rope_theta,
                                         window=0)
                return y, _upd(fk, slot, k2), _upd(fv, slot, v2), rk, rv

            def local_branch(_):
                y, k2, v2 = A.gqa_decode(bp["attn"], h, rk[slot], rv[slot], t,
                                         ring=True, theta=cfg.rope_theta)
                return y, fk, fv, _upd(rk, slot, k2), _upd(rv, slot, v2)

            if cache.ring_k.shape[0] == 0:      # homogeneous global
                y, fk, fv, rk, rv = global_branch(None)
            elif cache.full_k.shape[0] == 0:    # homogeneous local
                y, fk, fv, rk, rv = local_branch(None)
            else:
                y, fk, fv, rk, rv = jax.lax.cond(g, global_branch,
                                                 local_branch, None)
            x = x + y
            h = L.rmsnorm(bp["ln2"], x, cfg.norm_eps)
            x = x + _ffn_apply(cfg, bp, h)
            return (x, fk, fv, rk, rv), None

        carry = (x, cache.full_k, cache.full_v, cache.ring_k, cache.ring_v)
        (x, fk, fv, rk, rv), _ = jax.lax.scan(body, carry, xs)
        cache = cache._replace(full_k=fk, full_v=fv, ring_k=rk, ring_v=rv)

    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = L.lm_logits(params["lm_head"], x)[:, 0]
    return shard(logits, DP, MODEL), cache


def prefill(cfg: ArchConfig, params: dict, batch: dict, max_len: int
            ) -> tuple[jax.Array, DecodeCache]:
    """Run the prompt, building the decode cache. -> (last logits, cache)."""
    x = _embed_inputs(cfg, params, batch)
    b, s, _ = x.shape
    positions = jnp.arange(s, dtype=jnp.int32)
    wins = jnp.asarray(layer_windows(cfg))
    cache = init_cache(cfg, b, max_len)
    is_g, slots, _ = cache_slots(cfg)

    if cfg.attn_kind == "mla":
        def body(x, xs):
            bp, w = xs
            h = L.rmsnorm(bp["ln1"], x, cfg.norm_eps)
            c_l, kr_l = A.mla_prefill_cache(bp["attn"], h, positions, max_len,
                                            cfg.rope_theta)
            attn = A.mla_forward(bp["attn"], h, positions, cfg.qk_nope_dim,
                                 cfg.qk_rope_dim, cfg.rope_theta)
            x = x + attn
            h = L.rmsnorm(bp["ln2"], x, cfg.norm_eps)
            return x + _ffn_apply(cfg, bp, h), (c_l, kr_l)

        x, (cs, krs) = jax.lax.scan(body, x, (params["blocks"], wins))
        cache = cache._replace(mla_c=cs, mla_kr=krs)
    else:
        ring_len = cache.ring_k.shape[2] if cache.ring_k.shape[0] else 0

        def body(x, xs):
            bp, w = xs
            h = L.rmsnorm(bp["ln1"], x, cfg.norm_eps)
            # one K/V projection per layer, shared by cache and attention
            kv = A.gqa_kv(bp["attn"], h, positions, theta=cfg.rope_theta)
            kc, vc = A.gqa_prefill_cache(bp["attn"], h, positions, max_len,
                                         ring=False, theta=cfg.rope_theta,
                                         kv=kv)
            attn = A.gqa_forward(bp["attn"], h, positions, window=w,
                                 theta=cfg.rope_theta, kv=kv)
            x = x + attn
            h = L.rmsnorm(bp["ln2"], x, cfg.norm_eps)
            return x + _ffn_apply(cfg, bp, h), (kc, vc)

        x, (ks, vs) = jax.lax.scan(body, x, (params["blocks"], wins))
        # split per-layer full caches into the two pools
        if cache.full_k.shape[0]:
            gi = jnp.asarray(np.nonzero(is_g)[0])
            cache = cache._replace(full_k=ks[gi], full_v=vs[gi])
        if cache.ring_k.shape[0]:
            li = jnp.asarray(np.nonzero(~is_g)[0])
            take = min(ring_len, s)
            idx = positions[s - take:s] % ring_len
            rk = jnp.zeros_like(cache.ring_k)
            rv = jnp.zeros_like(cache.ring_v)
            # rows s-take:s of the full-layout cache hold the last `take`
            # *positions* (the cache is max_len-long, only s rows written)
            rk = rk.at[:, :, idx].set(ks[li][:, :, s - take:s])
            rv = rv.at[:, :, idx].set(vs[li][:, :, s - take:s])
            cache = cache._replace(ring_k=rk, ring_v=rv)

    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = L.lm_logits(params["lm_head"], x[:, -1:])[:, 0]
    return shard(logits, DP, MODEL), cache
