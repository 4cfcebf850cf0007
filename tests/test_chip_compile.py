"""Compiles of the serving path's kernels and decode step for a described
TPU v5e, at yi-6b's published widths (and the attention kernel at
Qwen2.5-14B's).

Nothing runs: the TPU compiler, which is installed with jax, compiles for
a chip that is described and not attached.  It refuses what interpret
mode accepts, such as a block shape the chip cannot tile, so these tests
guard the chip path without one.  This is the only file that describes
the topology; see the fixture for why it is made there and nowhere else.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import codecs
from repro.configs.registry import get_arch
from repro.kernels import _backend, gbdi_codec
from repro.kernels.bdi_compress import bdi_compress_kv
from repro.kernels.paged_attention import paged_attention_tail
from repro.models import transformer
from repro.serving import engine as E

YI = get_arch("yi-6b")
B, KVH, D, PMAX = 8, YI.n_kv_heads, YI.head_dim, 64
G = YI.n_heads // YI.n_kv_heads


@pytest.fixture(scope="module")
def topo():
    """The v5e topology, described inside a test and never at import:
    only one process at a time may load the TPU library, and the
    xdist worker that is given this file is the one that does.  The
    persistent compilation cache is off meanwhile, since an entry
    written for a described chip cannot be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        prev = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", prev)
            cc.reset_cache()


@pytest.fixture(scope="module")
def spec(topo):
    """ShapeDtypeStruct factory placed on one described chip."""
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def _on_chip(tree, spec):
    return jax.tree.map(lambda a: spec(a.shape, a.dtype), tree)


def _compile_paged_attention_tail(spec, b, kvh, g, pmax, page=16):
    pages = _on_chip(jax.eval_shape(
        lambda: codecs.get("bdi").init_pools(256, kvh, page, D)), spec)
    fn = jax.jit(lambda *a: paged_attention_tail(*a, interpret=False))
    fn.lower(spec((b, kvh, g, D), jnp.float32), pages,
             spec((b, pmax), jnp.int32), spec((b,), jnp.int32),
             spec((b, kvh, page, D), jnp.float32),
             spec((b, kvh, page, D), jnp.float32),
             spec((b,), jnp.int32)).compile()


def test_paged_attention_tail_compiles(spec):
    _compile_paged_attention_tail(spec, B, KVH, G, PMAX)


def test_paged_attention_tail_compiles_at_qwen_widths(spec):
    """Qwen2.5-14B's heads: GQA group 5, which is no multiple of the 8
    sublanes, 8 KV heads, and the long-decode cell's 512-page table."""
    qwen = get_arch("qwen2.5-14b")
    _compile_paged_attention_tail(spec, 4, qwen.n_kv_heads,
                                  qwen.n_heads // qwen.n_kv_heads, 512)


def test_bdi_compress_kv_compiles(spec):
    rows = 64 * KVH * 8
    fn = jax.jit(lambda x: bdi_compress_kv(x, interpret=False))
    fn.lower(spec((rows, D), jnp.float32)).compile()


@pytest.mark.parametrize("page", [8, 16])
def test_gbdi_codec_compiles(spec, page):
    rpp, pages = KVH * page, 64
    n = pages * rpp
    jax.jit(lambda x: gbdi_codec.gbdi_compress(
        x, rows_per_page=rpp, interpret=False)).lower(
            spec((n, D), jnp.float32)).compile()
    jax.jit(lambda d, b, i, s: gbdi_codec.gbdi_decompress(
        d, b, i, s, rows_per_page=rpp, interpret=False)).lower(
            spec((n, D), jnp.int8), spec((pages, gbdi_codec.K_BASES),
                                          jnp.float32),
            spec((n, 1), jnp.int8), spec((n, 1), jnp.float32)).compile()


def test_decode_step_compiles_with_fused_kernel(spec, monkeypatch):
    """A 2-layer, full-width decode step with the fused BDI attention
    kernel: the kernel must survive into the compiled step."""
    # the step reaches the kernel through the backend default, which the
    # CPU process running this test would resolve to interpret mode
    monkeypatch.setattr(_backend, "default_interpret", lambda: False)
    cfg = dataclasses.replace(YI, n_layers=2)
    codec, page = codecs.get("bdi"), 8
    params = _on_chip(jax.eval_shape(
        transformer.init_params, cfg, jax.random.PRNGKey(0)), spec)
    pools = _on_chip(jax.eval_shape(
        lambda: codec.init_pools(512 * cfg.n_layers, KVH, page, D)), spec)
    tail = spec((cfg.n_layers, B, KVH, page, D), jnp.float32)
    i32 = spec((B,), jnp.int32)
    compiled = E._decode_step.lower(
        params, pools, tail, tail, spec((cfg.n_layers, B, PMAX), jnp.int32),
        i32, i32, i32, i32, spec((B,), jnp.bool_),
        cfg=cfg, codec=codec, use_fused=True).compile()
    assert "tpu_custom_call" in compiled.as_text()
