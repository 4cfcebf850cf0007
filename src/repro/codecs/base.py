"""The :class:`PageCodec` protocol + registry.

The serving stack (``serving/engine.py``, ``serving/reference.py``,
``serving/prefix_cache.py``) is codec-agnostic: every touch of a
compressed KV page goes through a ``PageCodec`` instance.  This is the
code-level realization of the LCP claim that *any* compression
algorithm fits the page framework — the framework needs exactly the
five capabilities below, nothing else:

  * ``init_pools``            — allocate the device-resident page pools
    (an arbitrary pytree whose leaves lead with ``[n_pages]``: one page
    id names one layer's page, so all layers share the pool);
  * ``compress_kv_pages``     — turn exact f32 KV page blocks into the
    codec's compressed form (the batched page-fill path);
  * ``decompress_pages``      — the inverse, used by the gather-dequant
    attention fallback, warm prefix-cache scratch fills, and the oracle;
  * ``page_nbytes``           — **device-side** per-page compressed byte
    accounting: the numbers that feed CAMP preemption values and the
    prefix cache's SIP retention ranking;
  * ``canonical_roundtrip``   — compress-then-decompress, the function
    the canonical-prefix contract is defined against (prefill queries
    attend the roundtrip of completed pages so published page bits are
    pure functions of the token prefix — see serving/prefix_cache.py).

Optionally a codec brings fused kernels (``has_fused_kernels`` +
``paged_attention_tail`` / ``compress_kv_pages_fused``) — BDI's Pallas
pair — and may declare itself ``lossless`` (roundtrip == identity
bit-for-bit), which lets the engines skip the canonical roundtrip in
prefill entirely: canonical and exact values coincide, so the chunk
attends its own scratch and the second masked einsum disappears.

Registry: codecs register one singleton instance under a short name;
``get("bdi")`` / ``resolve(None)`` hand it back.  Singletons matter —
codec instances are jit static arguments, so one shared instance means
one shared trace across every engine (batched and oracle alike).
``REPRO_CODEC`` selects the default (CI runs the serving equivalence
suite under each registered name).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp


class PageCodec:
    """Interface every page codec implements (see the module docstring).

    Shape conventions: KV page blocks are f32 ``[n, KVH, page, D]`` (one
    leading block axis); pool pytree leaves lead with ``[n_pages]``.
    All methods must be jit-traceable — they run inside the engines'
    fused dispatches — and instances must be stateless singletons
    (they are jit *static* arguments).
    """

    name: str = "?"
    #: roundtrip == identity bit-for-bit.  The engines then skip the
    #: canonical roundtrip in prefill (canonical == exact by definition)
    #: and shrink the canonical scratch to zero length.
    lossless: bool = False
    #: codec ships Pallas kernels (fused paged attention + page-fill
    #: compression); engines only route ``use_fused`` to codecs that do.
    has_fused_kernels: bool = False
    #: codec ships a fused page-fill compressor but no fused attention
    #: (e.g. gbdi, adaptive): engines route ``use_fused`` to the publish
    #: path only and keep the gather-dequant attention fallback.
    has_fused_fill: bool = False
    #: ``page_nbytes`` depends only on coarse value structure (quantized
    #: delta widths, zero masks, constants), so it is invariant to
    #: sub-ULP noise in the raw KV input.  Codecs whose sizes read exact
    #: bit patterns (fpc's bf16-exactness classes, and adaptive, which
    #: folds fpc's size into its min) set this False: decode-tail KV is
    #: token-pinned but not bit-pinned across the batched engine and the
    #: op-by-op oracle, so their byte accounting may legitimately differ
    #: by a few bytes per decode-published page.
    ulp_stable_sizes: bool = True

    # -- required ------------------------------------------------------------

    def init_pools(self, n_pages: int, kvh: int, page: int, dh: int):
        """Zero-state page pools: a pytree, leaves [P, ...]."""
        raise NotImplementedError

    def compress_kv_pages(self, k: jax.Array, v: jax.Array):
        """f32 [n, KVH, page, D] x2 -> compressed pages pytree, leaves
        leading [n].  This is the reference (pure-jnp) path; it defines
        the codec's bits."""
        raise NotImplementedError

    def decompress_pages(self, pages) -> tuple[jax.Array, jax.Array]:
        """Compressed pages pytree -> (k, v) f32 [..., KVH, page, D].
        Must broadcast over arbitrary leading dims (the attention
        fallback gathers [S, PMAX]-leading pages)."""
        raise NotImplementedError

    def page_nbytes(self, pages) -> jax.Array:
        """Device-side per-page compressed byte counts, i32 [n]."""
        raise NotImplementedError

    # -- optional ------------------------------------------------------------

    def compress_kv_pages_fused(self, k: jax.Array, v: jax.Array):
        """Fused-kernel compression path (must be bit-exact with
        :meth:`compress_kv_pages`); defaults to the reference path."""
        return self.compress_kv_pages(k, v)

    def paged_attention_tail(self, q, pages, page_table, lengths,
                             tail_k, tail_v, tail_len):
        """Fused decode attention over [compressed pages + f32 tail].
        Only called when ``has_fused_kernels``; codecs without a kernel
        inherit the engines' gather-dequant fallback instead."""
        raise NotImplementedError(f"codec {self.name!r} has no fused "
                                  "attention kernel")

    def page_tags(self, pages) -> jax.Array:
        """Per-page codec-id tags, i32 [n] (Touché-style small tag).

        Single-algorithm codecs are tag 0 everywhere (the default);
        the ``adaptive`` composite overrides this with the per-page
        winning member id, which the engines mirror into the host-side
        ``page_codec_id`` table and the prefix cache's per-entry
        ``codec_ids``."""
        n = jax.tree.leaves(pages)[0].shape[0]
        return jnp.zeros((n,), jnp.int32)

    def canonical_roundtrip(self, k: jax.Array, v: jax.Array
                            ) -> tuple[jax.Array, jax.Array]:
        """compress-then-decompress of [n, KVH, page, D] blocks — the
        canonical-prefix contract's roundtrip function."""
        return self.decompress_pages(self.compress_kv_pages(k, v))

    def __repr__(self) -> str:  # pragma: no cover - debugging nicety
        return f"<PageCodec {self.name}>"


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

_REGISTRY: dict[str, PageCodec] = {}


def register(codec: PageCodec) -> PageCodec:
    """Register a codec singleton under ``codec.name`` (idempotent for
    the same instance; re-registering a name with a new instance is an
    error — engines key jit traces on the instance)."""
    prev = _REGISTRY.get(codec.name)
    assert prev is None or prev is codec, \
        f"codec name {codec.name!r} already registered"
    _REGISTRY[codec.name] = codec
    return codec


def available() -> list[str]:
    return sorted(_REGISTRY)


def get(name: str) -> PageCodec:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown page codec {name!r}; available: "
                       f"{', '.join(available())}") from None


def default_name() -> str:
    """Default codec name: ``REPRO_CODEC`` env var, else ``bdi``."""
    return os.environ.get("REPRO_CODEC", "").strip().lower() or "bdi"


def resolve(spec: str | PageCodec | None = None) -> PageCodec:
    """``None`` -> the ``REPRO_CODEC``/bdi default; a name -> registry
    lookup; an instance -> itself."""
    if spec is None:
        name = default_name()
        try:
            return get(name)
        except KeyError:
            # surface the *env var* in the error: a bad REPRO_CODEC used
            # to bubble up as a bare KeyError from deep inside engine
            # construction, with no hint where the name came from
            raise KeyError(
                f"REPRO_CODEC={name!r} names an unknown page codec; "
                f"registered codecs: {', '.join(available())}") from None
    if isinstance(spec, str):
        return get(spec)
    assert isinstance(spec, PageCodec), spec
    return spec
