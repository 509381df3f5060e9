"""Flash-decoding Pallas TPU kernel for 1-token queries over a PAGED KV cache.

The serving cache (``repro.nn.cache``) stores keys/values as a pool of
fixed-size pages; a per-slot page table maps logical page ``p`` of sequence
``b`` to a physical page id. This kernel is the split-KV trick from
flash-decoding (Dao et al.) married to paged-attention serving (Kwon et al.,
vLLM):

  * grid = (batch_slot, kv_head, logical_page) — the KV axis is split into
    pages and each page's partial softmax is combined online via the running
    (m, l, acc) logsumexp state in VMEM scratch (pages are the innermost grid
    dimension, so scratch carries across them);
  * the PHYSICAL page to stream into VMEM is computed from the page table via
    ``PrefetchScalarGridSpec`` — the table and the per-slot lengths are
    scalar-prefetched, so the BlockSpec index_map gathers pages straight from
    HBM with no host-side indirection;
  * masking is length-aware: page slots at logical position >= lengths[b]
    (and, for sliding-window layers, <= lengths[b] - window) are masked, so
    RAGGED sequences share one compiled program;
  * GQA-aware: queries arrive grouped (B, KV, G, hd); scores/accumulators are
    fp32 regardless of the (typically bf16) page dtype — the ``repro.precision``
    serving policy is "bf16 KV, fp32 logsumexp".

The kernel attends over *committed* tokens only (logical index < lengths[b]).
The current token's own k/v — which the DB sampler needs both for denoising
probes (not yet committed) and for the commit pass — is folded in afterwards
by ``combine_self`` from the returned (out, lse) partials; that keeps the
kernel free of any append/ordering concerns.

Decode is inference-only: no custom VJP (nothing differentiates through the
serving path). Validated against the gather-based reference in
``repro.nn.cache`` in interpret mode (CPU container); compiled path targets
TPU.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30
# TPU fp32 min sublane count; the GQA group axis is padded up to this so the
# (G, page_size) score tile is alignable. Interpret mode accepts any G.
MIN_GROUP_PAD = 8


def _decode_kernel(*refs, scale: float, page_size: int,
                   n_pages: int, window: Optional[int], quantized: bool):
    if quantized:
        (table_ref, len_ref, ks_ref, vs_ref, q_ref, k_ref, v_ref,
         o_ref, lse_ref, m_ref, l_ref, acc_ref) = refs
    else:
        (table_ref, len_ref, q_ref, k_ref, v_ref,
         o_ref, lse_ref, m_ref, l_ref, acc_ref) = refs
        ks_ref = vs_ref = None
    b = pl.program_id(0)
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[b]
    start = p * page_size

    # pages entirely past the sequence's committed length carry no valid
    # slots — skip their DMA'd tile outright (the mask below would zero them
    # anyway; this saves the MXU work on the ragged tail).
    @pl.when(start < length)
    def _update():
        q = q_ref[0, 0].astype(jnp.float32)            # (G, hd)
        k = k_ref[0, 0].astype(jnp.float32)            # (page_size, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        if quantized:
            # int8 bytes stream from HBM; dequant happens here in-register
            # with this PHYSICAL page's fp32 scale, scalar-prefetched like
            # the page table itself.
            phys = table_ref[b, p]
            k = k * ks_ref[phys]
            v = v * vs_ref[phys]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        idx = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = idx < length
        if window is not None:
            valid &= idx > length - window
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[...]                            # (rows, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        pexp = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(pexp, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            pexp, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(p == n_pages - 1)
    def _finalize():
        l = l_ref[...]
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        # lse of the page partials; a slot with lengths[b]==0 finalizes at
        # ~NEG_INF so combine_self gives it zero weight.
        lse_ref[0, 0] = m_ref[...] + jnp.log(jnp.maximum(l, 1e-30))


def flash_decode(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                 page_table: jax.Array, lengths: jax.Array, *,
                 window: Optional[int] = None,
                 k_scale: Optional[jax.Array] = None,
                 v_scale: Optional[jax.Array] = None,
                 interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Split-KV paged decode attention over committed tokens.

    q:          (B, KV, G, hd) — the single new token's grouped queries
    k_pages/v_pages: (P, KV, page_size, hd) physical page pool (KV-major:
                one head's page is a whole (page_size, hd) tile)
    page_table: (B, n_logical_pages) int32 — physical page id per logical
                page; entries past a sequence's allocation MUST still be
                in-bounds (point them at a reserved page — see nn.cache)
    lengths:    (B,) int32 committed-token counts (mask: idx < lengths[b])
    k_scale/v_scale: per-PHYSICAL-page fp32 dequant scales for an int8 pool
                ((P,) or (P, 1, 1, 1); both given or both None). They are
                scalar-prefetched exactly like the page table and applied
                in-register after the int8 page streams into VMEM.

    Returns ``(out, lse)``: out (B, KV, G, hd) fp32 — softmax-normalized over
    the committed tokens only — and lse (B, KV, G) fp32, the partials'
    logsumexp. Fold in the current token's own k/v with ``combine_self``.
    """
    B, KV, G, hd = q.shape
    psz = k_pages.shape[2]
    n_pages = page_table.shape[1]
    scale = 1.0 / (hd ** 0.5)
    quantized = k_scale is not None
    Gp = max(G, MIN_GROUP_PAD)
    if Gp != G:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, Gp - G), (0, 0)))

    kernel = functools.partial(_decode_kernel, scale=scale, page_size=psz,
                               n_pages=n_pages, window=window,
                               quantized=quantized)
    # with scales, the index_map lambdas receive two extra prefetch refs —
    # keep the unquantized specs verbatim so the bf16 program is unchanged
    if quantized:
        q_map = lambda b, kv, p, tbl, lens, ks, vs: (b, kv, 0, 0)
        kv_map = lambda b, kv, p, tbl, lens, ks, vs: (tbl[b, p], kv, 0, 0)
        lse_map = lambda b, kv, p, tbl, lens, ks, vs: (b, kv, 0, 0)
    else:
        q_map = lambda b, kv, p, tbl, lens: (b, kv, 0, 0)
        kv_map = lambda b, kv, p, tbl, lens: (tbl[b, p], kv, 0, 0)
        lse_map = lambda b, kv, p, tbl, lens: (b, kv, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4 if quantized else 2,
        grid=(B, KV, n_pages),
        in_specs=[
            pl.BlockSpec((1, 1, Gp, hd), q_map),
            pl.BlockSpec((1, 1, psz, hd), kv_map),
            pl.BlockSpec((1, 1, psz, hd), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Gp, hd), q_map),
            pl.BlockSpec((1, 1, Gp, 1), lse_map),
        ],
        # row statistics carry a trailing unit axis: Mosaic tiles the last
        # two dims of a block, so a (Gp,) vector is laid out as (Gp, 1)
        scratch_shapes=[
            pltpu.VMEM((Gp, 1), jnp.float32),    # m (running max)
            pltpu.VMEM((Gp, 1), jnp.float32),    # l (running sum)
            pltpu.VMEM((Gp, hd), jnp.float32),   # acc (weighted values)
        ],
    )
    prefetch = (page_table.astype(jnp.int32), lengths.astype(jnp.int32))
    if quantized:
        prefetch += (k_scale.reshape(-1).astype(jnp.float32),
                     v_scale.reshape(-1).astype(jnp.float32))
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, KV, Gp, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, KV, Gp, 1), jnp.float32),
        ],
        interpret=interpret,
        name="flash_decode",
    )(*prefetch, q, k_pages, v_pages)
    return out[:, :, :G], lse[:, :, :G, 0]


def combine_self(out: jax.Array, lse: jax.Array, s_self: jax.Array,
                 v_self: jax.Array) -> jax.Array:
    """Merge the paged partial with the current token's own (k, v).

    Standard two-partial flash combine: the cache partial carries
    (out, lse); the self term is a one-key partial with score ``s_self``
    (B, KV, G) and value ``v_self`` (B, KV, hd). An empty cache
    (lse ≈ -inf) degrades to pure self-attention — exactly the first
    decode step of an empty slot.
    """
    m = jnp.maximum(lse, s_self)
    w_cache = jnp.exp(lse - m)
    w_self = jnp.exp(s_self - m)
    num = out * w_cache[..., None] + v_self[:, :, None, :] * w_self[..., None]
    return num / (w_cache + w_self)[..., None]
