"""Chunked-prefill Pallas TPU kernel: a CHUNK of C queries over a PAGED KV
cache.

``flash_decode`` (PR 3) serves one query token per slot; prefilling a prompt
through it costs one serial attention step per token. This kernel is the
missing half: the whole prompt chunk's queries attend in ONE dispatch, after
the chunk's keys/values have been appended to the page pool
(``repro.nn.cache.append_paged_chunk``), so a prompt of S tokens costs
ceil(S / C) attention steps instead of S.

Layout and tricks shared with ``flash_decode``:

  * grid = (batch_slot, kv_head, logical_page); pages are the innermost grid
    dimension so the per-row (m, l, acc) logsumexp state carries across them
    in VMEM scratch;
  * the physical page streamed into VMEM comes from the scalar-prefetched
    page table (``PrefetchScalarGridSpec``) — no host-side indirection;
  * GQA-aware: queries arrive grouped (B, C, KV, G, hd) and are flattened to
    rows r = i*G + g, so the (rows, page_size) score tile is MXU-shaped and
    the per-row query index i = r // G drives the causal mask;
  * masking is length-aware AND causal: the chunk occupies absolute positions
    [lengths[b], lengths[b] + C), its K/V are ALREADY in the pages, and key
    slot at logical index ``idx`` is valid for query row i iff
    ``idx <= lengths[b] + i`` (sliding-window layers additionally require
    ``idx > lengths[b] + i - window``). Ragged chunk tails (tokens past a
    slot's prompt) produce garbage rows that the caller discards — their
    writes were redirected to the trash page, never to live pages.

Unlike decode there is no ``combine_self``: the chunk's own keys live in the
pool before the kernel runs, so one pass covers history + intra-chunk causal.

Prefill is inference-only (no custom VJP). Validated against the gather
reference in ``repro.nn.cache`` in interpret mode (CPU container); compiled
path targets TPU.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30
# TPU fp32 min sublane count; the flattened (C*G) query-row axis is padded up
# to a multiple of this so the (rows, page_size) score tile is alignable.
MIN_ROW_PAD = 8


def _prefill_kernel(*refs, scale: float, page_size: int,
                    n_pages: int, chunk: int, group: int,
                    window: Optional[int], quantized: bool):
    if quantized:
        (table_ref, len_ref, ks_ref, vs_ref, q_ref, k_ref, v_ref,
         o_ref, m_ref, l_ref, acc_ref) = refs
    else:
        (table_ref, len_ref, q_ref, k_ref, v_ref,
         o_ref, m_ref, l_ref, acc_ref) = refs
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

    # The furthest key any query in this chunk may attend is
    # lengths[b] + chunk - 1; pages entirely past that carry nothing valid —
    # skip their DMA'd tile outright (saves MXU work on the unreached tail).
    @pl.when(start < length + chunk)
    def _update():
        q = q_ref[0, 0].astype(jnp.float32)            # (rows, hd)
        k = k_ref[0, 0].astype(jnp.float32)            # (page_size, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        if quantized:
            # in-register dequant with this physical page's prefetched scale
            phys = table_ref[b, p]
            k = k * ks_ref[phys]
            v = v * vs_ref[phys]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        idx = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        # query row r = i*G + g sits at absolute position lengths[b] + i
        qpos = length + jax.lax.broadcasted_iota(jnp.int32, s.shape,
                                                 0) // group
        valid = idx <= qpos
        if window is not None:
            valid &= idx > qpos - window
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


def flash_prefill(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                  page_table: jax.Array, lengths: jax.Array, *,
                  window: Optional[int] = None,
                  k_scale: Optional[jax.Array] = None,
                  v_scale: Optional[jax.Array] = None,
                  interpret: bool = False) -> jax.Array:
    """Chunked-prefill paged attention (history + intra-chunk causal).

    q:          (B, C, KV, G, hd) — the chunk's grouped queries; the chunk
                occupies absolute positions [lengths[b], lengths[b] + C) and
                its OWN k/v must already be appended to the pool
                (``repro.nn.cache.append_paged_chunk``)
    k_pages/v_pages: (P, KV, page_size, hd) physical page pool (KV-major:
                one head's page is a whole (page_size, hd) tile)
    page_table: (B, n_logical_pages) int32; entries past a sequence's
                allocation MUST be in-bounds (reserved trash page — nn.cache)
    lengths:    (B,) int32 committed tokens per slot BEFORE this chunk
    k_scale/v_scale: per-PHYSICAL-page fp32 dequant scales for an int8 pool
                ((P,) or (P, 1, 1, 1); both given or both None),
                scalar-prefetched like the table and applied in-register

    Returns out (B, C, KV, G, hd) fp32 — fully softmax-normalized (no lse:
    the chunk's self keys are in the pool, nothing left to fold in).
    """
    B, C, KV, G, hd = q.shape
    psz = k_pages.shape[2]
    n_pages = page_table.shape[1]
    scale = 1.0 / (hd ** 0.5)
    quantized = k_scale is not None
    rows = C * G
    Rp = -(-rows // MIN_ROW_PAD) * MIN_ROW_PAD
    # rows flatten (C, G) with G minor, so row r = i*G + g as the mask expects
    qr = q.transpose(0, 2, 1, 3, 4).reshape(B, KV, rows, hd)
    if Rp != rows:
        qr = jnp.pad(qr, ((0, 0), (0, 0), (0, Rp - rows), (0, 0)))

    kernel = functools.partial(_prefill_kernel, scale=scale, page_size=psz,
                               n_pages=n_pages, chunk=C, group=G,
                               window=window, quantized=quantized)
    # with scales, the index_map lambdas receive two extra prefetch refs —
    # keep the unquantized specs verbatim so the bf16 program is unchanged
    if quantized:
        q_map = lambda b, kv, p, tbl, lens, ks, vs: (b, kv, 0, 0)
        kv_map = lambda b, kv, p, tbl, lens, ks, vs: (tbl[b, p], kv, 0, 0)
    else:
        q_map = lambda b, kv, p, tbl, lens: (b, kv, 0, 0)
        kv_map = lambda b, kv, p, tbl, lens: (tbl[b, p], kv, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4 if quantized else 2,
        grid=(B, KV, n_pages),
        in_specs=[
            pl.BlockSpec((1, 1, Rp, hd), q_map),
            pl.BlockSpec((1, 1, psz, hd), kv_map),
            pl.BlockSpec((1, 1, psz, hd), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Rp, hd), q_map),
        ],
        # row statistics carry a trailing unit axis: Mosaic tiles the last
        # two dims of a block, so an (Rp,) vector is laid out as (Rp, 1)
        scratch_shapes=[
            pltpu.VMEM((Rp, 1), jnp.float32),    # m (running max)
            pltpu.VMEM((Rp, 1), jnp.float32),    # l (running sum)
            pltpu.VMEM((Rp, hd), jnp.float32),   # acc (weighted values)
        ],
    )
    prefetch = (page_table.astype(jnp.int32), lengths.astype(jnp.int32))
    if quantized:
        prefetch += (k_scale.reshape(-1).astype(jnp.float32),
                     v_scale.reshape(-1).astype(jnp.float32))
    [out] = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, KV, Rp, hd), jnp.float32)],
        interpret=interpret,
        name="flash_prefill",
    )(*prefetch, qr, k_pages, v_pages)
    return out[:, :, :rows].reshape(B, KV, C, G, hd).transpose(0, 2, 1, 3, 4)
