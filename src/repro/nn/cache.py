"""Paged KV cache for serving (vLLM-style) + the paged decode attention op.

Instead of one dense worst-case ``(B, C_max, KV, hd)`` slab per layer, keys
and values live in a pool of fixed-size PAGES shared by every sequence slot:

  pages      (P, KV, page_size, hd)   physical storage (bf16 under the
                                      serving precision policy); KV-major so
                                      one head's page is a whole
                                      (page_size, hd) tile for the kernels
  page_table (B, n_logical_pages)     int32 — physical page id backing
                                      logical page p of slot b
  lengths    (B,) int32               committed tokens per slot

Memory is allocated in page granularity proportional to what sequences
*actually* use (the scheduler in ``launch/serve`` hands pages back when a
sequence retires), ragged prompt lengths share ONE compiled program (masking
is length-aware, never shape-aware), and the same pool layout feeds both the
gather-based reference attend and the Pallas flash-decode kernel
(``repro.kernels.flash_decode``).

Physical page 0 is RESERVED as the trash page whenever per-slot ``active``
masks are in play: writes for inactive slots are redirected there instead of
branching, so the append stays one dense scatter. ``init_paged_kv`` always
allocates it; allocators must hand out pages starting at 1 and point unused
page-table entries at 0 (they are DMA'd by the kernel, never read back
unmasked).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.nn import attention as A
from repro.nn.layers import apply_rope

NEG_INF = -1e30
TRASH_PAGE = 0
DEFAULT_PAGE_SIZE = 16


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PagedKV:
    """One layer's paged key/value pool. Registered as a pytree so it can be
    stacked over units, carried through ``lax.scan``, and sliced with
    ``tree_map`` exactly like the dense cache dicts it replaces.

    ``k_scale``/``v_scale`` are present ONLY when the pool stores quantized
    pages (integer storage dtype): one fp32 scalar per physical page per
    tensor. They are shaped ``(*units, P, 1, 1, 1)`` so their page axis sits
    at ``PAGE_AXIS`` exactly like the page data itself (the same gather /
    scatter index expressions move pages and their scales together) and
    dequantization is a plain broadcast multiply. Float pools leave them
    ``None`` — the unquantized pytree structure, and therefore every compiled
    program on the bf16 path, is byte-identical to the pre-quantization
    layout."""
    k: jax.Array    # (P, KV, page_size, hd) — leading unit axes when stacked
    v: jax.Array
    k_scale: Optional[jax.Array] = None   # (P, 1, 1, 1) fp32, quantized only
    v_scale: Optional[jax.Array] = None

    def tree_flatten(self):
        return (self.k, self.v, self.k_scale, self.v_scale), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def page_size(self) -> int:
        return self.k.shape[-2]

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None


KV_SCALE_DTYPE = jnp.float32


def resolve_kv_dtype(dtype):
    """Resolve a KV storage dtype spec (``'bf16' | 'int8' | np/jnp dtype``)
    to a numpy dtype."""
    if isinstance(dtype, str):
        dtype = {"bf16": jnp.bfloat16, "fp32": jnp.float32,
                 "fp16": jnp.float16, "f32": jnp.float32}.get(dtype, dtype)
    return jnp.dtype(dtype)


def is_quantized_dtype(dtype) -> bool:
    """True for KV storage dtypes that need per-page scales (int8)."""
    return jnp.issubdtype(resolve_kv_dtype(dtype), jnp.integer)


def quantize_pages(x: jax.Array, dtype=jnp.int8):
    """Per-page symmetric absmax quantization. ``x`` is ``(..., KV, psz,
    hd)`` float pages (any number of leading page/unit axes); returns
    ``(q, scale)`` with ``q`` in ``dtype`` and ``scale`` fp32 shaped
    ``(..., 1, 1, 1)`` so ``dequantize_pages`` is a broadcast multiply.
    All-zero pages get scale 0 (q == 0 dequantizes to exactly 0)."""
    qmax = float(jnp.iinfo(dtype).max)
    xf = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(xf), axis=(-3, -2, -1), keepdims=True)
    scale = absmax / qmax
    inv = jnp.where(scale > 0, 1.0 / jnp.where(scale > 0, scale, 1.0), 0.0)
    q = jnp.clip(jnp.round(xf * inv), -qmax, qmax).astype(dtype)
    return q, scale.astype(KV_SCALE_DTYPE)


def dequantize_pages(q: jax.Array, scale: jax.Array) -> jax.Array:
    """Inverse of ``quantize_pages``: fp32 pages from int pages + scales."""
    return q.astype(jnp.float32) * scale


def init_paged_kv(n_pages: int, page_size: int, dims: A.AttnDims,
                  dtype=jnp.bfloat16) -> PagedKV:
    dtype = resolve_kv_dtype(dtype)
    shape = (n_pages, dims.n_kv_heads, page_size, dims.head_dim)
    k, v = jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)
    if is_quantized_dtype(dtype):
        scale = jnp.zeros((n_pages, 1, 1, 1), KV_SCALE_DTYPE)
        return PagedKV(k, v, scale, scale)
    return PagedKV(k, v)


def pages_for(n_tokens: int, page_size: int) -> int:
    return -(-n_tokens // page_size)


def identity_page_table(batch: int, pages_per_slot: int) -> jax.Array:
    """Static allocation: slot b owns pages [1 + b*pps, 1 + (b+1)*pps) —
    page 0 stays reserved as the trash page."""
    return (1 + jnp.arange(batch * pages_per_slot, dtype=jnp.int32)
            ).reshape(batch, pages_per_slot)


def cache_bytes(tree) -> int:
    """Total bytes of a cache pytree (paged or dense; also accepts the
    ``jax.eval_shape`` abstract tree, so sizes can be reported without
    allocating). Mixed-dtype trees — an int8 pool with its fp32 scale
    leaves, fp32 recurrent states beside bf16 pages — are summed per leaf:
    every leaf contributes size × itemsize of its OWN dtype, so quantized
    pools report page bytes AND scale bytes rather than assuming one
    homogeneous dtype."""
    return sum(int(np.prod(x.shape)) * jnp.dtype(x.dtype).itemsize
               for x in jax.tree_util.tree_leaves(tree))


def cache_bytes_by_dtype(tree) -> Dict[str, int]:
    """Per-dtype byte breakdown of a cache pytree — the health/stats
    surface for mixed-dtype (quantized) pools, where a single total hides
    the fp32 scale arrays riding beside the int8 pages."""
    out: Dict[str, int] = {}
    for x in jax.tree_util.tree_leaves(tree):
        d = jnp.dtype(x.dtype)
        out[d.name] = out.get(d.name, 0) + int(np.prod(x.shape)) * d.itemsize
    return out


def reset_slots(tree, init_tree, slot_mask: jax.Array, batch_axis: int):
    """Restore masked slots' entries (along ``batch_axis``) to their INIT
    values from ``init_tree`` — NOT to zero: e.g. the xLSTM max-stabilizer
    states initialize to -1e30.

    Used when a continuous-batching slot is recycled for a NEW request:
    paged KV needs no reset (length masking hides stale pages), but per-slot
    RECURRENT state (mamba/xLSTM) and fixed cross-attention blocks would
    otherwise leak the previous occupant's state into the new sequence.
    """
    def one(cur, init):
        shape = [1] * cur.ndim
        shape[batch_axis] = slot_mask.shape[0]
        return jnp.where(slot_mask.reshape(shape), init.astype(cur.dtype),
                         cur)
    return jax.tree_util.tree_map(one, tree, init_tree)


def append_paged(pkv: PagedKV, k_new: jax.Array, v_new: jax.Array,
                 page_table: jax.Array, lengths: jax.Array,
                 active: Optional[jax.Array] = None) -> PagedKV:
    """Write one token's (k, v) per slot at logical position ``lengths[b]``.

    k_new/v_new: (B, KV, hd). Inactive slots write to the trash page —
    a dense scatter with redirected indices, no per-slot branching.
    """
    psz = pkv.page_size
    logical = lengths // psz
    slot = lengths % psz
    phys = jnp.take_along_axis(page_table, logical[:, None], axis=1)[:, 0]
    if active is not None:
        phys = jnp.where(active, phys, TRASH_PAGE)
    if not pkv.quantized:
        return PagedKV(
            pkv.k.at[phys, :, slot].set(k_new.astype(pkv.k.dtype)),
            pkv.v.at[phys, :, slot].set(v_new.astype(pkv.v.dtype)),
        )
    # Quantized pool: the page is the quantization granule, so the write is
    # read-modify-REQUANTIZE on the B touched pages. Positions past the new
    # token are zeroed before the absmax — recycled pages carry stale
    # garbage that would otherwise inflate the scale and crush the real
    # tokens' precision (attention masks hide the zeros exactly as they hid
    # the garbage).
    B = k_new.shape[0]
    rows = jnp.arange(B)
    keep = (jnp.arange(psz)[None, :] <= slot[:, None])[:, None, :, None]

    def one(pool, scale, new):
        pg = dequantize_pages(pool[phys], scale[phys])    # (B, KV, psz, hd)
        pg = pg.at[rows, :, slot].set(new.astype(jnp.float32))
        q, s = quantize_pages(jnp.where(keep, pg, 0.0), pool.dtype)
        return pool.at[phys].set(q), scale.at[phys].set(s)

    k_p, k_s = one(pkv.k, pkv.k_scale, k_new)
    v_p, v_s = one(pkv.v, pkv.v_scale, v_new)
    return PagedKV(k_p, v_p, k_s, v_s)


def append_paged_chunk(pkv: PagedKV, k_new: jax.Array, v_new: jax.Array,
                       page_table: jax.Array, lengths: jax.Array,
                       n_valid: jax.Array) -> PagedKV:
    """Write a whole CHUNK of C tokens' (k, v) per slot in one dense scatter.

    k_new/v_new: (B, C, KV, hd); chunk token i of slot b lands at logical
    position ``lengths[b] + i``. ``n_valid`` (B,) int32 is the count of real
    tokens in the chunk per slot (ragged tails / inactive slots write to the
    trash page — same no-branch redirect as ``append_paged``). Valid tokens
    are always a chunk PREFIX (prompts are right-padded), so lengths advance
    by exactly ``n_valid``.
    """
    B, C = k_new.shape[:2]
    psz = pkv.page_size
    if not pkv.quantized:
        pos = lengths[:, None] + jnp.arange(C, dtype=lengths.dtype)[None, :]
        logical = jnp.clip(pos // psz, 0, page_table.shape[1] - 1)
        slot = pos % psz
        phys = jnp.take_along_axis(page_table, logical, axis=1)     # (B, C)
        valid = jnp.arange(C)[None, :] < n_valid[:, None]
        phys = jnp.where(valid, phys, TRASH_PAGE)
        fp, fs = phys.reshape(-1), slot.reshape(-1)
        k_flat = k_new.reshape(B * C, *k_new.shape[2:])
        v_flat = v_new.reshape(B * C, *v_new.shape[2:])
        return PagedKV(
            pkv.k.at[fp, :, fs].set(k_flat.astype(pkv.k.dtype)),
            pkv.v.at[fp, :, fs].set(v_flat.astype(pkv.v.dtype)),
        )
    # Quantized pool: requantize every page the chunk touches. A C-token
    # chunk starting mid-page spans at most C // psz + 1 pages per slot;
    # gather those, dequantize, splice the chunk in at its per-slot offset,
    # zero everything past lengths + n_valid (ragged tails AND stale
    # garbage — see ``append_paged``), requantize, scatter pages + scales
    # back. Touched pages with no valid token (inactive slots) are
    # redirected to the trash page, same no-branch trick as above.
    npg = page_table.shape[1]
    npt = C // psz + 1
    base = lengths // psz
    tlog = base[:, None] + jnp.arange(npt, dtype=lengths.dtype)   # (B, npt)
    tphys = jnp.take_along_axis(page_table, jnp.clip(tlog, 0, npg - 1),
                                axis=1)
    end = lengths + n_valid
    real = tlog * psz < end[:, None]
    tphys = jnp.where(real, tphys, TRASH_PAGE)
    span = npt * psz
    rows = jnp.arange(B)[:, None]
    rel = (lengths % psz)[:, None] + jnp.arange(C, dtype=lengths.dtype)
    keep = ((base[:, None] * psz + jnp.arange(span))
            < end[:, None])[..., None, None]                  # (B,span,1,1)
    fp = tphys.reshape(-1)

    def one(pool, scale, new):
        pg = dequantize_pages(pool[tphys], scale[tphys])  # (B,npt,KV,psz,hd)
        KVh, hd = pg.shape[2], pg.shape[4]
        # token-major view (B, span, KV, hd) for the splice, then back
        flat = pg.transpose(0, 1, 3, 2, 4).reshape(B, span, KVh, hd)
        flat = flat.at[rows, rel].set(new.astype(jnp.float32))
        flat = jnp.where(keep, flat, 0.0)
        pages = flat.reshape(B, npt, psz, KVh, hd).transpose(0, 1, 3, 2, 4)
        q, s = quantize_pages(pages, pool.dtype)
        return (pool.at[fp].set(q.reshape(B * npt, KVh, psz, hd)),
                scale.at[fp].set(s.reshape(B * npt, 1, 1, 1)))

    k_p, k_s = one(pkv.k, pkv.k_scale, k_new)
    v_p, v_s = one(pkv.v, pkv.v_scale, v_new)
    return PagedKV(k_p, v_p, k_s, v_s)


# the page axis of a PagedKV leaf counted from the END: leaves are
# (*units, P, KV, psz, hd) with a VARIABLE number of leading unit axes
# (VLM stacks (n_units, k_self, P, ...)), so only trailing-axis indexing
# names the page axis reliably.
PAGE_AXIS = -4


def _page_index(ids):
    """Index tuple selecting physical pages ``ids`` at ``PAGE_AXIS`` for
    ``.at[...]`` updates, whatever the number of leading unit axes."""
    return (Ellipsis, ids, slice(None), slice(None), slice(None))


def copy_pool_pages(cache, src, dst):
    """Copy physical page ``src`` onto ``dst`` in every PagedKV leaf of a
    model cache (leaves are (*units, P, KV, psz, hd) — the page table is
    shared across units, so one physical id names the same page everywhere).
    Pages are addressed at ``PAGE_AXIS`` from the end: families stack a
    VARIABLE number of leading unit axes (VLM's self leaves carry an extra
    k_self axis), so positional ``[:, page]`` indexing would silently hit
    the wrong axis. Dense per-slot leaves (recurrent states, cross blocks)
    pass through untouched. This is the device half of copy-on-write prefix
    sharing. Quantized pools move each page's scale alongside its data —
    the scale arrays share ``PAGE_AXIS``, so the same index expressions
    apply."""
    def one(x):
        if isinstance(x, PagedKV):
            idx = _page_index(dst)

            def cp(a):
                if a is None:
                    return None
                return a.at[idx].set(jnp.take(a, src, axis=PAGE_AXIS))

            return PagedKV(cp(x.k), cp(x.v), cp(x.k_scale), cp(x.v_scale))
        return x
    return jax.tree_util.tree_map(one, cache,
                                  is_leaf=lambda x: isinstance(x, PagedKV))


def dense_to_paged(k: jax.Array, v: jax.Array, page_size: int
                   ) -> Tuple[PagedKV, jax.Array]:
    """View a dense (B, C, KV, hd) cache as pages + identity table, so the
    flash-decode kernel can also serve the legacy dense decode path. No
    trash page (this view is never appended to)."""
    B, C, KV, hd = k.shape
    psz = min(page_size, C)
    pad = (-C) % psz
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    npg = (C + pad) // psz
    pages = PagedKV(k.reshape(B * npg, psz, KV, hd).transpose(0, 2, 1, 3),
                    v.reshape(B * npg, psz, KV, hd).transpose(0, 2, 1, 3))
    table = jnp.arange(B * npg, dtype=jnp.int32).reshape(B, npg)
    return pages, table


# ---------------------------------------------------------------------------
# Slot spill / restore (host-side preemption store)
# ---------------------------------------------------------------------------
#
# Preemption needs a slot's ENTIRE sequence state to survive losing its slot
# and pages: the committed KV pages (paged leaves) plus the per-slot DENSE
# state the families keep outside the pool — recurrent mamba/xLSTM states and
# the fixed cross-attention conditioning blocks. DiffusionBlocks makes this
# snapshot unusually small and clean: every block is an independently trained
# denoiser over the same hidden stream, so there are no cross-block
# activations to capture — the cache pytree IS the whole state.
#
# ``spill_slot`` gathers to HOST numpy (the spill store lives off-device, so
# a preempted request costs no pool memory); ``restore_slot`` scatters the
# snapshot back into freshly allocated pages (possibly different physical
# ids — the page table is rewritten by the scheduler) and the same slot-axis
# rows. Both walk the cache with one flatten, so the leaf order is identical
# between spill and restore by construction.
#
# ``dense_axes`` maps top-level cache keys of dense (non-paged) subtrees to
# their slot axis (``model.paged_state_axes``): VLM/encdec cross blocks sit
# at axis 1, hybrid mamba states at axis 2 (an extra inner-layer axis).


@dataclasses.dataclass
class SpilledSlot:
    """Host-side snapshot of one slot's cache state: ``data[i]`` corresponds
    to flattened leaf i — an ``(k, v)`` numpy pair of gathered pages for a
    PagedKV leaf, a numpy slot-row for a dense leaf. ``n_pages`` is the
    number of (used) pages the snapshot covers.

    ``to_bytes``/``from_bytes`` give the snapshot a wire format (the
    RDMA-copy stub for migrating requests between workers whose pools do
    NOT share memory): a plain ``np.savez`` container, no pickle — the
    receiving process needs only numpy to reconstruct it, and a snapshot
    restores into ANY pool with matching per-page leaf shapes, regardless
    of that pool's total page count or slot count."""
    data: list
    n_pages: int

    def to_bytes(self) -> bytes:
        import io
        arrays = {"n_pages": np.asarray(self.n_pages, np.int64)}
        kinds, dtypes = [], []
        for i, entry in enumerate(self.data):
            if isinstance(entry, tuple) and len(entry) == 4:
                # quantized PagedKV leaf: (k, v, k_scale, v_scale)
                kinds.append(2)
                dtypes.append(entry[0].dtype.name)
                arrays[f"k{i}"], arrays[f"v{i}"] = entry[0], entry[1]
                arrays[f"ks{i}"], arrays[f"vs{i}"] = entry[2], entry[3]
            elif isinstance(entry, tuple):      # PagedKV leaf: (k, v) pages
                kinds.append(1)
                dtypes.append(entry[0].dtype.name)
                arrays[f"k{i}"], arrays[f"v{i}"] = entry
            else:                               # dense per-slot row
                kinds.append(0)
                dtypes.append(entry.dtype.name)
                arrays[f"d{i}"] = entry
        arrays["kinds"] = np.asarray(kinds, np.int8)
        # extension dtypes (bf16) serialize as raw void bytes — record the
        # name so the receiver can view them back
        arrays["dtypes"] = np.asarray(dtypes)
        buf = io.BytesIO()
        np.savez(buf, **arrays)
        return buf.getvalue()

    @classmethod
    def from_bytes(cls, raw: bytes) -> "SpilledSlot":
        import io
        with np.load(io.BytesIO(raw), allow_pickle=False) as z:
            kinds, dtypes = z["kinds"], z["dtypes"]
            data = []
            for i, kind in enumerate(kinds):
                dt = np.dtype(str(dtypes[i]))
                if kind == 2:
                    data.append((z[f"k{i}"].view(dt), z[f"v{i}"].view(dt),
                                 z[f"ks{i}"].view(np.float32),
                                 z[f"vs{i}"].view(np.float32)))
                elif kind == 1:
                    data.append((z[f"k{i}"].view(dt), z[f"v{i}"].view(dt)))
                else:
                    data.append(z[f"d{i}"].view(dt))
            return cls(data=data, n_pages=int(z["n_pages"]))


def _is_pkv(x) -> bool:
    return isinstance(x, PagedKV)


def _dense_slot_axis(path, dense_axes) -> int:
    for p in path:
        if isinstance(p, jax.tree_util.DictKey) and p.key in dense_axes:
            return dense_axes[p.key]
    raise KeyError(
        f"dense cache leaf at {jax.tree_util.keystr(path)} has no slot axis "
        f"in paged_state_axes {dense_axes} — the family must declare where "
        "its per-slot state lives before it can be spilled")


def spill_slot(cache, slot: int, page_ids, dense_axes=None) -> SpilledSlot:
    """Snapshot slot ``slot``'s state to host memory: the content of its
    ``page_ids`` physical pages from every PagedKV leaf (gathered at
    ``PAGE_AXIS``) and its row of every dense per-slot leaf (at the axis
    ``dense_axes`` names). The cache itself is NOT modified — the scheduler
    frees the pages separately."""
    dense_axes = dense_axes or {}
    ids = jnp.asarray(np.asarray(page_ids, np.int32))
    leaves = jax.tree_util.tree_flatten_with_path(cache, is_leaf=_is_pkv)[0]
    data = []
    for path, leaf in leaves:
        if _is_pkv(leaf):
            entry = (np.asarray(jnp.take(leaf.k, ids, axis=PAGE_AXIS)),
                     np.asarray(jnp.take(leaf.v, ids, axis=PAGE_AXIS)))
            if leaf.quantized:
                entry += (np.asarray(jnp.take(leaf.k_scale, ids,
                                              axis=PAGE_AXIS)),
                          np.asarray(jnp.take(leaf.v_scale, ids,
                                              axis=PAGE_AXIS)))
            data.append(entry)
        else:
            ax = _dense_slot_axis(path, dense_axes)
            data.append(np.asarray(jnp.take(leaf, slot, axis=ax)))
    return SpilledSlot(data=data, n_pages=len(page_ids))


def restore_slot(cache, slot: int, page_ids, spilled: SpilledSlot,
                 dense_axes=None):
    """Write a ``spill_slot`` snapshot back: page content lands in the
    freshly allocated ``page_ids`` (``len(page_ids) == spilled.n_pages``;
    the ids may differ from the spill-time ones — logical order is what
    matters) and dense rows overwrite slot ``slot``. Returns the updated
    cache; the scheduler then rewrites the page table to ``page_ids``."""
    dense_axes = dense_axes or {}
    assert len(page_ids) == spilled.n_pages, \
        f"restore got {len(page_ids)} pages for a {spilled.n_pages}-page spill"
    ids = jnp.asarray(np.asarray(page_ids, np.int32))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(cache,
                                                           is_leaf=_is_pkv)
    assert len(leaves) == len(spilled.data), \
        "cache structure changed between spill and restore"
    new = []
    for (path, leaf), saved in zip(leaves, spilled.data):
        if _is_pkv(leaf):
            if not isinstance(saved, tuple):
                raise ValueError(
                    f"cache-state snapshot mismatch at "
                    f"{jax.tree_util.keystr(path)}: the snapshot holds a "
                    "dense row where the target pool has a paged leaf — "
                    "spill and restore caches come from different model "
                    "families")
            _check_restore_dtypes(path, leaf, saved)
            if spilled.n_pages == 0:
                # dense-rows-only snapshot (page-handle migration): the
                # handed pages already hold the KV — no paged writes
                new.append(leaf)
                continue
            idx = _page_index(ids)
            k_s, v_s = saved[0], saved[1]
            restored = PagedKV(leaf.k.at[idx].set(jnp.asarray(k_s)),
                               leaf.v.at[idx].set(jnp.asarray(v_s)))
            if leaf.quantized:
                restored = PagedKV(
                    restored.k, restored.v,
                    leaf.k_scale.at[idx].set(jnp.asarray(saved[2])),
                    leaf.v_scale.at[idx].set(jnp.asarray(saved[3])))
            new.append(restored)
        else:
            ax = _dense_slot_axis(path, dense_axes)
            idx = (slice(None),) * ax + (slot,)
            new.append(leaf.at[idx].set(
                jnp.asarray(saved).astype(leaf.dtype)))
    return jax.tree_util.tree_unflatten(treedef, new)


def _check_restore_dtypes(path, leaf: PagedKV, saved: tuple):
    """Refuse to scatter a snapshot's pages into a pool with a different
    storage dtype or quantization layout. Reinterpreting e.g. int8 page
    bytes as bf16 (mismatched ``--kv-dtype`` between disagg workers) would
    silently serve garbage KV — fail loudly with the remediation instead."""
    have_scales = len(saved) == 4
    snap_dt, pool_dt = np.dtype(saved[0].dtype), np.dtype(leaf.k.dtype)
    if snap_dt != pool_dt or have_scales != leaf.quantized:
        def _desc(dt, scaled):
            return (f"{np.dtype(dt).name} pages "
                    f"{'WITH' if scaled else 'without'} per-page scales")
        raise ValueError(
            f"cache-state dtype mismatch at {jax.tree_util.keystr(path)}: "
            f"snapshot carries {_desc(snap_dt, have_scales)} but the target "
            f"pool stores {_desc(pool_dt, leaf.quantized)}. The spilling and "
            "restoring pools must be built with the same --kv-dtype; "
            "re-prefill the request on the destination worker instead of "
            "migrating its cache state.")


# ---------------------------------------------------------------------------
# Conditioning memory (fixed per-slot cross-attention blocks)
# ---------------------------------------------------------------------------

def cross_attend(q, k, v, cond_lengths):
    """Cross-attention over a fixed per-slot conditioning block with a
    per-slot VALID length — the serving counterpart of the unmasked
    ``attention.attend(mask_mod=None)`` cross path.

    q: (B, S, H, hd) un-roped queries; k/v: (B, Sk, KV, hd) the slot's
    conditioning memory (image patches / encoded audio frames), zero-padded
    past ``cond_lengths[b]``. Padding must be MASKED, not attended: attending
    zero keys would dilute the softmax. ``cond_lengths[b] == 0`` means the
    slot is UNCONDITIONED — the sum of weights is zero and the output is
    exactly 0 (no NaN), which is what an absent cross term contributes.

    Returns (B, S, H, hd) in q.dtype (fp32 softmax inside).
    """
    B, S, H, hd = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = 1.0 / (hd ** 0.5)
    qg = q.reshape(B, S, KV, G, hd).astype(jnp.float32)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k.astype(jnp.float32)) * scale
    valid = jnp.arange(Sk)[None, :] < cond_lengths[:, None]        # (B, Sk)
    s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - jnp.maximum(m, NEG_INF / 2))   # all-masked rows -> p ~ 0
    p = jnp.where(valid[:, None, None, None, :], p, 0.0)
    l = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum("bkgqs,bskd->bkgqd", p / l, v.astype(jnp.float32))
    return out.transpose(0, 3, 1, 2, 4).reshape(B, S, H, hd).astype(q.dtype)


def conditioning_fingerprint(aux_inputs) -> int:
    """Content hash of a request's aux conditioning inputs (image/audio
    embeddings), folded into ``PrefixPageCache`` keys: identical prompt text
    under DIFFERENT conditioning must never share prefix pages (every
    token's hidden stream — and therefore its paged self-attention K/V —
    passes through cross-attention to this memory), while identical text
    AND identical conditioning shares exactly as unconditioned text does.

    Host-side (numpy), deterministic across processes. Returns 0 for
    unconditioned requests (``None`` / empty dict) — the unconditioned trie
    root, so text-only serving keeps today's hit rates."""
    import hashlib

    import numpy as np
    if not aux_inputs:
        return 0
    h = hashlib.sha256()
    for key in sorted(aux_inputs):
        arr = np.ascontiguousarray(np.asarray(aux_inputs[key], np.float32))
        h.update(key.encode())
        h.update(str(arr.shape).encode())
        h.update(arr.tobytes())
    return int.from_bytes(h.digest()[:8], "big") or 1


# ---------------------------------------------------------------------------
# Attend over the pool (committed tokens < lengths[b]) + the token's own k/v
# ---------------------------------------------------------------------------

def _attend_pages_ref(qg, pkv: PagedKV, page_table, lengths, k_self, v_self,
                      window: Optional[int]):
    """Gather-based reference: logical KV materialized per slot, fp32
    softmax over [cached (idx < lengths[b]) || self]. qg: (B, KV, G, hd);
    k_self/v_self: (B, KV, hd). Returns (B, KV, G, hd) fp32."""
    B, KV, G, hd = qg.shape
    npg, psz = page_table.shape[1], pkv.page_size
    L = npg * psz
    kk = pkv.k[page_table].astype(jnp.float32)        # (B, npg, KV, psz, hd)
    vv = pkv.v[page_table].astype(jnp.float32)
    if pkv.quantized:                 # per-page dequant: broadcast multiply
        kk = kk * pkv.k_scale[page_table]
        vv = vv * pkv.v_scale[page_table]
    kk = kk.transpose(0, 2, 1, 3, 4).reshape(B, KV, L, hd)
    vv = vv.transpose(0, 2, 1, 3, 4).reshape(B, KV, L, hd)
    scale = 1.0 / (hd ** 0.5)
    qf = qg.astype(jnp.float32)
    s = jnp.einsum("bkgd,bksd->bkgs", qf, kk) * scale
    idx = jnp.arange(L)
    valid = idx[None, :] < lengths[:, None]
    if window is not None:
        valid &= idx[None, :] > lengths[:, None] - window
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    s_self = jnp.einsum("bkgd,bkd->bkg", qf,
                        k_self.astype(jnp.float32)) * scale
    s_all = jnp.concatenate([s, s_self[..., None]], axis=-1)
    w = jax.nn.softmax(s_all, axis=-1)
    out = jnp.einsum("bkgs,bksd->bkgd", w[..., :-1], vv)
    return out + w[..., -1:] * v_self.astype(jnp.float32)[:, :, None, :]


def attend_paged(qg, pkv: PagedKV, page_table, lengths, k_self, v_self, *,
                 window: Optional[int] = None, impl: str = "auto"):
    """Dispatch between the gather reference and the Pallas flash-decode
    kernel (split-KV over pages, logsumexp-combined, then the self term is
    folded in from the fp32 partials)."""
    if impl in ("pallas", "kernels"):
        from repro.kernels import ops as kops
        from repro.kernels import flash_decode as FD
        out_p, lse = kops.flash_decode(qg, pkv.k, pkv.v, page_table,
                                       lengths, window=window,
                                       k_scale=pkv.k_scale,
                                       v_scale=pkv.v_scale)
        scale = 1.0 / (qg.shape[-1] ** 0.5)
        s_self = jnp.einsum("bkgd,bkd->bkg", qg.astype(jnp.float32),
                            k_self.astype(jnp.float32)) * scale
        return FD.combine_self(out_p, lse, s_self,
                               v_self.astype(jnp.float32))
    return _attend_pages_ref(qg, pkv, page_table, lengths, k_self, v_self,
                             window)


def paged_decode_attention(params, x, dims: A.AttnDims, pkv: PagedKV, *,
                           lengths, page_table, active=None,
                           commit: bool = True,
                           window: Optional[int] = None, impl: str = "auto"):
    """One-token decode over the paged cache — the serving counterpart of
    ``attention.decode_attention``.

    x: (B, 1, d); each slot's token sits at its OWN absolute position
    ``lengths[b]`` (rope + mask are per-slot, so ragged batches trace once).
    ``commit=False`` is the DB denoising probe: attend but never append —
    the pool is returned untouched instead of copy-discarded.

    Returns (out (B, 1, d), new_pkv).
    """
    B = x.shape[0]
    q, k, v = A.project_qkv(params, x, dims)
    posv = lengths[:, None]                       # (B, 1) per-slot positions
    q = apply_rope(q, posv, dims.rope_theta)
    k = apply_rope(k, posv, dims.rope_theta)
    KV, G, hd = dims.n_kv_heads, dims.q_per_kv, dims.head_dim
    qg = q[:, 0].reshape(B, KV, G, hd)
    k_self, v_self = k[:, 0], v[:, 0]             # (B, KV, hd)
    out = attend_paged(qg, pkv, page_table, lengths, k_self, v_self,
                       window=window, impl=impl)
    out = out.reshape(B, 1, dims.n_heads * hd).astype(x.dtype)
    out = out @ params["wo"].astype(x.dtype)
    new_pkv = append_paged(pkv, k_self, v_self, page_table, lengths,
                           active) if commit else pkv
    return out, new_pkv


# ---------------------------------------------------------------------------
# Chunked prefill: C queries at a time over the pool (the chunk's own k/v are
# appended FIRST, so one attend covers history + intra-chunk causal)
# ---------------------------------------------------------------------------

def _attend_prefill_ref(qg, pkv: PagedKV, page_table, lengths,
                        window: Optional[int]):
    """Gather-based reference for chunk queries. qg: (B, C, KV, G, hd) at
    absolute positions lengths[b] + i; key at logical index j is valid for
    query i iff j <= lengths[b] + i (and within the sliding window). Returns
    (B, C, KV, G, hd) fp32."""
    B, C, KV, G, hd = qg.shape
    npg, psz = page_table.shape[1], pkv.page_size
    L = npg * psz
    kk = pkv.k[page_table].astype(jnp.float32)        # (B, npg, KV, psz, hd)
    vv = pkv.v[page_table].astype(jnp.float32)
    if pkv.quantized:                 # per-page dequant: broadcast multiply
        kk = kk * pkv.k_scale[page_table]
        vv = vv * pkv.v_scale[page_table]
    kk = kk.transpose(0, 2, 1, 3, 4).reshape(B, KV, L, hd)
    vv = vv.transpose(0, 2, 1, 3, 4).reshape(B, KV, L, hd)
    scale = 1.0 / (hd ** 0.5)
    qf = qg.astype(jnp.float32)
    s = jnp.einsum("bckgd,bksd->bkgcs", qf, kk) * scale   # (B,KV,G,C,L)
    idx = jnp.arange(L)
    qabs = lengths[:, None] + jnp.arange(C)               # (B, C)
    valid = idx[None, None, :] <= qabs[:, :, None]        # (B, C, L)
    if window is not None:
        valid &= idx[None, None, :] > qabs[:, :, None] - window
    s = jnp.where(valid[:, None, None, :, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgcs,bksd->bkgcd", w, vv)
    return out.transpose(0, 3, 1, 2, 4)                   # (B, C, KV, G, hd)


def attend_prefill(qg, pkv: PagedKV, page_table, lengths, *,
                   window: Optional[int] = None, impl: str = "auto"):
    """Dispatch between the gather reference and the Pallas chunked-prefill
    kernel (``repro.kernels.flash_prefill``)."""
    if impl in ("pallas", "kernels"):
        from repro.kernels import ops as kops
        return kops.flash_prefill(qg, pkv.k, pkv.v, page_table, lengths,
                                  window=window, k_scale=pkv.k_scale,
                                  v_scale=pkv.v_scale)
    return _attend_prefill_ref(qg, pkv, page_table, lengths, window)


def paged_prefill_attention(params, x, dims: A.AttnDims, pkv: PagedKV, *,
                            lengths, page_table, n_valid,
                            window: Optional[int] = None, impl: str = "auto"):
    """Chunk-of-C prefill over the paged cache — the ingest counterpart of
    ``paged_decode_attention``. x: (B, C, d); slot b's chunk sits at its OWN
    absolute positions [lengths[b], lengths[b] + C) (per-slot rope + masks:
    ragged batches and prefix-cache offsets trace once). The chunk's K/V are
    written into pool pages in ONE scatter (ragged tails past ``n_valid[b]``
    to the trash page), then one attend covers [committed history ||
    intra-chunk causal]. Rows past ``n_valid[b]`` return garbage the caller
    discards — exactly like inactive decode slots.

    Returns (out (B, C, d), new_pkv).
    """
    B, C = x.shape[:2]
    q, k, v = A.project_qkv(params, x, dims)
    posv = lengths[:, None] + jnp.arange(C, dtype=lengths.dtype)[None, :]
    q = apply_rope(q, posv, dims.rope_theta)
    k = apply_rope(k, posv, dims.rope_theta)
    new_pkv = append_paged_chunk(pkv, k, v, page_table, lengths, n_valid)
    KV, G, hd = dims.n_kv_heads, dims.q_per_kv, dims.head_dim
    qg = q.reshape(B, C, KV, G, hd)
    out = attend_prefill(qg, new_pkv, page_table, lengths, window=window,
                         impl=impl)
    out = out.reshape(B, C, dims.n_heads * hd).astype(x.dtype)
    return out @ params["wo"].astype(x.dtype), new_pkv


# ---------------------------------------------------------------------------
# Shared-prefix page cache (host-side allocator metadata)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _PrefixNode:
    """One cached page of prompt-prefix KV. Full-page nodes chain into a trie
    keyed by their page's token ids; each node may also carry TAIL candidates
    — partially-filled pages whose leading tokens continue this chain."""
    page: int
    children: Dict[tuple, "_PrefixNode"] = dataclasses.field(
        default_factory=dict)
    tails: List[Tuple[int, "np.ndarray"]] = dataclasses.field(
        default_factory=list)


@dataclasses.dataclass
class PrefixMatch:
    """Result of a prefix-cache lookup: ``pages`` are the shared physical
    pages (full pages, plus at most one partial TAIL page), ``n_tokens`` the
    prompt tokens they cover. ``tail_tokens`` > 0 means the LAST shared page
    is partially filled — the slot's first write lands inside it, so the
    scheduler must copy-on-write it before writing."""
    pages: List[int]
    n_tokens: int
    tail_tokens: int


class PrefixPageCache:
    """Host-side shared-prefix registry over the physical page pool.

    Prompt prefixes are hashed at PAGE granularity by token content: a trie
    node per full page (chained, so equal pages in different contexts never
    collide) plus partial-tail candidates for the page that follows a chain.
    The cache holds one refcount on every registered page so it survives its
    owner's retirement; the scheduler (``launch.serve.ContinuousBatcher``)
    adds one ref per slot that maps a shared page and frees a page only when
    its count drops to zero. Pages with refcount > 1 are READ-ONLY for any
    slot — a slot about to write into one gets a private copy first
    (``copy_pool_pages``), which is what makes the sharing copy-on-write.

    CONDITIONING-AWARE: every lookup/registration carries the request's
    conditioning fingerprint (``conditioning_fingerprint`` — a content hash
    of its aux image/audio embeddings; 0 = unconditioned). Each fingerprint
    owns its own trie root, so identical prompt text under different
    conditioning NEVER shares pages (the page content depends on the
    conditioning through cross-attention), while requests with identical
    text AND identical conditioning — and all unconditioned requests —
    share exactly as before.
    """

    def __init__(self, page_size: int):
        self.page_size = page_size
        self.roots: Dict[int, _PrefixNode] = {}
        self.hits = 0            # lookups that shared at least one page
        self.tokens_shared = 0   # prompt tokens served from shared pages

    def _root(self, cond_fp: int) -> _PrefixNode:
        if cond_fp not in self.roots:
            self.roots[cond_fp] = _PrefixNode(page=-1)
        return self.roots[cond_fp]

    # ---- lookup ------------------------------------------------------
    def match(self, tokens, cond_fp: int = 0) -> PrefixMatch:
        """Longest shared prefix of ``tokens`` (np int array) under the
        request's conditioning fingerprint. Never matches the WHOLE prompt's
        last page as full+exact unless the prompt is page-aligned; a partial
        tail match covers at most page_size-1 tokens of the next page.

        Pure lookup — no refcounts are taken and no statistics move (the
        scheduler may defer the admission); ``hits`` / ``tokens_shared`` are
        updated by the caller when a match is actually admitted."""
        import numpy as np
        node = self.roots.get(cond_fp)   # pure: never create roots on lookup
        if node is None:
            return PrefixMatch(pages=[], n_tokens=0, tail_tokens=0)
        tokens = np.asarray(tokens)
        psz = self.page_size
        pages, n = [], 0
        while n + psz <= tokens.size:
            key = tuple(int(t) for t in tokens[n:n + psz])
            child = node.children.get(key)
            if child is None:
                break
            node, n = child, n + psz
            pages.append(child.page)
        tail_tokens, best = 0, None
        rest = tokens[n:]
        for page, ttoks in node.tails:
            m = 0
            lim = min(ttoks.size, rest.size)
            while m < lim and int(ttoks[m]) == int(rest[m]):
                m += 1
            if m > tail_tokens:
                tail_tokens, best = m, page
        if best is not None and tail_tokens > 0:
            pages.append(best)
            n += tail_tokens
        return PrefixMatch(pages=pages, n_tokens=n, tail_tokens=tail_tokens)

    # ---- registration ------------------------------------------------
    def insert(self, tokens, pages: List[int], refcount: Dict[int, int],
               cond_fp: int = 0):
        """Register a freshly-prefilled prompt's pages under its conditioning
        fingerprint. ``pages[i]`` backs tokens [i*psz, (i+1)*psz). Full pages
        extend the trie; a non-empty partial last page becomes a tail
        candidate. Every NEWLY registered page gains one cache-held ref in
        ``refcount``. Pages already in the trie (the request itself was a
        cache hit) are left alone."""
        import numpy as np
        tokens = np.asarray(tokens)
        psz = self.page_size
        node, n, i = self._root(cond_fp), 0, 0
        while n + psz <= tokens.size:
            key = tuple(int(t) for t in tokens[n:n + psz])
            child = node.children.get(key)
            if child is None:
                child = _PrefixNode(page=pages[i])
                node.children[key] = child
                refcount[pages[i]] = refcount.get(pages[i], 0) + 1
            node, n, i = child, n + psz, i + 1
        tail = tokens[n:]
        if tail.size and i < len(pages):
            known = any(np.array_equal(t, tail) for _, t in node.tails)
            if not known:
                node.tails.append((pages[i], tail.copy()))
                refcount[pages[i]] = refcount.get(pages[i], 0) + 1
        # If nothing was registered under a freshly created root (prompt
        # shorter than a page with no tail page to offer, say), drop the
        # root again: an empty root matches nothing, survives eviction
        # sweeps that stop as soon as enough pages are free, and would
        # accumulate forever across fingerprints.
        root = self.roots.get(cond_fp)
        if root is not None and not root.children and not root.tails:
            del self.roots[cond_fp]

    # ---- eviction ----------------------------------------------------
    def evict(self, refcount: Dict[int, int], free_pages: List[int],
              need: int) -> int:
        """Drop cache-held refs until ``need`` pages are free (deepest trie
        nodes and tails first — prefixes stay useful longest; conditioning
        tries are walked in insertion order). Pages whose count hits zero go
        back on the free list. Returns pages freed."""
        freed = 0

        def drop(page):
            nonlocal freed
            refcount[page] -= 1
            if refcount[page] == 0:
                del refcount[page]
                free_pages.append(page)
                freed += 1

        def walk(node):
            nonlocal freed
            for key in list(node.children):
                if len(free_pages) >= need:
                    return
                walk(node.children[key])
                child = node.children[key]
                if not child.children and not child.tails:
                    drop(child.page)
                    del node.children[key]
            while node.tails and len(free_pages) < need:
                page, _ = node.tails.pop()
                drop(page)

        for fp in list(self.roots):
            root = self.roots[fp]
            if len(free_pages) < need:
                walk(root)
            # Prune emptied roots even when eviction was satisfied mid-walk
            # (or before this root was reached): breaking out of the sweep
            # used to strand empty roots in ``self.roots``.
            if not root.children and not root.tails:
                del self.roots[fp]
        return freed
