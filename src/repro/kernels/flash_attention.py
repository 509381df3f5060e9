"""Flash attention Pallas TPU kernels: tiled online-softmax forward (emitting
the per-row logsumexp) plus recomputation-based backward kernels (dq and
dk/dv), wired together with ``jax.custom_vjp`` so training differentiates
through hand-written Pallas code instead of autodiff-ing the ``pallas_call``
(which XLA cannot transpose and Mosaic cannot compile).

Masking is computed from block indices (no (S, S) mask in HBM). Supported
mask kinds — all the masks the DiffusionBlocks training path uses:

  full       no masking (bidirectional)
  causal     kpos <= qpos
  window     causal sliding window of ``window`` keys
  db_concat  paper App. E.4 [clean || noisy] mask (mask_seq = S, streams 2S)
  two_pass   DB two-pass noisy-stream mask (keys = [clean || noisy_diag])

Layout: q (B, H, Sq, hd), k/v (B, KV, Sk, hd) — head-major so a (block_q, hd)
q tile and (block_k, hd) kv tiles stream through VMEM while the MXU runs
(block_q × hd) @ (hd × block_k). A tile is as long as ``default_tile``
gives for its sequence (512, 256 or 128: MXU-aligned); accumulators live
in VMEM scratch across the entries of one output tile.

The grid is block-sparse: ``tile_schedule`` evaluates ``_tile_mask`` at
trace time and lists only the (q tile, k tile) pairs it keeps, q-major for
the forward and dq kernels, k-major for dk/dv; the kernels read that list
as scalar prefetch and run grid (B, H, n_live). A tile the mask keeps
whole skips the mask. A skipped tile would add only exact zeros (p = 0,
the correction exp(0) = 1), and the live tiles keep the dense grid's
order, so at one tile size a TPU gives the dense grid's results bit for bit.

Validated (values and grads) against ``ref.mha_reference`` in interpret mode
(CPU container); compiled path targets TPU.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.tiles import pad_seq as _pad_seq

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
# default tile lengths, longest first. A grid step has a fixed cost (DMA
# issue, row statistics) that outweighs a 128×128 tile's products at hd 64:
# on a TPU v5e the §5.4 LM's db_concat kernels (16 × 12 heads over the
# 2048-long stream) take 38 ms per block update at 512×512, 90 at 128×128.
TILES = (512, 256, 128)
NEG_INF = -1e30

MASK_KINDS = ("full", "causal", "window", "db_concat", "two_pass")

# flags of a schedule entry: the first / last entry of its output tile
# (initialise / finalise the accumulators); a tile the mask keeps only in
# part; an output tile no pair reaches (initialise and write only)
FIRST, LAST, PARTIAL, EMPTY = 1, 2, 4, 8


@dataclasses.dataclass(frozen=True)
class FlashConfig:
    """Static kernel configuration (hashable — jit/custom_vjp nondiff arg)."""
    mask_kind: str = "causal"
    window: Optional[int] = None        # only for mask_kind == "window"
    mask_seq: Optional[int] = None      # S for db_concat / two_pass
    block_q: int = DEFAULT_BLOCK_Q
    block_k: int = DEFAULT_BLOCK_K
    interpret: bool = False

    def __post_init__(self):
        # hard raises (not asserts): an unchecked kind would fall through
        # _tile_mask to bounds-only masking — silent full attention
        if self.mask_kind not in MASK_KINDS:
            raise ValueError(f"unknown mask_kind {self.mask_kind!r}; "
                             f"one of {MASK_KINDS}")
        if self.mask_kind == "window" and self.window is None:
            raise ValueError("mask_kind='window' requires window")
        if self.mask_kind in ("db_concat", "two_pass") \
                and self.mask_seq is None:
            raise ValueError(f"mask_kind={self.mask_kind!r} requires "
                             "mask_seq")


def _tile_mask(qpos, kpos, cfg: FlashConfig, seq_q: int, seq_k: int):
    """Boolean keep-mask for a (block_q, block_k) tile of global positions."""
    mask = (qpos < seq_q) & (kpos < seq_k)
    if cfg.mask_kind == "causal":
        mask &= kpos <= qpos
    elif cfg.mask_kind == "window":
        mask &= (kpos <= qpos) & (kpos > qpos - cfg.window)
    elif cfg.mask_kind == "db_concat":
        S = cfg.mask_seq
        q_clean = qpos < S
        k_clean = kpos < S
        clean_clean = q_clean & k_clean & (kpos <= qpos)
        noisy_clean = (~q_clean) & k_clean & (kpos < qpos - S)
        noisy_self = (~q_clean) & (kpos == qpos)
        mask &= clean_clean | noisy_clean | noisy_self
    elif cfg.mask_kind == "two_pass":
        S = cfg.mask_seq
        mask &= ((kpos < S) & (kpos < qpos)) | (kpos == qpos + S)
    return mask


def _tile_positions(iq, ik, block_q: int, block_k: int):
    qpos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 0)
    kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 1)
    return qpos, kpos


def default_tile(seq: int) -> int:
    """Tile length along a sequence of ``seq`` positions: the longest of
    ``TILES`` that divides it, else 128 over a padded tail."""
    return next((t for t in TILES if seq % t == 0), TILES[-1])


def _fit(cfg: FlashConfig, seq_q: int, seq_k: int) -> FlashConfig:
    """Tiles no longer than the sequences they cover."""
    return dataclasses.replace(cfg, block_q=min(cfg.block_q, seq_q),
                               block_k=min(cfg.block_k, seq_k))


def _order(state: np.ndarray) -> np.ndarray:
    """Entries (outer tile, inner tile, flags) of ``state`` (outer, inner:
    0 dead, 1 partial, 2 full) in outer-major order. An outer tile with no
    live inner tile gets one EMPTY entry, on the previous entry's inner
    tile so that no new tile is fetched."""
    rows, inner = [], 0
    for o, line in enumerate(state):
        live = np.flatnonzero(line)
        if live.size == 0:
            rows.append((o, inner, FIRST | LAST | EMPTY))
            continue
        for j, i in enumerate(live):
            rows.append((o, i, (FIRST if j == 0 else 0)
                         | (LAST if j == live.size - 1 else 0)
                         | (PARTIAL if line[i] == 1 else 0)))
        inner = live[-1]
    return np.asarray(rows, np.int32).T


@functools.lru_cache(maxsize=64)
def tile_schedule(cfg: FlashConfig, seq_q: int, seq_k: int):
    """The live tiles of a (seq_q, seq_k) attention under ``cfg``'s mask.

    Returns ``((q_major, k_major), live_share)``. Each table is an int32
    (3, n) array of entries (q tile, k tile, flags) in the order a kernel
    runs them: q-major for the forward and dq kernels, k-major for dk/dv.
    Every q tile (k tile) appears in ``q_major`` (``k_major``), a tile no
    pair reaches once, flagged EMPTY. ``live_share`` is the share of the
    dense grid's tiles in which the mask keeps some pair. Liveness is
    ``_tile_mask`` itself, evaluated on numpy positions (padding bounds
    included), so the schedule cannot drift from the kernels' mask."""
    cfg = _fit(cfg, seq_q, seq_k)
    bq, bk = cfg.block_q, cfg.block_k
    nq, nk = -(-seq_q // bq), -(-seq_k // bk)
    kpos = np.arange(nk * bk)[None, :]
    state = np.zeros((nq, nk), np.int8)
    for iq in range(nq):
        qpos = iq * bq + np.arange(bq)[:, None]
        keep = _tile_mask(qpos, kpos, cfg, seq_q, seq_k).reshape(bq, nk, bk)
        state[iq] = np.where(keep.all(axis=(0, 2)), 2,
                             keep.any(axis=(0, 2)).astype(np.int8))
    q_major = _order(state)
    k_major = _order(state.T)[[1, 0, 2]]
    for t in (q_major, k_major):
        t.setflags(write=False)
    return (q_major, k_major), float(np.count_nonzero(state)) / state.size


def _run_tile(flag, update):
    """``update(masked)`` for the entry's tile: with the mask on a partial
    tile, without it on a full one; an EMPTY entry computes nothing."""
    kind = flag & (PARTIAL | EMPTY)
    pl.when(kind == PARTIAL)(lambda: update(True))
    pl.when(kind == 0)(lambda: update(False))


def _prefetch_map(block_map):
    """Index map of a schedule grid (b, h, t): ``block_map(b, h, iq, ik)``
    at entry t of the scalar-prefetched tables."""
    return lambda b, h, t, tq, tk, tf: block_map(b, h, tq[t], tk[t])


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _fwd_kernel(tq_ref, tk_ref, tf_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_ref, l_ref, acc_ref, *, scale: float, cfg: FlashConfig,
                seq_q: int, seq_k: int):
    t = pl.program_id(2)
    flag = tf_ref[t]

    @pl.when((flag & FIRST) != 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def update(masked: bool):
        q = q_ref[0, 0].astype(jnp.float32)        # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)        # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if masked:
            qpos, kpos = _tile_positions(tq_ref[t], tk_ref[t], cfg.block_q,
                                         cfg.block_k)
            mask = _tile_mask(qpos, kpos, cfg, seq_q, seq_k)
            s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                        # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        if masked:
            p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    _run_tile(flag, update)

    @pl.when((flag & LAST) != 0)
    def _finalize():
        l = l_ref[...]
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        # logsumexp per q row; fully-masked (padded) rows stay at ~NEG_INF
        lse_ref[0, 0] = m_ref[...] + jnp.log(jnp.maximum(l, 1e-30))


def _fwd_impl(q, k, v, cfg: FlashConfig) -> Tuple[jax.Array, jax.Array]:
    """Returns (out (B,H,Sq,hd), lse (B,H,Sq_pad,1) float32).

    Row statistics (lse, and m/l in scratch) carry a trailing unit axis:
    Mosaic tiles the last two dims of every block by (8, 128) unless they
    span the whole array axis, so a (block_q,) row vector must be laid out
    as (block_q, 1)."""
    B, H, Sq, hd = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    G = H // KV
    scale = 1.0 / (hd ** 0.5)
    cfg = _fit(cfg, Sq, Sk)
    block_q, block_k = cfg.block_q, cfg.block_k
    q = _pad_seq(q, Sq + (-Sq) % block_q)
    k = _pad_seq(k, Sk + (-Sk) % block_k)
    v = _pad_seq(v, Sk + (-Sk) % block_k)
    (table, _), _ = tile_schedule(cfg, Sq, Sk)

    q_map = _prefetch_map(lambda b, h, iq, ik: (b, h, iq, 0))
    kv_map = _prefetch_map(lambda b, h, iq, ik: (b, h // G, ik, 0))
    kernel = functools.partial(_fwd_kernel, scale=scale, cfg=cfg,
                               seq_q=Sq, seq_k=Sk)
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, H, table.shape[1]),
            in_specs=[
                pl.BlockSpec((1, 1, block_q, hd), q_map),
                pl.BlockSpec((1, 1, block_k, hd), kv_map),
                pl.BlockSpec((1, 1, block_k, hd), kv_map),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, block_q, hd), q_map),
                pl.BlockSpec((1, 1, block_q, 1), q_map),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, 1), jnp.float32),   # m (running max)
                pltpu.VMEM((block_q, 1), jnp.float32),   # l (running sum)
                pltpu.VMEM((block_q, hd), jnp.float32),  # acc (weighted v)
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((*q.shape[:3], 1), jnp.float32),
        ],
        interpret=cfg.interpret,
        name="flash_attention_fwd",
    )(*table, q, k, v)
    return out[:, :, :Sq], lse


# ---------------------------------------------------------------------------
# Backward: dq kernel (q-major schedule), dk/dv kernel (k-major schedule).
# Both recompute the score tiles from (q, k) and the stored logsumexp — the
# (Sq, Sk) probability matrix never exists in HBM (FlashAttention-style).
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(tq_ref, tk_ref, tf_ref, q_ref, k_ref, v_ref, do_ref,
                   lse_ref, delta_ref, dq_ref, acc_ref, *, scale: float,
                   cfg: FlashConfig, seq_q: int, seq_k: int):
    t = pl.program_id(2)
    flag = tf_ref[t]

    @pl.when((flag & FIRST) != 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def update(masked: bool):
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]                        # (bq, 1)
        delta = delta_ref[0, 0]                    # (bq, 1)

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        p = jnp.exp(s - lse)
        if masked:
            qpos, kpos = _tile_positions(tq_ref[t], tk_ref[t], cfg.block_q,
                                         cfg.block_k)
            p = jnp.where(_tile_mask(qpos, kpos, cfg, seq_q, seq_k), p, 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        acc_ref[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    _run_tile(flag, update)

    @pl.when((flag & LAST) != 0)
    def _finalize():
        dq_ref[0, 0] = acc_ref[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(tq_ref, tk_ref, tf_ref, q_ref, k_ref, v_ref, do_ref,
                    lse_ref, delta_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                    scale: float, cfg: FlashConfig, seq_q: int, seq_k: int):
    t = pl.program_id(2)
    flag = tf_ref[t]

    @pl.when((flag & FIRST) != 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def update(masked: bool):
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        p = jnp.exp(s - lse)                                # (bq, bk)
        if masked:
            qpos, kpos = _tile_positions(tq_ref[t], tk_ref[t], cfg.block_q,
                                         cfg.block_k)
            p = jnp.where(_tile_mask(qpos, kpos, cfg, seq_q, seq_k), p, 0.0)
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale                       # (bq, bk)
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    _run_tile(flag, update)

    @pl.when((flag & LAST) != 0)
    def _finalize():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_impl(q, k, v, o, lse, do, cfg: FlashConfig):
    B, H, Sq, hd = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    G = H // KV
    scale = 1.0 / (hd ** 0.5)
    cfg = _fit(cfg, Sq, Sk)
    block_q, block_k = cfg.block_q, cfg.block_k
    Sq_pad = Sq + (-Sq) % block_q
    Sk_pad = Sk + (-Sk) % block_k
    qp, dop, op = _pad_seq(q, Sq_pad), _pad_seq(do, Sq_pad), _pad_seq(o, Sq_pad)
    kp, vp = _pad_seq(k, Sk_pad), _pad_seq(v, Sk_pad)
    (q_major, k_major), _ = tile_schedule(cfg, Sq, Sk)
    # delta_i = sum_d dO_i · O_i — the softmax-normalization correction term
    # (one elementwise reduce; padded rows carry dO = 0 so contribute nothing)
    delta = jnp.sum(dop.astype(jnp.float32) * op.astype(jnp.float32),
                    axis=-1, keepdims=True)                 # (B, H, Sq_pad, 1)

    q_map = _prefetch_map(lambda b, h, iq, ik: (b, h, iq, 0))
    kv_map = _prefetch_map(lambda b, h, iq, ik: (b, h // G, ik, 0))
    kvh_map = _prefetch_map(lambda b, h, iq, ik: (b, h, ik, 0))
    q_spec = pl.BlockSpec((1, 1, block_q, hd), q_map)
    kv_spec = pl.BlockSpec((1, 1, block_k, hd), kv_map)
    row_spec = pl.BlockSpec((1, 1, block_q, 1), q_map)
    in_specs = [q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec]

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, cfg=cfg,
                          seq_q=Sq, seq_k=Sk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, H, q_major.shape[1]),
            in_specs=in_specs,
            out_specs=q_spec,
            scratch_shapes=[pltpu.VMEM((block_q, hd), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(qp.shape, q.dtype),
        interpret=cfg.interpret,
        name="flash_attention_bwd_dq",
    )(*q_major, qp, kp, vp, dop, lse, delta)

    # dk/dv computed per q-head into (B, H, Sk, hd); GQA group-sum follows.
    kvh_spec = pl.BlockSpec((1, 1, block_k, hd), kvh_map)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, cfg=cfg,
                          seq_q=Sq, seq_k=Sk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, H, k_major.shape[1]),
            in_specs=in_specs,
            out_specs=[kvh_spec, kvh_spec],
            scratch_shapes=[pltpu.VMEM((block_k, hd), jnp.float32),
                            pltpu.VMEM((block_k, hd), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, H, Sk_pad, hd), k.dtype),
                   jax.ShapeDtypeStruct((B, H, Sk_pad, hd), v.dtype)],
        interpret=cfg.interpret,
        name="flash_attention_bwd_dkv",
    )(*k_major, qp, kp, vp, dop, lse, delta)

    dq = dq[:, :, :Sq]
    dk, dv = dk[:, :, :Sk], dv[:, :, :Sk]
    if G > 1:   # GQA: sum the per-q-head contributions within each kv group
        dk = dk.reshape(B, KV, G, Sk, hd).sum(axis=2).astype(k.dtype)
        dv = dv.reshape(B, KV, G, Sk, hd).sum(axis=2).astype(v.dtype)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp wiring
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash(q, k, v, cfg: FlashConfig):
    out, _ = _fwd_impl(q, k, v, cfg)
    return out


def _flash_fwd(q, k, v, cfg: FlashConfig):
    out, lse = _fwd_impl(q, k, v, cfg)
    return out, (q, k, v, out, lse)


def _flash_bwd(cfg: FlashConfig, res, do):
    q, k, v, o, lse = res
    return _bwd_impl(q, k, v, o, lse, do, cfg)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    mask_kind: Optional[str] = None,
                    mask_seq: Optional[int] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: bool = False) -> jax.Array:
    """q: (B, H, Sq, hd); k/v: (B, KV, Sk, hd); H = KV * G. Returns like q.
    Tiles default to ``default_tile`` of each sequence.

    Fully differentiable: gradients run through the Pallas backward kernels
    (``jax.custom_vjp``), never through autodiff of ``pallas_call``.
    """
    if mask_kind is None:
        mask_kind = ("window" if window is not None
                     else "causal" if causal else "full")
    cfg = FlashConfig(mask_kind=mask_kind, window=window, mask_seq=mask_seq,
                      block_q=block_q or default_tile(q.shape[2]),
                      block_k=block_k or default_tile(k.shape[2]),
                      interpret=interpret)
    return _flash(q, k, v, cfg)
