"""Jitted kernel wrappers. On the CPU dev container the Pallas kernels run in
interpret mode (the kernel body executes as JAX ops — correctness path); on a
TPU backend they compile to Mosaic.

Every wrapper is differentiable: gradients flow through the hand-written
Pallas backward kernels (``jax.custom_vjp`` in the kernel modules), never
through autodiff of ``pallas_call``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.kernels import edm_loss as _edm
from repro.kernels import flash_attention as _fa
from repro.kernels import flash_decode as _fd
from repro.kernels import flash_prefill as _fp
from repro.kernels import fused_adaln as _ad


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit,
                   static_argnames=("causal", "window", "mask_kind",
                                    "mask_seq"))
def flash_attention_hmajor(q, k, v, causal: bool = True,
                           window: Optional[int] = None,
                           mask_kind: Optional[str] = None,
                           mask_seq: Optional[int] = None):
    """(B, H, S, hd) layout."""
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               mask_kind=mask_kind, mask_seq=mask_seq,
                               interpret=_interpret())


def _route_mask(mask_mod, causal: bool, window: Optional[int]):
    """Map an ``attention.MaskMod`` onto a kernel mask kind.

    Mask constructors the kernel supports carry a ``kernel_mask`` tag
    ``(kind, window, mask_seq)``; anything untagged (custom masks, decode
    ring-buffer validity masks, …) is REJECTED so we never silently compute
    wrong attention.
    """
    if mask_mod is None:
        return (("window", window, None) if window is not None
                else ("causal", None, None) if causal
                else ("full", None, None))
    tag = getattr(mask_mod, "kernel_mask", None)
    if tag is None:
        raise NotImplementedError(
            f"mask_mod {getattr(mask_mod, '__name__', mask_mod)!r} has no "
            "Pallas kernel equivalent; use impl='chunked' (or tag the mask "
            "constructor with .kernel_mask = (kind, window, mask_seq)). "
            "One-token decode does not route here at all — it has a "
            "dedicated split-KV kernel, ops.flash_decode")
    return tag


def _check_positions(pos, n: int, name: str):
    """The kernel derives mask positions from block indices, so ``pos`` must
    be ``arange(n)``. Wrong lengths always raise; wrong CONTENTS (packed
    segments, offsets, ring buffers) raise when the array is concrete —
    inside a jit trace contents are unobservable, so there the arange
    assumption is on the caller (every in-repo path builds arange)."""
    if pos is None:
        return
    if pos.shape[0] != n:
        raise NotImplementedError(
            f"pallas flash attention requires {name} == arange({n}); got "
            f"length {pos.shape[0]}")
    if not isinstance(pos, jax.core.Tracer):
        import numpy as np
        if not np.array_equal(np.asarray(pos), np.arange(n)):
            raise NotImplementedError(
                f"pallas flash attention requires {name} == arange({n}); "
                "got non-standard positions (packed/offset/ring positions "
                "have no kernel mask equivalent — use impl='chunked')")


def flash_attention(q, k, v, *, mask_mod=None, qpos=None, kpos=None,
                    causal: bool = True, window: Optional[int] = None):
    """(B, S, H, hd) layout adapter used by repro.nn.attention.

    ``mask_mod`` is routed onto the kernel's block-index masks (causal /
    sliding-window / DB concat / DB two-pass); unsupported masks raise
    ``NotImplementedError``, as do non-arange ``qpos``/``kpos`` where
    detectable (see ``_check_positions``).
    """
    kind, win, mseq = _route_mask(mask_mod, causal, window)
    Sq, Sk = q.shape[1], k.shape[1]
    _check_positions(qpos, Sq, "qpos")
    _check_positions(kpos, Sk, "kpos")
    qh = q.transpose(0, 2, 1, 3)
    kh = k.transpose(0, 2, 1, 3)
    vh = v.transpose(0, 2, 1, 3)
    out = flash_attention_hmajor(qh, kh, vh, causal=causal, window=win,
                                 mask_kind=kind, mask_seq=mseq)
    return out.transpose(0, 2, 1, 3)


@jax.jit
def ln_modulate(x, scale, shift):
    return _ad.fused_ln_modulate(x, scale, shift, interpret=_interpret())


@jax.jit
def gate_residual(res, branch, gate):
    return _ad.fused_gate_residual(res, branch, gate, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("sigma_data",))
def euler_update(z, f, sigma, sigma_to, sigma_data: float = 0.5):
    return _ad.fused_euler(z, f, sigma, sigma_to, sigma_data,
                           interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("sigma_data",))
def edm_loss(f, z, y, sigma, sigma_data: float = 0.5):
    return _edm.edm_loss(f, z, y, sigma, sigma_data, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("window",))
def flash_decode(q, k_pages, v_pages, page_table, lengths,
                 window: Optional[int] = None,
                 k_scale=None, v_scale=None):
    """Split-KV paged decode attention (flash-decoding). q: (B, KV, G, hd);
    k/v pages: (P, KV, page_size, hd). For int8 pools pass the per-page fp32
    ``k_scale``/``v_scale`` arrays — dequant is fused into the kernel.
    Returns (out, lse) fp32 partials over the committed tokens; fold in the
    current token's own k/v with ``flash_decode.combine_self``. This is the
    decode route — the prefill / train masks above never see 1-token
    queries."""
    return _fd.flash_decode(q, k_pages, v_pages, page_table, lengths,
                            window=window, k_scale=k_scale, v_scale=v_scale,
                            interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("window",))
def flash_prefill(q, k_pages, v_pages, page_table, lengths,
                  window: Optional[int] = None,
                  k_scale=None, v_scale=None):
    """Chunked-prefill paged attention. q: (B, C, KV, G, hd) — one prompt
    CHUNK of grouped queries at absolute positions [lengths[b], lengths[b]+C)
    whose own k/v are already appended to the pool
    (``repro.nn.cache.append_paged_chunk``). For int8 pools pass the
    per-page fp32 ``k_scale``/``v_scale`` arrays (fused dequant). Returns
    the fully-normalized fp32 output over [committed history || intra-chunk
    causal] — the serving ingest counterpart of ``flash_decode``."""
    return _fp.flash_prefill(q, k_pages, v_pages, page_table, lengths,
                             window=window, k_scale=k_scale, v_scale=v_scale,
                             interpret=_interpret())
