"""Shared layer machinery for all architecture families.

``LayerCtx`` threads everything a layer needs through scans: conditioning
(AdaLN mods from σ), positions, mask construction, KV caches, execution mode.

Modes:
  train      — full sequence, causal (+SWA) mask
  prefill    — like train, additionally returns KV/state caches
  decode     — one token + cache
  prefill_chunk — C prompt tokens + PAGED cache: attention layers append the
               whole chunk's K/V to pool pages and attend [history ||
               intra-chunk causal] in one shot (``cache.paged_prefill_
               attention`` / the flash-prefill kernel); recurrent layers
               advance their state over the chunk with one in-dispatch scan.
               Per-slot ``ctx.n_valid`` bounds real tokens (ragged tails
               write to the trash page / hold recurrent state)
  db_concat  — DB AR training, [clean || noisy] single stream, custom mask
               (paper App. E.4 concat variant; attention layers only)
  db_two_pass— DB AR training, paired (clean, noisy) streams; noisy stream is
               denoised against the clean prefix state (works for SSM too)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro import runtime, tracing
from repro.configs.base import ModelConfig
from repro.nn import layers as L
from repro.nn import attention as A
from repro.nn import adaln
from repro.nn import cache as KVC
from repro.nn.init import ParamSpec
from repro.nn.moe import moe_fwd, moe_spec


@dataclasses.dataclass
class LayerCtx:
    cfg: ModelConfig
    mode: str = "train"
    positions: Optional[jax.Array] = None       # mask positions (S,)
    rope_positions: Optional[jax.Array] = None  # rope phases (S,)
    mask_mod: Optional[Callable] = None
    cond: Optional[jax.Array] = None            # (B, d) sigma embedding, or None
    cond_mask: Optional[jax.Array] = None       # (S,) bool: where AdaLN applies
    pos: Any = None                             # decode: scalar position
    kv_x: Optional[jax.Array] = None            # cross-attn memory (B, Sk, d)
    kv_positions: Optional[jax.Array] = None
    impl: str = "auto"                          # attention impl
    precision: Any = None                       # repro.precision.Policy | None
    # ---- paged serving decode (repro.nn.cache) ----
    lengths: Optional[jax.Array] = None         # (B,) committed tokens / slot
    page_table: Optional[jax.Array] = None      # (B, n_logical_pages) int32
    active: Optional[jax.Array] = None          # (B,) bool: slots that commit
    n_valid: Optional[jax.Array] = None         # (B,) prefill_chunk: real toks
    cond_lengths: Optional[jax.Array] = None    # (B,) valid conditioning toks
    #   per-slot length of the cross-attention (image/audio) memory block;
    #   0 = unconditioned slot (cross contributes exactly zero). None keeps
    #   the legacy unmasked read (dense caches sized to the true length).
    commit: bool = True                         # False = denoise probe (no append)
    q_chunk: int = dataclasses.field(default_factory=lambda: runtime.attn_chunk())
    kv_chunk: int = dataclasses.field(default_factory=lambda: runtime.attn_chunk())

    def dims(self) -> A.AttnDims:
        c = self.cfg
        return A.AttnDims(c.n_heads, c.n_kv_heads, c.head_dim, c.rope_theta)


def chunk_token_scan(step_fn, x, state, n_valid):
    """Advance a RECURRENT layer over a prefill chunk inside ONE dispatch.

    Attention layers ingest a chunk as one sequence-level call; recurrences
    (mamba / xLSTM) are inherently serial per token, so they advance with a
    ``lax.scan`` over the chunk's tokens instead — still killing the
    per-token dispatch, and numerically IDENTICAL to the per-token prefill
    (same decode-step math, same masked holds). ``step_fn(x_t (B,1,d),
    state) -> (y_t (B,1,d), new_state)``; slots whose valid tokens ran out
    (t >= n_valid[b]) hold their state. Returns (y (B,C,d), final_state)."""
    from repro.nn.scan_util import uscan
    S_c = x.shape[1]
    acts = jnp.arange(S_c)[:, None] < n_valid[None, :]      # (C, B)

    def tok(st, xs):
        xt, act = xs
        y_t, ns = step_fn(xt[:, None], st)
        return masked_state_update(ns, st, act), y_t[:, 0]

    new_state, ys = uscan(tok, state, (x.transpose(1, 0, 2), acts))
    return ys.transpose(1, 0, 2), new_state


def masked_state_update(new_state, old_state, active: Optional[jax.Array]):
    """Per-slot recurrent-state commit mask for ragged / continuous batching:
    inactive slots keep their old state. Leaves are (B, ...)-leading at the
    point of update (inside the unit scan). Attention KV needs no such mask —
    the paged append already redirects inactive writes to the trash page."""
    if active is None or old_state is None:
        return new_state
    return jax.tree_util.tree_map(
        lambda n, o: jnp.where(active.reshape((-1,) + (1,) * (n.ndim - 1)),
                               n, o), new_state, old_state)


def cross_cached_attn(p, x, ctx: LayerCtx, cache):
    """Cross-attention over a PRECOMPUTED per-slot (k, v) conditioning block
    (decode / prefill_chunk: the memory was projected once at prefill or at
    engine admission — re-encoding per step would be wasted). One code path
    for every conditioned family (VLM image blocks, encdec audio blocks).

    With ``ctx.cond_lengths`` the block is attended under a per-slot valid
    length (``cache.cross_attend``): the paged engine keeps one fixed-size
    block per slot and admits RAGGED conditioning, including length-0
    (unconditioned) slots in the same compiled program. Without it, the
    legacy unmasked read serves dense caches sized to the true length."""
    dims = ctx.dims()
    q, _, _ = A.project_qkv(p, x, dims)
    if ctx.cond_lengths is not None:
        out = KVC.cross_attend(q, cache["k"].astype(x.dtype),
                               cache["v"].astype(x.dtype), ctx.cond_lengths)
    else:
        out = A.attend(q, cache["k"].astype(x.dtype),
                       cache["v"].astype(x.dtype), mask_mod=None,
                       qpos=jnp.zeros((x.shape[1],), jnp.int32),
                       kpos=jnp.arange(cache["k"].shape[1]), impl="naive")
    out = out.reshape(*x.shape[:2], dims.n_heads * dims.head_dim)
    return out @ p["wo"].astype(x.dtype)


def project_cross_kv(p, cond, dims):
    """Project conditioning embeddings (B, Sk, d) into a cross block's
    (k, v) — the admission-time half of ``cross_cached_attn``, the same math
    ``attention.project_qkv`` applies to ``kv_x`` at dense prefill (the q
    projection is skipped: queries come from the text stream per step)."""
    B, Sk, _ = cond.shape
    k = cond @ p["wk"].astype(cond.dtype)
    v = cond @ p["wv"].astype(cond.dtype)
    if "bk" in p:
        k = k + p["bk"].astype(cond.dtype)
        v = v + p["bv"].astype(cond.dtype)
    k = k.reshape(B, Sk, dims.n_kv_heads, dims.head_dim)
    v = v.reshape(B, Sk, dims.n_kv_heads, dims.head_dim)
    return k, v


def write_cross_block(cross_cache, cross_params, cond, dims, block: int,
                      slot=None):
    """Write projected conditioning into per-slot cross blocks.

    cross_cache: {"k", "v"} with leaves (n_units, num_slots, block, KV, hd);
    cross_params: the stacked per-unit cross-attention params (leading
    n_units axis); cond: (B, Sk, d), zero-padded here to the fixed ``block``
    capacity so ONE compiled program serves every conditioning length.
    ``slot=None`` requires B == num_slots and overwrites every slot's block;
    an int32 ``slot`` (traced is fine) overwrites one slot's block, B == 1.
    The full block is always written, so a recycled slot can never observe a
    previous occupant's tail."""
    Sk = cond.shape[1]
    assert Sk <= block, f"conditioning length {Sk} exceeds block {block}"
    if Sk < block:
        cond = jnp.pad(cond, ((0, 0), (0, block - Sk), (0, 0)))
    k, v = jax.vmap(lambda p: project_cross_kv(p, cond, dims))(cross_params)
    k = k.astype(cross_cache["k"].dtype)       # (units, B, block, KV, hd)
    v = v.astype(cross_cache["v"].dtype)
    if slot is None:
        assert k.shape == cross_cache["k"].shape, (
            f"set_conditioning(slot=None) writes ALL slots: cond batch "
            f"{cond.shape[0]} != num_slots {cross_cache['k'].shape[1]}")
        return {"k": k, "v": v}
    start = (jnp.zeros((), jnp.int32), jnp.asarray(slot, jnp.int32)) + \
        (jnp.zeros((), jnp.int32),) * 3
    return {"k": jax.lax.dynamic_update_slice(cross_cache["k"], k, start),
            "v": jax.lax.dynamic_update_slice(cross_cache["v"], v, start)}


def default_mask(cfg: ModelConfig, bidirectional: bool = False):
    if bidirectional:
        return A.bidirectional_mask
    if cfg.sliding_window:
        return A.sliding_window_mask(cfg.sliding_window)
    return A.causal_mask


# ---------------------------------------------------------------------------
# Standard transformer layer (attention + MLP/MoE), with optional AdaLN
# ---------------------------------------------------------------------------

def tlayer_spec(cfg: ModelConfig, db: bool, *, cross: bool = False,
                moe_layer: bool = False):
    d = cfg.d_model
    dims = A.AttnDims(cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.rope_theta)
    spec = {
        "ln1": L.norm_spec(d, cfg.norm),
        "attn": A.attention_spec(d, dims, cfg.qkv_bias),
        "ln2": L.norm_spec(d, cfg.norm),
    }
    if moe_layer:
        assert cfg.moe is not None
        spec["moe"] = moe_spec(d, cfg.d_ff, cfg.moe, cfg.mlp)
    else:
        spec["mlp"] = L.mlp_spec(d, cfg.d_ff, cfg.mlp)
    if db:
        spec["adaln"] = adaln.adaln_spec(d, n_mods=6)
    if cross:
        # gate for cross-attn output (llama-3.2-vision style tanh gate)
        spec["xgate"] = ParamSpec((1,), (None,), "zeros")
    return spec


def _mods(params, ctx: LayerCtx):
    if ctx.cond is None or "adaln" not in params:
        return (None,) * 6
    return adaln.adaln_mods(params["adaln"], ctx.cond, ctx.cfg.d_model, 6)


def _norm_modulate(p_ln, h, ctx: LayerCtx, shift, scale, cond_mask):
    """norm → AdaLN modulate; under ``impl="kernels"`` the non-parametric-LN
    case fuses both into one Pallas pass (custom-VJP backward). Parametric
    norms (rmsnorm/layernorm carry a weight the kernel does not apply) and the
    cond-masked concat path keep the jnp composition."""
    if (ctx.impl == "kernels" and shift is not None and cond_mask is None
            and ctx.cfg.norm == "nonparam_ln" and shift.ndim == 3
            and shift.shape[1] == 1):   # (B, 1, d) per-example mods only
        from repro.kernels import ops as kops
        return kops.ln_modulate(h, scale[:, 0], shift[:, 0])
    return adaln.modulate(L.apply_norm(p_ln, h, ctx.cfg.norm), shift, scale,
                          cond_mask)


def tlayer_apply(params, h, ctx: LayerCtx, *, cross: bool = False,
                 moe_layer: bool = False, bidirectional: bool = False,
                 cache=None):
    """Returns (h, new_cache, aux_loss)."""
    cfg = ctx.cfg
    aux = jnp.zeros((), jnp.float32)
    cm = ctx.cond_mask
    with tracing.scope(tracing.ADALN):
        s1, c1, g1, s2, c2, g2 = _mods(params, ctx)
        x = _norm_modulate(params["ln1"], h, ctx, s1, c1, cm)
    with tracing.scope(tracing.ATTN):
        attn_out, new_cache = _tlayer_attention(params, x, ctx, cross,
                                                bidirectional, cache)
    with tracing.scope(tracing.ADALN):
        h = adaln.gate(h, attn_out, g1, cm, impl=ctx.impl)
        x = _norm_modulate(params["ln2"], h, ctx, s2, c2, cm)
    with tracing.scope(tracing.MLP):
        if moe_layer:
            mlp_out, aux = moe_fwd(params["moe"], x, cfg.moe, cfg.mlp)
        else:
            mlp_out = L.apply_mlp(params["mlp"], x, cfg.mlp)
    with tracing.scope(tracing.ADALN):
        h = adaln.gate(h, mlp_out, g2, cm, impl=ctx.impl)
    return h, new_cache, aux


def _tlayer_attention(params, x, ctx: LayerCtx, cross: bool,
                      bidirectional: bool, cache):
    """The attention half of ``tlayer_apply`` on the normed input ``x``:
    (attention output, new cache) for the layer's mode."""
    cfg = ctx.cfg
    dims = ctx.dims()
    if ctx.mode in ("decode", "prefill_chunk") and not cross:
        if isinstance(cache, KVC.PagedKV):
            if ctx.mode == "prefill_chunk":
                attn_out, new_cache = KVC.paged_prefill_attention(
                    params["attn"], x, dims, cache, lengths=ctx.lengths,
                    page_table=ctx.page_table, n_valid=ctx.n_valid,
                    window=cfg.sliding_window, impl=ctx.impl)
            else:
                attn_out, new_cache = KVC.paged_decode_attention(
                    params["attn"], x, dims, cache, lengths=ctx.lengths,
                    page_table=ctx.page_table, active=ctx.active,
                    commit=ctx.commit, window=cfg.sliding_window,
                    impl=ctx.impl)
        else:
            if ctx.mode == "prefill_chunk":
                raise NotImplementedError(
                    "prefill_chunk requires the paged cache "
                    "(repro.nn.cache); dense caches prefill per-token")
            attn_out, new_cache = A.decode_attention(
                params["attn"], x, dims, cache, ctx.pos,
                window=cfg.sliding_window, kv_chunk=ctx.kv_chunk,
                impl=ctx.impl)
    elif cross:
        # cross-attention to ctx.kv_x (image/audio memory); cache holds
        # precomputed (k, v) in decode/prefill reuse.
        if cache is not None and ctx.mode in ("decode", "prefill_chunk"):
            attn_out = cross_cached_attn(params["attn"], x, ctx, cache)
            new_cache = cache
        else:
            if ctx.kv_x is None:
                raise ValueError(
                    "cross-attention layer with no conditioning memory: "
                    "pass aux_inputs (image_embs/audio_embs) on the dense "
                    "train/prefill path — the serving engine admits "
                    "unconditioned requests via cond_lengths=0 instead")
            attn_out, (k, v) = A.attention_fwd(
                params["attn"], x, dims, positions=ctx.positions,
                mask_mod=None, kv_x=ctx.kv_x,
                kv_positions=ctx.kv_positions, impl=ctx.impl,
                q_chunk=ctx.q_chunk, kv_chunk=ctx.kv_chunk)
            new_cache = {"k": k, "v": v} if ctx.mode == "prefill" else None
        attn_out = attn_out * jnp.tanh(params["xgate"].astype(attn_out.dtype))
    else:
        mask_mod = ctx.mask_mod or default_mask(cfg, bidirectional)
        attn_out, (k, v) = A.attention_fwd(
            params["attn"], x, dims, positions=ctx.positions,
            mask_mod=mask_mod, rope_positions=ctx.rope_positions,
            impl=ctx.impl, q_chunk=ctx.q_chunk, kv_chunk=ctx.kv_chunk)
        new_cache = {"k": k, "v": v} if ctx.mode == "prefill" else None
    return attn_out, new_cache


def two_pass_mask(seq_len: int):
    """Mask for two-pass DB attention: q are the S noisy tokens; keys are
    [clean(0..S-1) || noisy_diag(0..S-1)]. Noisy query i sees clean j < i and
    its own noisy key (position S+i)."""
    S = seq_len

    def mask(qpos, kpos):
        q = qpos[:, None]          # noisy query index i (0..S-1)
        k = kpos[None, :]
        clean = (k < S) & (k < q)
        self_k = k == q + S
        return clean | self_k
    mask.kernel_mask = ("two_pass", None, S)
    return mask


def tlayer_two_pass(params, h_clean, h_noisy, ctx: LayerCtx, *,
                    moe_layer: bool = False):
    """DB two-pass for an attention layer: clean stream runs standard causal;
    noisy stream attends clean past + own noisy kv. Returns (clean, noisy, aux)."""
    cfg = ctx.cfg
    dims = ctx.dims()
    S = h_clean.shape[1]
    s1, c1, g1, s2, c2, g2 = _mods(params, ctx)
    aux = jnp.zeros((), jnp.float32)

    # --- attention ---
    xc = L.apply_norm(params["ln1"], h_clean, cfg.norm)          # clean: no mods
    xn = _norm_modulate(params["ln1"], h_noisy, ctx, s1, c1, None)
    qc, kc, vc = A.project_qkv(params["attn"], xc, dims)
    qn, kn, vn = A.project_qkv(params["attn"], xn, dims)
    pos = ctx.positions if ctx.positions is not None else jnp.arange(S)
    qc = L.apply_rope(qc, pos, dims.rope_theta)
    kc = L.apply_rope(kc, pos, dims.rope_theta)
    qn = L.apply_rope(qn, pos, dims.rope_theta)
    kn = L.apply_rope(kn, pos, dims.rope_theta)
    base_mask = ctx.mask_mod or default_mask(cfg, False)
    oc = A.attend(qc, kc, vc, mask_mod=base_mask, qpos=pos, kpos=pos,
                  impl=ctx.impl, q_chunk=ctx.q_chunk, kv_chunk=ctx.kv_chunk)
    k_cat = jnp.concatenate([kc, kn], axis=1)
    v_cat = jnp.concatenate([vc, vn], axis=1)
    kpos_cat = jnp.concatenate([pos, pos + S])
    on = A.attend(qn, k_cat, v_cat, mask_mod=two_pass_mask(S), qpos=pos,
                  kpos=kpos_cat, impl=ctx.impl, q_chunk=ctx.q_chunk,
                  kv_chunk=ctx.kv_chunk)
    proj = lambda o: o.reshape(*o.shape[:2], dims.n_heads * dims.head_dim) \
        @ params["attn"]["wo"].astype(o.dtype)
    h_clean = h_clean + proj(oc)
    h_noisy = adaln.gate(h_noisy, proj(on), g1, impl=ctx.impl)

    # --- mlp ---
    xc = L.apply_norm(params["ln2"], h_clean, cfg.norm)
    xn = _norm_modulate(params["ln2"], h_noisy, ctx, s2, c2, None)
    if moe_layer:
        mc, aux1 = moe_fwd(params["moe"], xc, cfg.moe, cfg.mlp)
        mn, aux2 = moe_fwd(params["moe"], xn, cfg.moe, cfg.mlp)
        aux = aux1 + aux2
    else:
        mc = L.apply_mlp(params["mlp"], xc, cfg.mlp)
        mn = L.apply_mlp(params["mlp"], xn, cfg.mlp)
    h_clean = h_clean + mc
    h_noisy = adaln.gate(h_noisy, mn, g2, impl=ctx.impl)
    return h_clean, h_noisy, aux
