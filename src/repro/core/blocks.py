"""DiffusionBlocks conversion (paper §3.1–3.3) — the framework's core.

``DiffusionBlocksModel`` wraps any family model (``repro.models``) and exposes:

  * block partitioning: unit ranges per block + equi-probability noise ranges;
  * per-block training losses (paper Eq. 6) via the AR adapter (App. E.4),
    in ``concat`` (clean‖noisy single stream, modified causal mask) or
    ``two_pass`` (paired streams; required for SSM/hybrid) mode;
  * end-to-end baseline loss (vanilla next-token CE) for the comparisons;
  * block-wise inference: the Euler sampler (Eq. 5) that denoises the next
    token's embedding through the blocks, plus ``serve_step`` used by the
    dry-run decode shapes.

Block independence is structural: ``block_loss(params, b, …)`` only ever
*reads* units[start_b : start_b+size_b] (+ shared embed/head/cond), so
gradients for other blocks are never materialized.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import precision as precision_mod
from repro import tracing
from repro.configs.base import HYBRID, SSM, DBConfig, ModelConfig
from repro.core import edm
from repro.core import partition as P
from repro.models import build_model
from repro.models.common import LayerCtx
from repro.nn import attention as A
from repro.nn.scan_util import uscan


def chunked_ce(model, params, h: jax.Array, targets: jax.Array,
               chunk: int = 512) -> jax.Array:
    """Memory-safe cross-entropy through the readout: the (S, vocab) logits
    are never materialized for the full sequence — per-chunk logits are
    computed, reduced, and REMATERIALIZED in the backward pass
    (jax.checkpoint). Standard production-LM trick; cuts the loss memory from
    O(S·V) to O(chunk·V)."""
    B, S = targets.shape
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)), constant_values=-1)
    nc = h.shape[1] // chunk
    hc = h.reshape(B, nc, chunk, -1).transpose(1, 0, 2, 3)
    tc = targets.reshape(B, nc, chunk).transpose(1, 0, 2)

    @jax.checkpoint
    def one(h_i, t_i):
        logits = model.logits(params, h_i)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        tgt = jnp.maximum(t_i, 0)
        ce = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
        return jnp.sum(jnp.where(t_i >= 0, ce, 0.0))

    def step(tot, xs):
        h_i, t_i = xs
        return tot + one(h_i, t_i), None

    total, _ = uscan(step, jnp.zeros((), jnp.float32), (hc, tc))
    return total / (B * S)


def _needs_two_pass(cfg: ModelConfig) -> bool:
    """SSM recurrences have no attention mask — the concat trick does not
    apply (DESIGN.md §Arch-applicability)."""
    return cfg.family in (HYBRID, SSM)


class DiffusionBlocksModel:
    def __init__(self, cfg: ModelConfig, db: DBConfig,
                 distribution: Optional[Sequence[int]] = None):
        self.cfg = cfg
        self.db = db
        self.model = build_model(cfg, db)
        self.edges = P.sigma_edges(db)                     # descending, B+1
        self.ranges = P.unit_ranges(self.model.n_units, db.num_blocks,
                                    distribution)
        self.causal_mode = ("two_pass" if _needs_two_pass(cfg)
                            else db.causal_mode)

    # ------------------------------------------------------------------
    @property
    def num_blocks(self) -> int:
        return self.db.num_blocks

    def init(self, rng, dtype=jnp.float32):
        return self.model.init(rng, dtype)

    def sample_block_sigma(self, rng, shape, b: int) -> jax.Array:
        q_lo, q_hi = P.block_qrange(self.db, b, with_overlap=True)
        return edm.sample_sigma_in_qrange(rng, shape, self.db, q_lo, q_hi)

    # ------------------------------------------------------------------
    # conditioning inputs (modality frontends live on the model —
    # ``model.encode_conditioning`` is the ONE code path shared by the
    # training losses, the dense dry-run shapes, and the serving engine's
    # admission-time encode)
    # ------------------------------------------------------------------
    def make_ctx(self, params, S: int, mode: str, sigma=None,
                 aux_inputs: Optional[Dict[str, jax.Array]] = None,
                 precision=None, cond_lengths=None, **kw) -> LayerCtx:
        ctx = LayerCtx(cfg=self.cfg, mode=mode, positions=jnp.arange(S),
                       precision=precision_mod.get_policy(precision),
                       cond_lengths=cond_lengths, **kw)
        if sigma is not None:
            ctx.cond = self.model.cond(params, jnp.log(sigma.reshape(-1)))
        # decode reads cross-attention K/V from the cache (filled at prefill
        # or at engine admission); re-encoding the modality frontend per
        # decode step would be wasted.
        if mode != "decode":
            kv_x = self.model.encode_conditioning(params, aux_inputs, ctx)
            if kv_x is not None:
                ctx.kv_x = kv_x
                ctx.kv_positions = jnp.arange(kv_x.shape[1])
        return ctx

    # ------------------------------------------------------------------
    # Training losses
    # ------------------------------------------------------------------
    def block_loss(self, params, b: int, tokens: jax.Array, rng,
                   aux_inputs=None, impl: str = "auto",
                   unit_range: Optional[Tuple[int, int]] = None,
                   sigma_qrange: Optional[Tuple] = None,
                   precision=None) -> Tuple[jax.Array, Dict]:
        """Paper Eq. (6) for the AR adapter: noisy slot i carries
        z_i = emb(x_i) + σ ε, conditioned on clean x_{<i}; the block denoises
        it and CE is taken through the readout. σ ~ p_noise restricted to
        block b's (overlap-expanded) range, one σ per example.

        ``sigma_qrange`` overrides the block-derived (q_lo, q_hi) noise range
        with (possibly traced) values — the block-parallel engine trains all
        blocks in one program, so the range must be data, not a constant.

        ``precision`` (repro.precision policy) sets the compute dtype of the
        hidden stream; the σ-preconditioning, denoiser combine, and loss
        reductions stay fp32 regardless (reduce_dtype)."""
        pol = precision_mod.get_policy(precision)
        cd = pol.compute_for(self.cfg.family)
        Bsz, S = tokens.shape
        start, size = unit_range if unit_range is not None else self.ranges[b]
        with tracing.scope(tracing.NOISE):
            r_sig, r_eps = jax.random.split(rng)
            if sigma_qrange is not None:
                q_lo, q_hi = sigma_qrange
                sigma = edm.sample_sigma_in_qrange(r_sig, (Bsz, 1, 1),
                                                   self.db, q_lo, q_hi)
            else:
                sigma = self.sample_block_sigma(r_sig, (Bsz, 1, 1), b)

            table = self.model.embedding_table(params)
            emb_clean = table[tokens]
            z, _ = edm.add_noise(r_eps, emb_clean.astype(jnp.float32), sigma)
            c_skip, c_out, c_in, _ = edm.preconditioning(sigma,
                                                         self.db.sigma_data)
            z_in = (c_in * z).astype(cd)
            n = 2 * S if self.causal_mode == "concat" else S
            ctx = self.make_ctx(params, n, "train", sigma, aux_inputs,
                                impl=impl, precision=pol)

        if self.causal_mode == "concat":
            with tracing.scope(tracing.NOISE):
                stream = jnp.concatenate([emb_clean.astype(cd), z_in], axis=1)
                ctx.mask_mod = A.db_concat_mask(S)
                ctx.rope_positions = jnp.concatenate(
                    [jnp.arange(S), jnp.arange(S)])
                ctx.cond_mask = jnp.arange(2 * S) >= S
            with tracing.scope(tracing.LAYERS):
                h, _, aux = self.model.apply_units(params, stream, start,
                                                   size, ctx)
                f_out = h[:, S:]
        else:
            with tracing.scope(tracing.LAYERS):
                _, f_out, aux = self.model.apply_units_two_pass(
                    params, emb_clean.astype(cd), z_in, start, size, ctx)

        loss, metrics = self._readout_loss(params, f_out, z, emb_clean,
                                           sigma, tokens, impl)
        metrics.update({"loss": loss, "aux": aux,
                        "sigma_mean": jnp.mean(sigma)})
        if self.cfg.moe is not None:
            loss = loss + self.cfg.moe.router_aux_weight * aux
        return loss, metrics

    @tracing.scope(tracing.READOUT_CE)
    def _readout_loss(self, params, f_out, z, emb_clean, sigma, tokens,
                      impl):
        """The block's loss from its output F: Eq. (6) in F-space (``l2``)
        or CE through the readout of the denoised embedding."""
        Bsz = tokens.shape[0]
        if self.db.loss == "l2":
            # Eq. (6) score matching in F-space (continuous targets): the
            # fused kernel never materializes the (y − c_skip z)/c_out target
            # in HBM; fwd AND bwd run through the custom-VJP Pallas path.
            sig_b = sigma.reshape(Bsz)
            f32 = f_out.astype(jnp.float32)
            y32 = emb_clean.astype(jnp.float32)
            if impl == "kernels":
                from repro.kernels import ops as kops
                loss = kops.edm_loss(f32, z, y32, sig_b,
                                     sigma_data=self.db.sigma_data)
            else:
                loss = edm.edm_l2_loss(f32, z, y32, sigma, self.db.sigma_data)
            return loss, {"l2": loss}
        d_hat = edm.denoise_combine(z, f_out.astype(jnp.float32), sigma,
                                    self.db.sigma_data)
        loss = chunked_ce(self.model, params,
                          d_hat.astype(emb_clean.dtype), tokens)
        return loss, {"ce": loss}

    def e2e_loss(self, params, tokens, rng=None, aux_inputs=None,
                 impl: str = "auto", precision=None):
        """Standard end-to-end next-token CE over the FULL stack — the
        backprop baseline the paper compares against (model built with the
        same AdaLN params; cond=None keeps them inert)."""
        pol = precision_mod.get_policy(precision)
        Bsz, S = tokens.shape
        ctx = self.make_ctx(params, S, "train", None, aux_inputs, impl=impl,
                            precision=pol)
        h = self.model.embed(params, tokens,
                             dtype=pol.compute_for(self.cfg.family))
        h, _, aux = self.model.apply_units(params, h, 0, self.model.n_units,
                                           ctx)
        loss = chunked_ce(self.model, params, h[:, :-1], tokens[:, 1:])
        metrics = {"ce": loss, "aux": aux}
        if self.cfg.moe is not None:
            loss = loss + self.cfg.moe.router_aux_weight * aux
        return loss, metrics

    # ------------------------------------------------------------------
    # Inference: block-wise Euler sampling of the next token (App. B / H)
    # ------------------------------------------------------------------
    def denoise_schedule(self, steps_per_block: int = 1) -> list:
        """[(block, σ_from, σ_to)] — descending; the last step lands on 0."""
        out = []
        Bn = self.num_blocks
        for b in range(Bn):
            hi, lo = float(self.edges[b]), float(self.edges[b + 1])
            if b == Bn - 1:
                lo = 0.0
            qs = np.linspace(hi, lo, steps_per_block + 1)
            for i in range(steps_per_block):
                out.append((b, float(qs[i]), float(qs[i + 1])))
        return out

    def _probe_block(self, params, b: int, z: jax.Array, sigma: float,
                     cache, pos, ctx_base: LayerCtx) -> jax.Array:
        """Run block b's units over one noisy token (decode probe:
        ``commit=False`` — caches are read, never appended). Returns F
        (B,1,d)."""
        start, size = self.ranges[b]
        sig = jnp.full((z.shape[0], 1, 1), sigma, jnp.float32)
        _, _, c_in, _ = edm.preconditioning(sig, self.db.sigma_data)
        ctx = dataclasses.replace(ctx_base, mode="decode", pos=pos,
                                  commit=False)
        ctx.cond = self.model.cond(params, jnp.log(sig.reshape(-1)))
        sub_cache = jax.tree_util.tree_map(
            lambda c: c[start:start + size], cache)
        h = (c_in * z).astype(z.dtype)
        h, _, _ = self.model.apply_units(params, h, start, size, ctx,
                                         sub_cache)
        return h

    @tracing.scope(tracing.PROBE)
    def denoise_next_token(self, params, cache, pos, rng, ctx_base,
                           steps_per_block: int = 1) -> jax.Array:
        """Full Euler chain (σ_max → 0) for the token at ``pos`` (dense
        caches) or at each slot's ``ctx_base.lengths`` (paged serving cache).
        Returns the denoised embedding D (B,1,d).

        ``steps_per_block`` is a PYTHON int: the schedule is unrolled at
        trace time, so under ``jax.jit`` it MUST be a static argument — each
        distinct value compiles its own program, and passing it as a traced
        value fails. ``launch.serve`` bakes it into the jitted engine
        closures once; ad-hoc callers should use
        ``static_argnames=("steps_per_block",)`` rather than thrashing the
        jit cache with wrapper lambdas."""
        batch = (ctx_base.lengths.shape[0] if ctx_base.lengths is not None
                 else self.model.cache_batch(cache))
        d = self.cfg.d_model
        z = self.db.sigma_max * jax.random.normal(rng, (batch, 1, d))
        for b, s_from, s_to in self.denoise_schedule(steps_per_block):
            f = self._probe_block(params, b, z, s_from, cache, pos, ctx_base)
            sig = jnp.asarray(s_from, jnp.float32)
            d_hat = edm.denoise_combine(z, f.astype(jnp.float32), sig,
                                        self.db.sigma_data)
            z = edm.euler_step(z, d_hat, s_from, max(s_to, 0.0)) \
                if s_to > 0 else d_hat
            z = z.astype(f.dtype)
        return z

    @tracing.scope(tracing.COMMIT)
    def commit_token(self, params, cache, pos, token, ctx_base):
        """Append the chosen clean token to every unit's cache in ONE scan.

        Training-consistent: each block's clean stream starts from RAW token
        embeddings (blocks are independent denoisers — block b never sees
        block b-1's output). The scan body resets the hidden stream to the
        embedding at every block boundary (``reset_mask``), so the commit
        traces a single ``lax.scan`` over ALL units — tracing cost no longer
        scales with ``num_blocks`` (the seed looped blocks in Python and
        re-concatenated the cache pytree per token). Total cost is still L
        layer evaluations."""
        ctx = dataclasses.replace(ctx_base, mode="decode", pos=pos, cond=None)
        pol = precision_mod.get_policy(ctx.precision)
        # absolute-position-embedding families (whisper) embed the token at
        # its true offset: per-slot lengths on the paged path, pos on dense
        if ctx.lengths is not None:
            epos = ctx.lengths[:, None]
        elif pos is not None:
            epos = jnp.asarray(pos).reshape(1, 1)
        else:
            epos = None
        emb = self.model.embed(params, token,
                               dtype=pol.compute_for(self.cfg.family),
                               positions=epos)
        starts = self._block_starts()
        _, new_cache, _ = self.model.apply_units(
            params, emb, 0, self.model.n_units, ctx, cache,
            reset_mask=starts)
        return new_cache

    def _block_starts(self) -> jax.Array:
        starts = np.zeros(self.model.n_units, dtype=bool)
        for b in range(self.num_blocks):
            starts[self.ranges[b][0]] = True
        return jnp.asarray(starts)

    @tracing.scope(tracing.SAMPLE)
    def sample_token(self, logits, rng, temperature: float = 0.0,
                     top_k: int = 0):
        """Greedy (``temperature == 0``) or temperature / top-k sampling.
        Both are fully traced — temperature/top_k are static Python values
        selecting the trace, rng is data — so sampling lives INSIDE the
        scan-fused generation loop (no per-token host round-trip)."""
        logits = logits.astype(jnp.float32)
        if temperature == 0.0:
            return jnp.argmax(logits, axis=-1)
        logits = logits / temperature
        if top_k and top_k < logits.shape[-1]:
            kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
            logits = jnp.where(logits < kth, -jnp.inf, logits)
        return jax.random.categorical(rng, logits)

    @tracing.scope(tracing.SAMPLE)
    def readout_logits(self, params, d_final):
        """The readout of the denoised embedding, named with sampling."""
        return self.model.logits(params, d_final)

    def serve_step(self, params, cache, pos, rng, aux_inputs=None,
                   steps_per_block: int = 1, temperature: float = 0.0,
                   top_k: int = 0, cond_lengths=None):
        """One generation step over DENSE caches: denoise token at ``pos``
        through the blocks, sample, commit. This is what decode dry-run
        shapes lower; the paged serving engine uses ``serve_step_paged``.
        ``cond_lengths`` masks the cross (conditioning) blocks per row when
        the dense cache was filled via ``model.set_conditioning`` (ragged
        conditioning); None keeps the unmasked read of prefill-sized blocks.
        ``steps_per_block``/``temperature``/``top_k`` are static under jit
        (see denoise_next_token). Returns (token (B,), new_cache)."""
        ctx_base = self.make_ctx(params, 1, "decode", None, aux_inputs,
                                 cond_lengths=cond_lengths)
        ctx_base.positions = None
        r_noise, r_samp = jax.random.split(rng)
        d_final = self.denoise_next_token(params, cache, pos, r_noise,
                                          ctx_base, steps_per_block)
        logits = self.readout_logits(params, d_final)
        token = self.sample_token(logits[:, 0], r_samp, temperature, top_k)
        new_cache = self.commit_token(params, cache, pos, token[:, None],
                                      ctx_base)
        return token, new_cache

    # ------------------------------------------------------------------
    # Paged serving steps (repro.nn.cache pools; used by launch.serve)
    # ------------------------------------------------------------------
    def _paged_ctx(self, params, lengths, page_table, active, precision,
                   impl, cond_lengths=None) -> LayerCtx:
        ctx = self.make_ctx(params, 1, "decode", None, None,
                            precision=precision, impl=impl,
                            cond_lengths=cond_lengths)
        ctx.positions = None
        ctx.lengths = lengths
        ctx.page_table = page_table
        ctx.active = active
        return ctx

    def serve_step_paged(self, params, kv, page_table, lengths, rng, *,
                         active=None, steps_per_block: int = 1,
                         temperature: float = 0.0, top_k: int = 0,
                         precision=None, impl: str = "auto",
                         cond_lengths=None):
        """One generation step over the PAGED serving cache: each slot
        denoises + commits at its OWN position ``lengths[b]`` (ragged batches
        share this one trace). ``active`` masks slots that commit this step —
        inactive slots compute but write nothing (KV appends are redirected
        to the trash page, recurrent states held). Conditioned slots read
        their cross memory from the cache (written once at admission by
        ``model.set_conditioning``) under the per-slot valid length
        ``cond_lengths`` — aux inputs are never re-encoded per step. Keyword
        config is static under jit. Returns (token (B,), new_kv,
        new_lengths)."""
        ctx = self._paged_ctx(params, lengths, page_table, active, precision,
                              impl, cond_lengths)
        r_noise, r_samp = jax.random.split(rng)
        d_final = self.denoise_next_token(params, kv, None, r_noise, ctx,
                                          steps_per_block)
        logits = self.readout_logits(params, d_final)
        token = self.sample_token(logits[:, 0], r_samp, temperature, top_k)
        new_kv = self.commit_token(params, kv, None, token[:, None], ctx)
        new_lengths = lengths + (active.astype(lengths.dtype)
                                 if active is not None else 1)
        return token, new_kv, new_lengths

    def commit_prompt_token(self, params, kv, page_table, lengths, token, *,
                            active=None, precision=None, impl: str = "auto",
                            cond_lengths=None):
        """Prefill building block: commit a known (prompt) token at each
        slot's ``lengths[b]`` without the denoising probe. Returns
        (new_kv, new_lengths)."""
        ctx = self._paged_ctx(params, lengths, page_table, active, precision,
                              impl, cond_lengths)
        new_kv = self.commit_token(params, kv, None, token, ctx)
        new_lengths = lengths + (active.astype(lengths.dtype)
                                 if active is not None else 1)
        return new_kv, new_lengths

    @tracing.scope(tracing.COMMIT)
    def commit_prompt_chunk(self, params, kv, page_table, lengths, tokens, *,
                            n_valid, precision=None, impl: str = "auto",
                            cond_lengths=None):
        """Chunked-prefill building block: commit up to C known (prompt)
        tokens per slot in ONE dispatch — a prompt of S tokens costs
        ceil(S / C) of these instead of S ``commit_prompt_token`` steps.

        tokens: (B, C) — slot b's next prompt tokens starting at its own
        offset ``lengths[b]`` (entries past ``n_valid[b]`` are padding:
        attention writes them to the trash page, recurrent states hold).
        Each block's clean stream restarts from raw embeddings at the block
        boundaries exactly as in ``commit_token``; attention layers append
        the chunk's K/V to pool pages and attend [history || intra-chunk
        causal] via ``cache.paged_prefill_attention`` (the flash-prefill
        kernel under ``impl="kernels"``); recurrent units advance their
        state over the chunk with one in-dispatch scan.

        Returns (new_kv, lengths + n_valid).
        """
        ctx = self._paged_ctx(params, lengths, page_table, None, precision,
                              impl, cond_lengths)
        ctx.mode = "prefill_chunk"
        ctx.n_valid = n_valid
        pol = precision_mod.get_policy(ctx.precision)
        C = tokens.shape[1]
        epos = lengths[:, None] + jnp.arange(C, dtype=lengths.dtype)[None, :]
        emb = self.model.embed(params, tokens,
                               dtype=pol.compute_for(self.cfg.family),
                               positions=epos)
        _, new_kv, _ = self.model.apply_units(
            params, emb, 0, self.model.n_units, ctx, kv,
            reset_mask=self._block_starts())
        return new_kv, lengths + n_valid

    def prefill_probe(self, params, tokens, k: int, aux_inputs=None,
                      impl: str = "auto"):
        """Dry-run probe: prefill over only the first k units (cost
        extrapolation — see launch/dryrun.py)."""
        S = tokens.shape[1]
        ctx = self.make_ctx(params, S, "prefill", None, aux_inputs, impl=impl)
        emb = self.model.embed(params, tokens)
        h, sub, _ = self.model.apply_units(params, emb, 0, k, ctx)
        return self.model.logits(params, h[:, -1:]), sub

    def prefill(self, params, tokens, aux_inputs=None, impl: str = "auto"):
        """Clean-stream prefill of all units' caches over a prompt. Each
        block's clean stream starts from raw embeddings (see commit_token)."""
        S = tokens.shape[1]
        ctx = self.make_ctx(params, S, "prefill", None, aux_inputs, impl=impl)
        emb = self.model.embed(params, tokens)
        parts, h_last = [], None
        for b in range(self.num_blocks):
            start, size = self.ranges[b]
            h_last, sub, _ = self.model.apply_units(params, emb, start, size,
                                                    ctx)
            parts.append(sub)
        cache = jax.tree_util.tree_map(
            lambda *xs: jnp.concatenate(xs, axis=0), *parts)
        logits = self.model.logits(params, h_last[:, -1:])
        return logits, cache
