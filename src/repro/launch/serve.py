"""High-throughput block-wise serving: chunked prefill + scan-fused decode
over a paged bf16 KV cache, with static and continuous-batching schedulers
and a shared-prefix page cache.

The seed served one jitted dispatch PLUS a host sync per generated token and
kept a dense fp32 worst-case cache slab; PR 3 fused decode into one scan but
still committed ONE prompt token per scan step, so time-to-first-token scaled
with prompt length. This engine:

  * prefills prompts in CHUNKS of ``chunk_size`` tokens: each chunk is one
    sequence-level attention dispatch (``blocks.commit_prompt_chunk`` →
    ``cache.paged_prefill_attention`` / the Pallas flash-prefill kernel), so
    a prompt of S tokens costs ceil(S / C) serial attention steps instead of
    S — the per-token scan stays available as ``prefill="per-token"`` and is
    the numerical reference;
  * folds the whole denoise → sample → commit loop into ONE jitted
    ``lax.scan`` over new-token positions (greedy and temperature/top-k both
    traced — no per-token host round-trip);
  * handles ragged prompts inside one program with per-slot offsets and
    activity masks (masking is length-aware, never shape-aware);
  * stores KV in the paged pool of ``repro.nn.cache`` (bf16 under the
    default ``precision="bf16"`` policy, fp32 logsumexp in the attend);
  * optionally routes attention through the split-KV Pallas kernels
    (``--impl kernels``): flash-decode for generation, flash-prefill for
    ingest;
  * optionally shares prompt-PREFIX pages across requests
    (``prefix_cache=True``): finished prompts register their full prefix
    pages (hashed by token content) in a refcounted trie; a new request
    whose prompt extends a cached prefix maps those pages read-only and
    prefills only its non-shared suffix. Pages are copy-on-write: the first
    divergent write into a shared page (a matched partial tail page at
    admission, or a registered page the owner keeps generating into) gets a
    private copy first (``cache.copy_pool_pages``).

Schedulers (``--scheduler``):

  static      admit the whole batch, prefill (chunk scan), then one decode
              scan — O(1) dispatches for the entire batch of generations.
  continuous  slot-based continuous batching: a fixed number of request
              slots over a shared page pool. The host interleaves ONE
              prefill-chunk dispatch (advancing every still-prefilling slot
              by up to ``chunk_size`` tokens) with each ``seg_len``-step
              decode segment, so admitting a long prompt stalls decoding
              slots by at most one chunk per segment.

Compile-cache notes: ``steps_per_block`` / ``temperature`` / ``top_k`` /
``precision`` / ``impl`` / ``prefill`` / ``chunk_size`` are STATIC — they
select the trace. ``DecodeEngine`` instances are memoized per (dbm, static
config) by ``get_engine``, so repeated ``generate`` calls reuse compiled
programs; only a new padded prompt width or segment length triggers a
retrace.
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import threading
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import precision as precision_mod
from repro import runtime, tracing
from repro.configs import DBConfig, get_config, reduced
from repro.core import DiffusionBlocksModel
from repro.checkpoint import load_blocks
from repro.data import MarkovLM
from repro.launch.faults import WorkerDied
from repro.nn import cache as KVC

DEFAULT_CHUNK = 64


def _ragged_transition_accuracy(lm, seqs) -> float:
    """Mean legal-transition rate over variable-length sequences — scored
    per row so zero-padding never fabricates (or breaks) transitions."""
    return float(np.mean([lm.transition_accuracy(np.asarray(s)[None])
                          for s in seqs]))


class DecodeEngine:
    """Owns the jitted scan-fused programs for one (model, static config).

    All programs are length-aware over the paged cache:
      _prefill        per-token reference: scan over prompt positions,
                      committing where t < plens[b] (one serial attention
                      step per token — the seed ingest path)
      _prefill_chunks chunked prefill: scan over ceil(S/C) prompt CHUNKS;
                      each step commits up to C tokens per slot at its own
                      offset in ONE sequence-level attention dispatch
      _prefill_chunk1 a single chunk step (the continuous batcher interleaves
                      these with decode segments from the host)
      _decode         scan over new-token positions: denoise → sample → commit
      _serve          continuous-batching segment: each active slot either
                      commits its next PROMPT token (per-token mode) or a
                      GENERATED token
      _first_logits   the logits _decode / _serve sample a slot's next token
                      from (``next_token_logits``)
    """

    def __init__(self, dbm: DiffusionBlocksModel, *, steps_per_block: int = 1,
                 temperature: float = 0.0, top_k: int = 0,
                 precision="bf16", impl: str = "auto",
                 prefill: str = "chunked", chunk_size: int = DEFAULT_CHUNK):
        if prefill not in ("chunked", "per-token"):
            raise ValueError(f"prefill must be 'chunked' or 'per-token', "
                             f"got {prefill!r}")
        self.dbm = dbm
        self.pol = precision_mod.get_policy(precision)
        self.impl = impl
        self.prefill_mode = prefill
        self.chunk_size = int(chunk_size)
        self.temperature, self.top_k = float(temperature), int(top_k)
        self.dispatches = 0          # jitted-call count (throughput reporting)
        self.prefill_steps = 0       # serial attention steps spent in prefill
        pol, spb = self.pol, steps_per_block
        temp, tk = temperature, top_k
        Ck = self.chunk_size

        def prefill_scan(params, kv, page_table, lengths, prompts, plens,
                         cond_lengths):
            def body(carry, t):
                kv, lengths = carry
                act = t < plens
                tok = jnp.take(prompts, t, axis=1)
                kv, lengths = dbm.commit_prompt_token(
                    params, kv, page_table, lengths, tok[:, None],
                    active=act, precision=pol, impl=impl,
                    cond_lengths=cond_lengths)
                return (kv, lengths), None
            return jax.lax.scan(body, (kv, lengths),
                                jnp.arange(prompts.shape[1]))[0]

        def chunk_step(params, kv, page_table, lengths, prompt_buf, plens,
                       cond_lengths):
            # slot b's next chunk starts at its OWN offset lengths[b] (ragged
            # plens and prefix-cache hits put slots at different offsets)
            idx = lengths[:, None] + jnp.arange(Ck, dtype=lengths.dtype)
            tok = jnp.take_along_axis(
                prompt_buf, jnp.clip(idx, 0, prompt_buf.shape[1] - 1), axis=1)
            n_valid = jnp.clip(plens - lengths, 0, Ck)
            return dbm.commit_prompt_chunk(
                params, kv, page_table, lengths, tok, n_valid=n_valid,
                precision=pol, impl=impl, cond_lengths=cond_lengths)

        def prefill_chunk_scan(params, kv, page_table, lengths, prompts,
                               plens, cond_lengths, n_chunks):
            def body(carry, _):
                kv, lengths = carry
                return chunk_step(params, kv, page_table, lengths, prompts,
                                  plens, cond_lengths), None
            return jax.lax.scan(body, (kv, lengths), None, length=n_chunks)[0]

        def decode_scan(params, kv, page_table, lengths, stop_at, rng,
                        cond_lengths, n):
            def body(carry, _):
                kv, lengths, rng = carry
                rng, rs = jax.random.split(rng)
                act = lengths < stop_at
                tok, kv, lengths = dbm.serve_step_paged(
                    params, kv, page_table, lengths, rs, active=act,
                    steps_per_block=spb, temperature=temp, top_k=tk,
                    precision=pol, impl=impl, cond_lengths=cond_lengths)
                return (kv, lengths, rng), tok
            (kv, lengths, rng), toks = jax.lax.scan(
                body, (kv, lengths, rng), None, length=n)
            return kv, lengths, rng, toks.T          # (B, n)

        def step_logits(params, kv, ctx, rng):
            d = dbm.denoise_next_token(params, kv, None, rng, ctx, spb)
            return dbm.readout_logits(params, d)[:, 0]

        def first_logits(params, kv, page_table, lengths, rng,
                         cond_lengths):
            # the rng splits of the first step of serve_scan / decode_scan
            _, rs = jax.random.split(rng)
            rn, _ = jax.random.split(rs)
            ctx = dbm._paged_ctx(params, lengths, page_table, None, pol, impl,
                                 cond_lengths)
            return step_logits(params, kv, ctx, rn).astype(jnp.float32)

        def serve_scan(params, kv, page_table, lengths, prompt_buf, plens,
                       stop_at, active, rng, cond_lengths, n):
            def body(carry, _):
                kv, lengths, rng = carry
                rng, rs = jax.random.split(rng)
                in_prompt = lengths < plens
                idx = jnp.clip(lengths, 0, prompt_buf.shape[1] - 1)
                ptok = jnp.take_along_axis(prompt_buf, idx[:, None], 1)[:, 0]
                act = active & (lengths < stop_at)
                ctx = dbm._paged_ctx(params, lengths, page_table, act, pol,
                                     impl, cond_lengths)
                rn, rsamp = jax.random.split(rs)
                gtok = dbm.sample_token(step_logits(params, kv, ctx, rn),
                                        rsamp, temp, tk)
                tok = jnp.where(in_prompt, ptok, gtok)
                kv = dbm.commit_token(params, kv, None, tok[:, None], ctx)
                emitted = jnp.where(act & ~in_prompt, tok, -1)
                lengths = lengths + act.astype(lengths.dtype)
                return (kv, lengths, rng), emitted
            (kv, lengths, rng), toks = jax.lax.scan(
                body, (kv, lengths, rng), None, length=n)
            return kv, lengths, rng, toks.T          # (B, n); -1 = no emit

        self._prefill = jax.jit(prefill_scan)
        self._prefill_chunk1 = jax.jit(chunk_step)
        self._prefill_chunks = jax.jit(prefill_chunk_scan,
                                       static_argnames=("n_chunks",))
        self._decode = jax.jit(decode_scan, static_argnames=("n",))
        self._serve = jax.jit(serve_scan, static_argnames=("n",))
        self._first_logits = jax.jit(first_logits)

    # ------------------------------------------------------------------
    def run_prefill(self, params, kv, table, lengths, prompts, plens,
                    cond_lengths=None):
        """Dispatch the configured prefill program over a whole (padded)
        prompt buffer; returns (kv, lengths) and accounts serial steps."""
        S0 = prompts.shape[1]
        if cond_lengths is None:
            cond_lengths = jnp.zeros((prompts.shape[0],), jnp.int32)
        if self.prefill_mode == "chunked":
            n_chunks = -(-S0 // self.chunk_size)
            kv, lengths = self._prefill_chunks(params, kv, table, lengths,
                                               prompts, plens, cond_lengths,
                                               n_chunks=n_chunks)
            self.prefill_steps += n_chunks
        else:
            kv, lengths = self._prefill(params, kv, table, lengths,
                                        prompts, plens, cond_lengths)
            self.prefill_steps += S0
        self.dispatches += 1
        return kv, lengths

    def next_token_logits(self, params, prompts, rng=None, *,
                          prompt_lengths=None,
                          page_size: int = KVC.DEFAULT_PAGE_SIZE):
        """fp32 logits (B, vocab) of each row's first generated token: the
        prompts go through the configured prefill program, then through the
        denoising chain that ``generate`` and the continuous batcher sample
        from, with the same rng splits — at temperature 0 the argmax is the
        first token ``generate(params, prompts, 1, rng)`` emits. Comparing
        this across ``impl`` routes checks the served attention numerically.
        Shapes are static, so the method also traces under ``jax.jit``."""
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        prompts = jnp.asarray(prompts, jnp.int32)
        B, S0 = prompts.shape
        plens = (jnp.full((B,), S0, jnp.int32) if prompt_lengths is None
                 else jnp.asarray(prompt_lengths, jnp.int32))
        pps = KVC.pages_for(S0 + 1, page_size)
        kv = self.dbm.model.init_paged_cache(B, 1 + B * pps, page_size,
                                             self.pol)
        table = KVC.identity_page_table(B, pps)
        clens = jnp.zeros((B,), jnp.int32)
        kv, lengths = self.run_prefill(params, kv, table,
                                       jnp.zeros((B,), jnp.int32), prompts,
                                       plens, clens)
        self.dispatches += 1
        return self._first_logits(params, kv, table, lengths, rng, clens)

    def generate(self, params, prompts, max_new: int, rng=None, *,
                 prompt_lengths=None, page_size: int = KVC.DEFAULT_PAGE_SIZE,
                 aux_inputs=None, cond_lengths=None,
                 reference: bool = False):
        """Static-batch generation. prompts: (B, S0) (right-padded when
        ``prompt_lengths`` is ragged) -> (B, S0 + max_new); row b holds its
        prompt then its ``max_new`` generated tokens starting at
        ``prompt_lengths[b]``.

        ``aux_inputs`` (dict of (B, Sk, d) conditioning embeddings —
        image_embs / audio_embs) is encoded ONCE through the model's
        frontend and written into every slot's cross block before prefill;
        the scan programs then read it from the cache under the per-slot
        valid lengths ``cond_lengths`` (default: the full encoded length for
        every row).

        ``reference=True`` replays the seed serving loop faithfully — one
        jitted dispatch + host sync per generated token — through the SAME
        step function, so greedy outputs are bit-identical to the fused scan
        (the decode-parity tests and ``benchmarks/table15_decode`` rely on
        this).
        """
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        prompts = jnp.asarray(prompts)
        B, S0 = prompts.shape
        plens = (jnp.full((B,), S0, jnp.int32) if prompt_lengths is None
                 else jnp.asarray(prompt_lengths, jnp.int32))
        pps = KVC.pages_for(int(jnp.max(plens)) + max_new, page_size)
        kv = self.dbm.model.init_paged_cache(B, 1 + B * pps, page_size,
                                             self.pol)
        table = KVC.identity_page_table(B, pps)
        lengths = jnp.zeros((B,), jnp.int32)
        if aux_inputs:
            cond = self.dbm.model.encode_conditioning(params, aux_inputs)
            if cond is None:
                spec = self.dbm.model.aux_input_specs(B)
                raise ValueError(
                    f"aux_inputs {sorted(aux_inputs)} not understood by "
                    f"family {self.dbm.cfg.family!r}: expected "
                    f"{sorted(spec) if spec else 'no aux inputs'}")
            if (cond_lengths is not None
                    and not self.dbm.model.cond_padding_safe):
                raise ValueError(
                    "ragged cond_lengths through the static batch is "
                    f"unsound for family {self.dbm.cfg.family!r}: its "
                    "frontend (bidirectional encoder) mixes padded frames "
                    "into every row. Serve ragged conditioning through "
                    "ContinuousBatcher.submit, which encodes each request "
                    "at its true length.")
            kv = self.dbm.model.set_conditioning(params, kv, cond)
            clens = (jnp.full((B,), cond.shape[1], jnp.int32)
                     if cond_lengths is None
                     else jnp.asarray(cond_lengths, jnp.int32))
        else:
            clens = jnp.zeros((B,), jnp.int32)
        kv, lengths = self.run_prefill(params, kv, table, lengths,
                                       prompts.astype(jnp.int32), plens,
                                       clens)
        stop_at = plens + max_new
        if reference:
            cols = []
            for _ in range(max_new):
                kv, lengths, rng, t = self._decode(params, kv, table, lengths,
                                                   stop_at, rng, clens, n=1)
                self.dispatches += 1
                cols.append(np.asarray(t))       # host sync per token (seed)
            gen = np.concatenate(cols, axis=1)
        else:
            kv, lengths, rng, t = self._decode(params, kv, table, lengths,
                                               stop_at, rng, clens,
                                               n=max_new)
            self.dispatches += 1
            gen = np.asarray(t)
        out = np.zeros((B, S0 + max_new), dtype=np.asarray(prompts).dtype)
        pl = np.asarray(plens)
        pr = np.asarray(prompts)
        for b in range(B):
            out[b, :pl[b]] = pr[b, :pl[b]]
            out[b, pl[b]:pl[b] + max_new] = gen[b]
        return jnp.asarray(out)


_ENGINE_DEFAULTS = dict(steps_per_block=1, temperature=0.0, top_k=0,
                        precision="bf16", impl="auto", prefill="chunked",
                        chunk_size=DEFAULT_CHUNK, kv_dtype=None)


def get_engine(dbm: DiffusionBlocksModel, **config) -> DecodeEngine:
    """Memoized engine per (dbm, static config): repeated ``generate`` calls
    reuse the compiled scan programs instead of thrashing the jit cache.
    The key is normalized against the engine defaults, so ``get_engine(dbm)``
    and an explicit-defaults call share one engine. ``kv_dtype`` (the
    ``--kv-dtype`` flag: int8 | bf16 | None) is folded into the precision
    policy name — ``('bf16', 'int8')`` and ``('bf16_kvint8', None)`` resolve
    to the same engine."""
    cfg = {**_ENGINE_DEFAULTS, **config}
    cfg["precision"] = precision_mod.with_kv_dtype(
        cfg["precision"], cfg.pop("kv_dtype", None)).name
    key = tuple(sorted(cfg.items()))
    cache = dbm.__dict__.setdefault("_serve_engines", {})
    if key not in cache:
        cache[key] = DecodeEngine(dbm, **cfg)
    return cache[key]


def generate(dbm, params, prompts: jnp.ndarray, max_new: int,
             steps_per_block: int = 1, rng=None, *, prompt_lengths=None,
             temperature: float = 0.0, top_k: int = 0, precision="bf16",
             kv_dtype=None,
             impl: str = "auto", page_size: int = KVC.DEFAULT_PAGE_SIZE,
             prefill: str = "chunked", chunk_size: int = DEFAULT_CHUNK,
             aux_inputs=None, cond_lengths=None, reference: bool = False):
    """prompts: (B, S0) -> (B, S0 + max_new), scan-fused over the paged
    bf16 KV cache (see DecodeEngine). The cache dtype follows the
    ``repro.precision`` policy (bf16 KV by default; recurrent states keep
    their family override). ``prefill="chunked"`` (default) ingests the
    prompt ``chunk_size`` tokens per scan step; ``"per-token"`` is the
    seed-style one-token-per-step reference scan. ``aux_inputs`` conditions
    the batch (VLM image_embs / audio audio_embs, (B, Sk, d)): encoded once
    and served from the per-slot cross blocks. ``reference=True`` =
    seed-style per-token DECODE loop (same math, one dispatch + host sync
    per token)."""
    eng = get_engine(dbm, steps_per_block=steps_per_block,
                     temperature=temperature, top_k=top_k,
                     precision=precision, kv_dtype=kv_dtype, impl=impl,
                     prefill=prefill, chunk_size=chunk_size)
    return eng.generate(params, prompts, max_new, rng,
                        prompt_lengths=prompt_lengths, page_size=page_size,
                        aux_inputs=aux_inputs, cond_lengths=cond_lengths,
                        reference=reference)


# ---------------------------------------------------------------------------
# Continuous batching
# ---------------------------------------------------------------------------

# Priority classes for SLO-aware scheduling: higher wins. Admission picks the
# best (priority, earliest TTFT deadline, oldest) queued request; preemption
# only ever spills STRICTLY lower-priority work for an admission, so classes
# are a total preorder, not advisory hints.
PRIORITY_CLASSES = {"batch": 0, "standard": 1, "interactive": 2}


class AdmissionError(RuntimeError):
    """Raised by ``submit`` when admission control sheds the request (queue
    depth or pool pressure over threshold). ``retry_after`` is the engine's
    service-time-based backoff hint in seconds (the HTTP frontend surfaces
    it as a ``Retry-After`` header on the 429)."""

    def __init__(self, msg: str, retry_after: float):
        super().__init__(msg)
        self.retry_after = float(retry_after)


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray
    max_new: int
    aux_inputs: Optional[dict] = None   # per-request conditioning (Sk, d)
    cond_fp: int = 0                    # conditioning fingerprint (0 = none)
    out: List[int] = dataclasses.field(default_factory=list)
    pages: List[int] = dataclasses.field(default_factory=list)
    shared_tokens: int = 0        # prompt tokens served from the prefix cache
    registered: bool = False      # prefix pages inserted into the cache
    submit_t: float = 0.0
    first_token_t: Optional[float] = None
    cancelled: bool = False       # retired early via ``cancel(rid)``
    error: Optional[str] = None   # rejection reason (non-strict scheduling)
    # --- SLO-aware scheduling ---
    priority: int = PRIORITY_CLASSES["standard"]
    ttft_deadline: Optional[float] = None   # absolute wall-clock deadline
    tpot_deadline_s: Optional[float] = None  # max seconds per output token
    deadline_blown: bool = False  # retired by the deadline enforcer
    # --- preemption (page spill / restore) ---
    spilled: Optional[KVC.SpilledSlot] = None  # host snapshot while queued
    spill_meta: Optional[dict] = None          # lengths/cond row to restore
    preempt_count: int = 0
    # --- disaggregated prefill/decode migration (launch/router) ---
    # page-handle handoff over a SHARED pool: the physical pages holding this
    # request's committed KV, refs still held, travelling with the request —
    # admission maps them instead of allocating + byte-copying
    handoff_pages: Optional[List[int]] = None
    migrations: int = 0           # completed prefill->decode handoffs
    failovers: int = 0            # re-routed off a dead worker

    @property
    def done(self) -> bool:
        return len(self.out) >= self.max_new

    @property
    def ttft(self) -> Optional[float]:
        return (None if self.first_token_t is None
                else self.first_token_t - self.submit_t)


def _paged_leaves(kv) -> list:
    """The PagedKV leaves of a model cache, in flatten order (dense per-slot
    leaves excluded) — the part of the cache a SharedPagePool makes common."""
    return [x for x in jax.tree_util.tree_leaves(kv, is_leaf=KVC._is_pkv)
            if KVC._is_pkv(x)]


def _graft_paged(kv, leaves: list):
    """Replace the PagedKV leaves of ``kv`` with ``leaves`` (same order),
    leaving dense per-slot state untouched — a reference swap, no copy."""
    it = iter(leaves)
    return jax.tree_util.tree_map(
        lambda x: next(it) if KVC._is_pkv(x) else x, kv,
        is_leaf=KVC._is_pkv)


class SharedPagePool:
    """ONE physical page pool shared by several batchers (disaggregated
    prefill/decode with page-handle migration): the free list, the refcount
    map, and the canonical paged-KV leaves are common; each batcher keeps its
    own dense per-slot state (recurrent rows, cross blocks) and its own page
    table. Steps of every sharing batcher serialize under ``lock``; a
    stepping batcher PULLS the canonical paged leaves before mutating and
    PUBLISHES them after, so a page a prefill worker hands to a decode worker
    is visible there without copying a byte — the request carries only the
    physical page ids (``Request.handoff_pages``)."""

    def __init__(self, total_pages: int):
        self.total_pages = int(total_pages)
        self.free_pages: List[int] = list(range(1, self.total_pages))
        self.page_refs: Dict[int, int] = {}
        self.lock = threading.RLock()
        self.paged: Optional[list] = None    # canonical PagedKV leaves

    def release(self, batcher: "ContinuousBatcher", pages) -> None:
        """Return refs the ROUTER holds (a dropped in-transit handoff) to the
        shared pool, serialized against every sharing batcher's step."""
        with self.lock:
            batcher._release_pages(pages)


class ContinuousBatcher:
    """Slot-based continuous batching over a shared page pool.

    ``num_slots`` request slots share ``total_pages`` physical pages
    (physical page 0 reserved as the trash page). Between dispatches the host
    admits queued requests into free slots and retires finished sequences,
    returning pages whose refcount drops to zero to the free list.

    Scheduling (``prefill="chunked"``, the default): each loop iteration runs
    ONE prefill-chunk dispatch — advancing every still-prefilling slot by up
    to ``chunk_size`` prompt tokens at its own offset — then one
    ``seg_len``-step decode segment for the slots past their prompt. A long
    prompt therefore stalls decoding slots by at most one chunk per segment,
    and reaches its first token after ceil(S / C) chunks instead of S
    per-token steps. ``prefill="per-token"`` restores the PR 3 behavior
    (prompt tokens commit one per scan step inside the segment).

    ``prefix_cache=True`` shares prompt-prefix pages across requests (see
    ``repro.nn.cache.PrefixPageCache``): a request whose prompt extends a
    previously-served prefix maps those pages read-only, starts prefilling
    at the first non-shared token, and copy-on-writes the boundary page.
    Requires a model whose sequence state lives entirely in paged KV
    (``model.kv_carries_all_state`` — recurrent families raise here, at
    construction time, not mid-serve).

    CONDITIONED requests: ``submit(..., aux_inputs={"image_embs": (Sk, d)})``
    (or ``audio_embs``) attaches per-request conditioning. The modality
    frontend runs ONCE at admission (``model.encode_conditioning`` — for
    audio that is the whole encoder stack, at the request's true frame
    count) and the projected result is written into the slot's fixed cross
    block (``model.set_conditioning``); every subsequent chunk/decode
    dispatch reads it from the cache under the per-slot valid length, so
    conditioned and unconditioned slots mix in ONE compiled program
    (``cond_lengths[s] == 0`` makes a slot's cross term exactly zero).
    Prefix sharing keys on (token content, conditioning fingerprint):
    identical text under different conditioning never shares pages.

    FRONTEND HOOKS (the asyncio server in ``repro.launch.server`` and the
    load harness in ``benchmarks/loadgen.py`` drive the batcher through
    these; plain ``run()`` keeps the original drain-the-queue semantics):

      step(rng)       ONE scheduling iteration — apply pending cancels,
                      admit, one prefill-chunk dispatch, one decode segment,
                      retire — returning the requests finished this
                      iteration. ``run()`` is now a loop over ``step``.
      cancel(rid)     thread-safe mid-flight abort: a queued request is
                      dropped, an admitted one retires its slot BETWEEN
                      segments — its pages return to the pool immediately,
                      respecting prefix-cache refcounts (shared pages only
                      drop this slot's ref).
      pause(rid) /    thread-safe flow control: a paused request keeps its
      resume(rid)     slot and pages but is excluded from decode segments —
                      slow-consumer backpressure without losing work.
      token_cb        optional ``(Request, list[int]) -> None`` called from
                      the scheduling thread with each segment's newly
                      emitted tokens (SSE streaming taps this).

    ``submit``/``cancel``/``pause``/``resume`` may be called from any
    thread; mutations are applied by the scheduling thread at the next
    ``step`` boundary — engine dispatches never race host bookkeeping.
    """

    def __init__(self, dbm, params, *, num_slots: int = 8,
                 page_size: int = KVC.DEFAULT_PAGE_SIZE,
                 max_prompt: int = 64, max_len: int = 128,
                 total_pages: Optional[int] = None, seg_len: int = 16,
                 steps_per_block: int = 1, temperature: float = 0.0,
                 top_k: int = 0, precision="bf16", kv_dtype=None,
                 impl: str = "auto",
                 prefill: str = "chunked",
                 chunk_size: Optional[int] = None,
                 prefix_cache: bool = False,
                 max_queue: Optional[int] = None,
                 shed_below_pages: int = 0,
                 faults=None,
                 shared_pool: Optional[SharedPagePool] = None):
        self.dbm, self.params = dbm, params
        chunk_size = (min(DEFAULT_CHUNK, max_prompt) if chunk_size is None
                      else chunk_size)
        self.eng = get_engine(dbm, steps_per_block=steps_per_block,
                              temperature=temperature, top_k=top_k,
                              precision=precision, kv_dtype=kv_dtype,
                              impl=impl,
                              prefill=prefill, chunk_size=chunk_size)
        self.chunked = prefill == "chunked"
        self.chunk_size = chunk_size
        if prefix_cache and not dbm.model.kv_carries_all_state:
            raise ValueError(
                f"prefix_cache=True is unsound for family "
                f"{dbm.cfg.family!r}: per-slot recurrent state is not paged, "
                "so mapping shared prefix pages would skip the recurrence. "
                "Serve this model with prefix_cache=False.")
        self.prefix = KVC.PrefixPageCache(page_size) if prefix_cache else None
        self.page_size, self.seg_len = page_size, seg_len
        self.max_prompt, self.max_len = max_prompt, max_len
        pps = KVC.pages_for(max_len, page_size)
        # default pool: worst-case pages per slot, plus — under prefix
        # sharing — one copy-on-write spare per slot (a decode write into a
        # cache-RETAINED boundary page copies it even when every mapped page
        # is live, so a zero-slack pool would deadlock on its own request)
        cow_spare = num_slots if prefix_cache else 0
        self.total_pages = (1 + num_slots * pps + cow_spare
                            if total_pages is None else total_pages)
        self._shared = shared_pool
        if shared_pool is not None:
            self.total_pages = shared_pool.total_pages
        self.kv = dbm.model.init_paged_cache(num_slots, self.total_pages,
                                             page_size, self.eng.pol)
        if shared_pool is None:
            self.free_pages = list(range(1, self.total_pages))
            self.page_refs = {}      # phys page -> refcount (slots + cache)
            self._pool_lock = threading.RLock()
        else:
            # shared pool: common free list / refcounts / paged leaves, one
            # lock serializing every sharing batcher's step. The FIRST
            # registrant's freshly-initialized paged leaves become canonical;
            # later registrants drop their own and adopt (shapes must match
            # — same model, page size and pool size).
            self.free_pages = shared_pool.free_pages
            self.page_refs = shared_pool.page_refs
            self._pool_lock = shared_pool.lock
            mine = _paged_leaves(self.kv)
            if shared_pool.paged is None:
                shared_pool.paged = mine
            else:
                assert len(shared_pool.paged) == len(mine) and all(
                    a.k.shape == b.k.shape and a.k.dtype == b.k.dtype
                    and a.quantized == b.quantized for a, b in
                    zip(shared_pool.paged, mine)), \
                    "batchers sharing a pool must serve the same model with " \
                    "the same page_size/total_pages/kv_dtype"
                self.kv = _graft_paged(self.kv, shared_pool.paged)
        self.num_slots = num_slots
        self.table = np.zeros((num_slots, pps), np.int32)   # 0 = trash page
        self.lengths = np.zeros(num_slots, np.int32)
        self.plens = np.zeros(num_slots, np.int32)
        self.stop_at = np.zeros(num_slots, np.int32)
        self.active = np.zeros(num_slots, bool)
        self.prompt_buf = np.zeros((num_slots, max_prompt), np.int32)
        self.cond_lengths = np.zeros(num_slots, np.int32)
        self.slot_req: List[Optional[Request]] = [None] * num_slots
        self.queue: collections.deque = collections.deque()
        self._next_rid = 0
        self.steps = 0               # decode-segment scan steps (all slots)
        self.ingest_dispatches = 0   # prefill-chunk calls THIS batcher made
        self.decode_dispatches = 0   # decode-segment calls THIS batcher made
        self.cow_copies = 0          # copy-on-write page copies performed
        self._lock = threading.Lock()        # guards queue/cancel/pause sets
        self._cancel_pending: set = set()    # rids to abort at next step
        self._paused: set = set()            # rids excluded from decode
        self.cancelled_count = 0
        self.token_cb: Optional[Callable[[Request, List[int]], None]] = None
        # --- SLO scheduling / preemption / admission control / chaos ---
        self._axes = dbm.model.paged_state_axes  # dense per-slot slot axes
        self.max_queue = max_queue           # class-aware queue-depth shed
        self.shed_below_pages = shed_below_pages  # pool-pressure shed (prio 0)
        self.faults = faults                 # repro.launch.faults injector
        self._preempt_pending: set = set()   # rids to spill at next step
        self.preemptions = 0                 # slots spilled to host
        self.restores = 0                    # spilled requests re-admitted
        self.deadline_cancels = 0            # requests retired by SLO misses
        self.shed_count = 0                  # submissions refused (429)
        self._svc_ewma: Optional[float] = None  # submit->finish seconds

    def submit(self, prompt, max_new: int, aux_inputs=None, *,
               priority="standard", ttft_slo_s: Optional[float] = None,
               tpot_slo_s: Optional[float] = None) -> int:
        """Queue a request. ``aux_inputs``: optional per-request conditioning
        — {"image_embs": (Sk, d)} / {"audio_embs": (Sk, d)} numpy/jax arrays
        WITHOUT a batch dim. The fingerprint for conditioning-aware prefix
        sharing is taken here (content hash); the encoder itself runs at
        admission.

        ``priority`` (a ``PRIORITY_CLASSES`` name or an int) orders admission
        and selects preemption victims; ``ttft_slo_s`` / ``tpot_slo_s`` are
        relative SLOs — a request that blows one is retired with its partial
        output and ``error`` set, never silently served late. Admission
        control (``max_queue`` / ``shed_below_pages``) raises
        ``AdmissionError`` instead of queueing; the backlog check only counts
        queued work at >= this request's priority, so under mixed overload
        the low classes shed first while the high classes still admit."""
        if isinstance(priority, str):
            if priority not in PRIORITY_CLASSES:
                raise ValueError(f"unknown priority class {priority!r}: "
                                 f"expected {sorted(PRIORITY_CLASSES)}")
            priority = PRIORITY_CLASSES[priority]
        priority = int(priority)
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        # Reject degenerate requests BEFORE any state is touched: an empty
        # prompt allocates zero pages (pages_for(0) == 0) and would dispatch
        # a prefill chunk whose every write lands in the trash page; a
        # max_new < 1 request could never retire through the stop_at check.
        # ValueError (not assert) so the HTTP frontend maps these to a 400.
        if prompt.size == 0:
            raise ValueError(
                "empty prompt: a request must carry at least one token "
                "(the serving stack has no BOS convention to invent one)")
        if max_new < 1:
            raise ValueError(f"max_new must be >= 1, got {max_new}")
        assert prompt.size <= self.max_prompt, "prompt exceeds max_prompt"
        assert prompt.size + max_new <= self.max_len, "request exceeds max_len"
        if aux_inputs:
            cap = self.dbm.model.max_cond_tokens
            if cap == 0:
                raise ValueError(
                    f"family {self.dbm.cfg.family!r} takes no aux "
                    "conditioning inputs")
            aux_inputs = {k: np.asarray(v, np.float32)
                          for k, v in aux_inputs.items()}
            for k, v in aux_inputs.items():
                assert v.ndim == 2 and v.shape[1] == self.dbm.cfg.d_model, \
                    f"{k}: expected (Sk, d_model), got {v.shape}"
                assert v.shape[0] <= cap, \
                    f"{k}: {v.shape[0]} tokens exceed the conditioning " \
                    f"block capacity {cap}"
        with self._lock:
            if self.max_queue is not None:
                backlog = sum(1 for r in self.queue if r.priority >= priority)
                if backlog >= self.max_queue:
                    self.shed_count += 1
                    raise AdmissionError(
                        f"queue depth {backlog} at priority >= {priority} "
                        f"over threshold {self.max_queue}",
                        self.retry_after_hint())
            if (self.shed_below_pages and priority <= 0
                    and len(self.free_pages) < self.shed_below_pages):
                self.shed_count += 1
                raise AdmissionError(
                    f"pool pressure: {len(self.free_pages)} free pages below "
                    f"threshold {self.shed_below_pages} (batch class shed)",
                    self.retry_after_hint())
            rid = self._next_rid
            self._next_rid += 1
        req = Request(rid, prompt, max_new, aux_inputs=aux_inputs or None,
                      cond_fp=KVC.conditioning_fingerprint(aux_inputs),
                      priority=priority, tpot_deadline_s=tpot_slo_s)
        req.submit_t = time.time()
        if ttft_slo_s is not None:
            req.ttft_deadline = req.submit_t + float(ttft_slo_s)
        with self._lock:
            self.queue.append(req)
        return rid

    def kv_stats(self) -> dict:
        """Pool-bytes surface for ``/v1/health``: the pool storage dtype and
        total cache bytes counted per leaf — mixed-dtype aware, so an int8
        pool reports its fp32 per-page scale arrays instead of silently
        under-reporting them."""
        leaves = _paged_leaves(self.kv)
        return {
            "kv_dtype": (jnp.dtype(leaves[0].k.dtype).name if leaves
                         else None),
            "kv_quantized": bool(leaves and leaves[0].quantized),
            "kv_bytes": int(KVC.cache_bytes(self.kv)),
            "kv_bytes_by_dtype": KVC.cache_bytes_by_dtype(self.kv),
        }

    def submit_request(self, req: Request) -> None:
        """Enqueue a pre-built ``Request`` (thread-safe). The disaggregation
        router hands work over this way: rids are allocated globally by the
        router and admission control already ran there, so the request lands
        in the queue untouched — including a migration payload
        (``req.spilled`` / ``req.handoff_pages``) to restore at admission."""
        with self._lock:
            self.queue.append(req)

    def cancel(self, rid: int) -> bool:
        """Abort request ``rid`` (thread-safe). Applied at the next ``step``
        boundary: a queued request is dropped before admission; an admitted
        one retires its slot between segments and frees its pages
        immediately (shared prefix pages only drop this slot's refcount —
        cache-retained copies survive). Returns False when ``rid`` is
        unknown or already finished."""
        with self._lock:
            known = (any(r.rid == rid for r in self.queue)
                     or any(r is not None and r.rid == rid
                            for r in self.slot_req))
            if known:
                self._cancel_pending.add(rid)
        return known

    def pause(self, rid: int):
        """Exclude ``rid`` from decode segments (thread-safe): the request
        keeps its slot and pages but emits no tokens until ``resume`` —
        slow-consumer backpressure."""
        with self._lock:
            self._paused.add(rid)

    def resume(self, rid: int):
        with self._lock:
            self._paused.discard(rid)

    def preempt(self, rid: int) -> bool:
        """Force-preempt an ADMITTED request (thread-safe, applied at the
        next ``step`` boundary): its slot state spills to host memory, its
        pages and slot free, and it re-queues for restore when capacity
        allows. The scheduler invokes the same mechanism automatically under
        pool pressure; this entry point exists for tests and operators.
        Returns False when ``rid`` is not currently in a slot."""
        with self._lock:
            known = any(r is not None and r.rid == rid for r in self.slot_req)
            if known:
                self._preempt_pending.add(rid)
        return known

    def retry_after_hint(self) -> float:
        """Backoff hint for shed requests: the smoothed submit→finish
        service time, clipped to [0.1s, 5s] (0.5s before any completion)."""
        return float(min(5.0, max(0.1, self._svc_ewma or 0.5)))

    def _note_service(self, dt: float):
        a = 0.2
        self._svc_ewma = (dt if self._svc_ewma is None
                          else a * dt + (1 - a) * self._svc_ewma)

    # ---- page accounting ---------------------------------------------
    def _alloc_page(self) -> Optional[int]:
        """Pop a free page, evicting prefix-cache entries under pressure."""
        if self.faults is not None and self.faults.fire("alloc_exhaust"):
            return None              # injected exhaustion: pretend pool empty
        if not self.free_pages and self.prefix is not None:
            self.prefix.evict(self.page_refs, self.free_pages, need=1)
        if not self.free_pages:
            return None
        page = self.free_pages.pop()
        self.page_refs[page] = self.page_refs.get(page, 0) + 1
        return page

    def _release_pages(self, pages):
        for p in pages:
            self.page_refs[p] -= 1
            if self.page_refs[p] == 0:
                del self.page_refs[p]
                self.free_pages.append(p)

    def _cow(self, slot: int, logical: int) -> bool:
        """Give ``slot`` a private copy of its ``logical``-th page (the page
        is shared / cache-retained and about to be written). Returns False
        when no page could be allocated."""
        with tracing.span(tracing.COW):
            src = int(self.table[slot, logical])
            dst = self._alloc_page()
            if dst is None:
                return False
            self.kv = KVC.copy_pool_pages(self.kv, src, dst)
            self.cow_copies += 1
            self.table[slot, logical] = dst
            req = self.slot_req[slot]
            req.pages[logical] = dst
            self._release_pages([src])   # drop this slot's ref on the page
            return True

    def _make_writable(self, slot: int, lo: int, hi: int) -> bool:
        """Copy-on-write every shared page overlapping token positions
        [lo, hi) of ``slot`` before a dispatch writes there."""
        psz = self.page_size
        for lp in range(lo // psz, (max(hi, lo + 1) - 1) // psz + 1):
            phys = int(self.table[slot, lp])
            if phys != KVC.TRASH_PAGE and self.page_refs.get(phys, 0) > 1:
                if not self._cow(slot, lp):
                    return False
        return True

    # ---- host-side scheduling between dispatches ---------------------
    def _write_conditioning(self, slot: int, req: Request):
        """Encode a newly-admitted request's conditioning ONCE and write it
        into the slot's cross block. One jitted program per aux shape set
        (the audio encoder runs at the request's TRUE frame count — padding
        frames through a bidirectional encoder would change its output);
        ``slot`` stays a traced scalar so all slots share the program."""
        if req.aux_inputs is None:
            self.cond_lengths[slot] = 0
            return
        # memoized on the dbm (like the engines): every batcher over the
        # same model reuses one compiled program per aux shape set
        progs = self.dbm.__dict__.setdefault("_cond_write_progs", {})
        key = tuple(sorted((k, v.shape) for k, v in req.aux_inputs.items()))
        key = (key, self.num_slots)
        fn = progs.get(key)
        if fn is None:
            model = self.dbm.model

            def encode_write(params, kv, aux, slot):
                cond = model.encode_conditioning(params, aux)
                return model.set_conditioning(params, kv, cond, slot)

            # donate the pool: without it every conditioned admission would
            # copy the whole paged cache to build the updated one (CPU
            # backends ignore donation with a warning, so skip it there)
            donate = () if jax.default_backend() == "cpu" else (1,)
            fn = progs[key] = jax.jit(encode_write, donate_argnums=donate)
        aux = {k: jnp.asarray(v)[None] for k, v in req.aux_inputs.items()}
        self.kv = fn(self.params, self.kv, aux, jnp.asarray(slot, jnp.int32))
        self.cond_lengths[slot] = next(iter(req.aux_inputs.values())).shape[0]

    def _order_key(self, r: Request):
        return (-r.priority,
                r.ttft_deadline if r.ttft_deadline is not None
                else float("inf"),
                r.rid)

    def _pop_best(self) -> Optional[Request]:
        """Pop the best queued candidate: highest priority class first, then
        earliest TTFT deadline, then oldest rid (FIFO within a class —
        preempted requests keep their original rid, so a restore naturally
        goes ahead of newer peers)."""
        with self._lock:
            if not self.queue:
                return None
            i = min(range(len(self.queue)),
                    key=lambda i: self._order_key(self.queue[i]))
            req = self.queue[i]
            del self.queue[i]
        return req

    def _requeue(self, req: Request):
        with self._lock:
            self.queue.appendleft(req)

    def _admit(self) -> int:
        new_slots = np.zeros(self.num_slots, bool)
        admitted = []
        budget = self.num_slots     # preemptions allowed per admission pass
        for s in range(self.num_slots):
            if self.active[s]:
                continue
            req = self._pop_best()
            if req is None:
                break
            # a spilled request restores into PRIVATE pages — its snapshot
            # already holds the prefix content, so no prefix matching
            restoring = req.spilled is not None
            match = (self.prefix.match(req.prompt, req.cond_fp)
                     if self.prefix is not None and not restoring
                     else KVC.PrefixMatch([], 0, 0))
            # PIN every matched page before any eviction can run: under pool
            # pressure evict() drops cache-held refs deepest-first, and
            # without the pin it could free (and later re-allocate) the very
            # pages this admission is about to map / CoW-copy from.
            for p in match.pages:
                self.page_refs[p] += 1
            total = KVC.pages_for(len(req.prompt) + req.max_new,
                                  self.page_size)
            # page-handle migration (shared pool): the request arrives
            # already holding refs on the physical pages with its committed
            # KV — they map directly, only the scratch tail allocates
            handed = req.handoff_pages or []
            # fresh pages: everything past the shared prefix, PLUS a copy
            # destination for a matched partial tail page (it is CoW'd at
            # admission — the slot's first write lands inside it)
            need = (total - len(match.pages) - len(handed)
                    + (1 if match.tail_tokens else 0))
            if need > len(self.free_pages) and self.prefix is not None:
                self.prefix.evict(self.page_refs, self.free_pages, need)
            # preempt STRICTLY lower-priority running work for the shortfall.
            # Victims never outrank the candidate, so a preempted request can
            # never preempt its preemptor back; the per-pass budget bounds
            # the spill churn a single admission wave can cause.
            while need > len(self.free_pages) and budget > 0:
                victims = [v for v in range(self.num_slots) if self.active[v]
                           and self.slot_req[v].priority < req.priority]
                if not victims:
                    break
                v = min(victims, key=lambda v: (self.slot_req[v].priority,
                                                -self.slot_req[v].rid))
                self._preempt_slot(v)
                budget -= 1
            if need > len(self.free_pages):
                self._release_pages(match.pages)   # unpin; retry next round
                self._requeue(req)
                break                      # wait for retirements
            row: List[int] = []
            ok = True
            pinned_tail = [match.pages[-1]] if match.tail_tokens else []
            shared_full = (match.pages[:-1] if match.tail_tokens
                           else match.pages)
            row.extend(shared_full)        # pin becomes the slot's map ref
            if match.tail_tokens:          # copy-on-write the boundary page
                dst = self._alloc_page()
                if dst is None:
                    ok = False
                else:
                    self.kv = KVC.copy_pool_pages(self.kv, match.pages[-1],
                                                  dst)
                    self.cow_copies += 1
                    self._release_pages(pinned_tail)   # unpin the source
                    pinned_tail = []
                    row.append(dst)
            row.extend(handed)         # page-handle: refs already travelled
            while ok and len(row) < total:
                p = self._alloc_page()
                if p is None:
                    ok = False
                else:
                    row.append(p)
            if not ok:
                # the allocator refused mid-build (fault injection, or a
                # racing eviction): unwind every ref this admission took —
                # NOT the handed migration pages, whose refs belong to the
                # in-transit request — and retry next step; never leave a
                # half-mapped slot
                keep = set(handed)
                self._release_pages([p for p in row if p not in keep]
                                    + pinned_tail)
                self._requeue(req)
                break
            req.pages = row
            if not restoring:
                req.shared_tokens = match.n_tokens
            if self.prefix is not None and match.n_tokens > 0:
                self.prefix.hits += 1
                self.prefix.tokens_shared += match.n_tokens
            self.table[s, :] = KVC.TRASH_PAGE
            self.table[s, :len(row)] = row
            self.lengths[s] = match.n_tokens   # prefill resumes at the suffix
            self.plens[s] = len(req.prompt)
            self.stop_at[s] = len(req.prompt) + req.max_new
            self.prompt_buf[s, :] = 0
            self.prompt_buf[s, :len(req.prompt)] = req.prompt
            self.slot_req[s] = req
            self.active[s] = True
            new_slots[s] = True
            admitted.append((s, req, restoring))
        if new_slots.any():
            # recycled slots must not inherit the previous occupant's
            # per-slot state (recurrent mamba/xLSTM, cross blocks); paged KV
            # needs no reset — length masking hides stale pages.
            self.kv = self.dbm.model.reset_paged_slots(
                self.kv, jnp.asarray(new_slots))
        for s, req, restoring in admitted:   # AFTER the reset:
            if restoring:                    # scatter the spill snapshot back
                self._restore_into_slot(s, req)
            else:                            # encode-once-per-request
                self._write_conditioning(s, req)
        return int(new_slots.sum())

    def _register_prefixes(self):
        """Insert freshly-completed prompts' prefix pages into the cache so
        later requests can share them (the cache takes one ref per page)."""
        if self.prefix is None:
            return
        for s in range(self.num_slots):
            req = self.slot_req[s]
            if (req is None or req.registered or not self.active[s]
                    or self.lengths[s] < self.plens[s]):
                continue
            npg = KVC.pages_for(int(self.plens[s]), self.page_size)
            self.prefix.insert(req.prompt,
                               [int(self.table[s, i]) for i in range(npg)],
                               self.page_refs, req.cond_fp)
            req.registered = True

    # ---- preemption / migration: page spill, detach, restore ----------
    def _clear_slot_row(self, s: int) -> None:
        """Blank slot ``s``'s scheduling row after its request left (spill,
        detach or retire) — the slot is recyclable afterwards."""
        self.table[s, :] = KVC.TRASH_PAGE
        self.active[s] = False
        self.cond_lengths[s] = 0
        self.lengths[s] = self.plens[s] = self.stop_at[s] = 0
        self.slot_req[s] = None

    def _spill_slot(self, s: int) -> Request:
        """Spill slot ``s`` to host memory and free it: the content of its
        USED pages (``pages_for(lengths[s])`` — later pages are scratch
        hidden by length-aware masking) and its dense per-slot rows
        (recurrent / cross state, ``model.paged_state_axes``) snapshot to
        numpy, its page refs release, and the request pops with the snapshot
        attached. Restore happens at a later admission — possibly into a
        DIFFERENT batcher's pool (the disaggregation router migrates
        finished-prefill requests this way) — via ``_restore_into_slot``;
        the round trip is rng-neutral: no dispatch runs for a spilled slot,
        so nothing perturbs the decode rng stream (same discipline as
        ``pause``)."""
        req = self.slot_req[s]
        n_used = KVC.pages_for(int(self.lengths[s]), self.page_size)
        used = [int(self.table[s, i]) for i in range(n_used)]
        req.spilled = KVC.spill_slot(self.kv, s, used, self._axes)
        req.spill_meta = dict(length=int(self.lengths[s]),
                              cond_length=int(self.cond_lengths[s]))
        self._release_pages(req.pages)
        req.pages = []
        self._clear_slot_row(s)
        return req

    def _detach_slot(self, s: int) -> Request:
        """Page-handle variant of ``_spill_slot`` for batchers on a SHARED
        pool: snapshot only the dense per-slot rows and hand the USED
        physical pages themselves to the request (``handoff_pages`` — their
        refs travel with it; scratch tail pages release). The receiving
        batcher maps those pages instead of allocating + byte-copying, so
        the migration moves the page table, not the KV bytes. Shared prefix
        pages stay shared: their refcount rides along and the receiver's
        copy-on-write machinery still guards divergent writes."""
        req = self.slot_req[s]
        n_used = KVC.pages_for(int(self.lengths[s]), self.page_size)
        req.handoff_pages = [int(self.table[s, i]) for i in range(n_used)]
        req.spilled = KVC.spill_slot(self.kv, s, [], self._axes)
        req.spill_meta = dict(length=int(self.lengths[s]),
                              cond_length=int(self.cond_lengths[s]))
        self._release_pages(req.pages[n_used:])
        req.pages = []
        self._clear_slot_row(s)
        return req

    def _preempt_slot(self, s: int) -> Request:
        """Spill slot ``s`` and re-queue its request at the FRONT with its
        original rid, partial output intact (pool-pressure preemption)."""
        req = self._spill_slot(s)
        req.preempt_count += 1
        self.preemptions += 1
        self._requeue(req)
        return req

    def _drop_payload(self, req: Request) -> None:
        """Discard an unrestored migration/preemption payload when its
        request dies in the queue (cancel, deadline, abort): the host
        snapshot drops, and page-handle refs return to the shared pool —
        queued requests must never keep pages past their death."""
        if req.handoff_pages:
            self._release_pages(req.handoff_pages)
        req.handoff_pages = None
        req.spilled = req.spill_meta = None

    def _restore_into_slot(self, s: int, req: Request):
        """Scatter a spilled request's snapshot into its freshly mapped slot
        (after ``reset_paged_slots`` zeroed the row): page content lands in
        the slot's new private pages (none for a page-handle migration — the
        handed pages already hold it), dense rows overwrite the reset state,
        and the scheduling row resumes at the spilled length. The physical
        page ids usually differ from the spill-time ones — only the logical
        order matters."""
        meta, n = req.spill_meta, req.spilled.n_pages
        self.kv = KVC.restore_slot(self.kv, s, req.pages[:n], req.spilled,
                                   self._axes)
        self.lengths[s] = meta["length"]
        self.cond_lengths[s] = meta["cond_length"]
        req.spilled = req.spill_meta = None
        req.handoff_pages = None
        self.restores += 1

    def _apply_preemptions(self):
        """Apply pending ``preempt`` calls (scheduling thread, between
        dispatches) — the forced-preemption twin of
        ``_apply_cancellations``."""
        with self._lock:
            pre, self._preempt_pending = self._preempt_pending, set()
        if not pre:
            return
        for s in range(self.num_slots):
            req = self.slot_req[s]
            if req is not None and self.active[s] and req.rid in pre:
                self._preempt_slot(s)

    def _make_writable_or_preempt(self, s: int, lo: int, hi: int) -> bool:
        """Copy-on-write with a preemption fallback — the no-deadlock
        replacement for raising on pool exhaustion. On CoW failure the
        lowest-priority active peer at <= this slot's priority spills
        (freeing its pages) and the CoW retries; when no peer is eligible,
        ``s`` ITSELF spills — spilling needs no allocation, so this always
        terminates with the pool whole. Returns False when ``s`` was
        spilled (the caller excludes it from the dispatch)."""
        while True:
            if self._make_writable(s, lo, hi):
                return True
            me = self.slot_req[s]
            victims = [v for v in range(self.num_slots)
                       if v != s and self.active[v]
                       and self.slot_req[v].priority <= me.priority]
            if not victims:
                self._preempt_slot(s)
                return False
            v = min(victims, key=lambda v: (self.slot_req[v].priority,
                                            -self.slot_req[v].rid))
            self._preempt_slot(v)

    # ---- SLO deadlines -----------------------------------------------
    def _enforce_deadlines(self) -> List[Request]:
        """Retire deadline-blown requests with their partial output: queued
        requests past their TTFT deadline are dropped before wasting
        admission; active slots are retired when the first token is late
        (TTFT) or the output pace falls behind ``tpot_deadline_s`` (measured
        over emitted tokens; paused slots are the CONSUMER's stall, not
        ours, and are exempt while paused)."""
        now = time.time()
        out: List[Request] = []
        with self._lock:
            kept: collections.deque = collections.deque()
            for r in self.queue:
                if (r.ttft_deadline is not None and r.first_token_t is None
                        and now > r.ttft_deadline):
                    r.deadline_blown = True
                    r.error = "ttft deadline exceeded"
                    self._drop_payload(r)
                    out.append(r)
                else:
                    kept.append(r)
            self.queue = kept
            paused = set(self._paused)
        for s in range(self.num_slots):
            req = self.slot_req[s]
            if req is None or not self.active[s] or req.rid in paused:
                continue
            blown = None
            if (req.ttft_deadline is not None and req.first_token_t is None
                    and now > req.ttft_deadline):
                blown = "ttft deadline exceeded"
            elif (req.tpot_deadline_s is not None and len(req.out) >= 2
                  and ((now - req.first_token_t) / (len(req.out) - 1)
                       > req.tpot_deadline_s)):
                blown = "tpot deadline exceeded"
            if blown:
                req.deadline_blown = True
                req.error = blown
                out.append(self._retire_slot(s))
        self.deadline_cancels += len(out)
        return out

    def recover(self):
        """Crash recovery (the ``EngineRunner`` supervisor calls this before
        restarting the engine thread): spill every active slot back to the
        queue, so the fresh loop re-admits and resumes them with no token
        loss or duplication — ``req.out`` persists and ``_collect`` only
        appends newly emitted tokens."""
        with self._pool_lock:
            for s in range(self.num_slots):
                if self.active[s]:
                    self._preempt_slot(s)

    def abort_all(self, msg: str) -> List[Request]:
        """Error out every queued and active request (the supervisor giving
        up after repeated crashes): slots retire, pages return to the pool,
        and each request carries ``error=msg`` so its stream can finish
        cleanly instead of hanging. Returns the aborted requests."""
        with self._pool_lock:
            with self._lock:
                reqs = list(self.queue)
                self.queue.clear()
            for r in reqs:
                self._drop_payload(r)
            for s in range(self.num_slots):
                if self.slot_req[s] is not None and self.active[s]:
                    reqs.append(self._retire_slot(s))
        for r in reqs:
            r.error = r.error or msg
        return reqs

    def extract_all(self, detach: bool = False) -> List[Request]:
        """Pop every queued and active request WITHOUT erroring them — the
        failover harvest after this batcher's worker died. By default active
        slots release their pages (their device KV died with the worker;
        partial output and any unrestored migration payload survive on the
        host, so the router re-prefills). ``detach=True`` — shared-pool
        failover, where the KV physically survives in the common segment —
        hands each active slot's used pages to its request
        (``handoff_pages``) so the router can re-migrate without replay.
        Queued requests pop as-is, payloads intact. The pool ends whole and
        the router re-routes the survivors."""
        with self._pool_lock:
            with self._lock:
                reqs = list(self.queue)
                self.queue.clear()
            for s in range(self.num_slots):
                if self.slot_req[s] is not None and self.active[s]:
                    reqs.append(self._detach_slot(s) if detach
                                else self._retire_slot(s))
        return reqs

    def _retire_slot(self, s: int) -> Request:
        """Free slot ``s``: release its request's page refs (shared pages
        survive while the prefix cache or another slot still holds them),
        blank the page-table row, and mark the slot recyclable."""
        req = self.slot_req[s]
        self._release_pages(req.pages)
        req.pages = []
        self.table[s, :] = KVC.TRASH_PAGE
        self.active[s] = False
        self.cond_lengths[s] = 0
        # zero the scheduling row: a slot cancelled mid-prefill would
        # otherwise keep lengths < plens and make every later chunk dispatch
        # commit its dead prompt into the trash page
        self.lengths[s] = self.plens[s] = self.stop_at[s] = 0
        self.slot_req[s] = None
        with self._lock:
            self._paused.discard(req.rid)
        return req

    def _retire(self) -> List[Request]:
        out = []
        for s in range(self.num_slots):
            req = self.slot_req[s]
            if req is None or not self.active[s]:
                continue
            if self.lengths[s] >= self.stop_at[s]:
                self._note_service(time.time() - req.submit_t)
                out.append(self._retire_slot(s))
        return out

    def _apply_cancellations(self) -> List[Request]:
        """Apply pending ``cancel`` calls (scheduling thread, between
        dispatches): drop queued requests, retire cancelled slots and free
        their pages. Returns the cancelled requests."""
        with self._lock:
            cancels, self._cancel_pending = self._cancel_pending, set()
        if not cancels:
            return []
        out = []
        with self._lock:
            kept = collections.deque()
            for r in self.queue:
                if r.rid in cancels:
                    r.cancelled = True
                    self._drop_payload(r)
                    out.append(r)
                else:
                    kept.append(r)
            self.queue = kept
        for s in range(self.num_slots):
            req = self.slot_req[s]
            if req is not None and req.rid in cancels:
                req.cancelled = True
                out.append(self._retire_slot(s))
        self.cancelled_count += len(out)
        return out

    def _collect(self, emitted: np.ndarray):
        now = time.time()
        for s in range(self.num_slots):
            req = self.slot_req[s]
            if req is None:
                continue
            toks = [int(t) for t in emitted[s] if t >= 0]
            if toks and req.first_token_t is None:
                req.first_token_t = now
            req.out.extend(toks)
            if toks and self.faults is not None:
                self.faults.maybe_sleep("token_stall")
            if toks and self.token_cb is not None:
                self.token_cb(req, toks)

    def _paused_mask(self) -> np.ndarray:
        with self._lock:
            paused = set(self._paused)
        if not paused:
            return np.zeros(self.num_slots, bool)
        return np.array([self.slot_req[s] is not None
                         and self.slot_req[s].rid in paused
                         for s in range(self.num_slots)])

    def has_work(self) -> bool:
        """True while a step could make progress OR bookkeeping is pending
        (queued/active requests, unapplied cancels)."""
        with self._lock:
            pending = bool(self._cancel_pending)
        return pending or bool(self.queue) or bool(self.active.any())

    def step(self, rng, *, strict: bool = True):
        """ONE scheduling iteration: apply pending cancellations, admit
        queued requests into free slots, run one prefill-chunk dispatch
        (chunked mode) and one ``seg_len``-step decode segment, then retire
        finished slots. Returns ``(rng, finished)`` — the requests that
        completed (or were cancelled / rejected) this iteration.

        ``strict=True`` (the ``run()`` default) raises when the head of the
        queue can never be admitted (pool too small and nothing running);
        ``strict=False`` — the serving frontend — instead pops that request
        with ``req.error`` set so one impossible request cannot wedge the
        engine loop.

        Copy-on-write exhaustion no longer raises in EITHER mode: the
        scheduler spills the lowest-priority active slot to host memory
        instead (``_make_writable_or_preempt``), so pool pressure degrades
        to preemption latency, never a deadlock or a lost request.

        On a ``SharedPagePool`` the step serializes with every sharing
        batcher under the pool lock, pulling the canonical paged leaves
        before mutating and publishing them after — even when the body
        raises (an injected crash), so the pool view other workers adopt is
        never lost."""
        with self._pool_lock:
            if self._shared is not None:
                self.kv = _graft_paged(self.kv, self._shared.paged)
            try:
                return self._step(rng, strict=strict)
            finally:
                if self._shared is not None:
                    self._shared.paged = _paged_leaves(self.kv)

    def _step(self, rng, *, strict: bool = True):
        if self.faults is not None:
            # injected BEFORE any bookkeeping mutates, so a crash at this
            # hook leaves the batcher consistent for recover(); worker_die
            # is the harder failure — the supervisor treats it as process
            # death (no restart), the ROUTER must fail the work over
            self.faults.maybe_raise("engine_crash")
            if self.faults.fire("worker_die"):
                raise WorkerDied(
                    f"injected worker_die "
                    f"(call {self.faults.calls['worker_die']})")
        finished = self._apply_cancellations()
        self._apply_preemptions()
        finished.extend(self._enforce_deadlines())
        if not (self.queue or self.active.any()):
            return rng, finished
        with tracing.span(tracing.ADMIT):
            admitted = self._admit()
        if not admitted and not self.active.any():
            # nothing running and nothing admitted: IMPOSSIBLE only when the
            # head request needs more pages than the pool can ever hold — a
            # transient allocator refusal (fault injection, racing eviction)
            # just retries next step
            req = self._pop_best()
            if req is None:
                return rng, finished
            need = KVC.pages_for(len(req.prompt) + req.max_new,
                                 self.page_size)
            if need <= self.total_pages - 1:
                self._requeue(req)
                return rng, finished
            msg = ("page pool too small for the next queued request "
                   f"(needs {need} of {self.total_pages - 1} pages)")
            if strict:
                self._requeue(req)
                raise RuntimeError(msg)
            req.error = msg
            self._drop_payload(req)
            finished.append(req)
            return rng, finished
        in_prompt = self.active & (self.lengths < self.plens)
        if self.chunked and in_prompt.any():
            # ONE chunk dispatch advances every prefilling slot by up to
            # chunk_size tokens at its own offset; decode-only slots see
            # n_valid == 0 inside the program.
            for s in np.nonzero(in_prompt)[0]:
                if not self.active[s]:
                    continue        # spilled by an earlier slot's CoW relief
                lo = int(self.lengths[s])
                hi = min(lo + self.chunk_size, int(self.plens[s]))
                self._make_writable_or_preempt(s, lo, hi)
            in_prompt = self.active & (self.lengths < self.plens)
        if self.chunked and in_prompt.any():
            self.kv, lengths = self.eng._prefill_chunk1(
                self.params, self.kv, jnp.asarray(self.table),
                jnp.asarray(self.lengths), jnp.asarray(self.prompt_buf),
                jnp.asarray(self.plens), jnp.asarray(self.cond_lengths))
            self.lengths = np.array(lengths)
            self.eng.dispatches += 1
            self.eng.prefill_steps += 1
            self.ingest_dispatches += 1
            self._register_prefixes()
        decode_ready = (self.active & (self.lengths >= self.plens)
                        if self.chunked else self.active)
        decode_ready = decode_ready & ~self._paused_mask()
        if decode_ready.any():
            for s in np.nonzero(decode_ready)[0]:
                if not self.active[s]:
                    continue        # spilled by an earlier slot's CoW relief
                lo = int(self.lengths[s])
                hi = min(lo + self.seg_len, int(self.stop_at[s]))
                self._make_writable_or_preempt(s, lo, hi)
            decode_ready = decode_ready & self.active
        if decode_ready.any():
            self.kv, lengths, rng, emitted = self.eng._serve(
                self.params, self.kv, jnp.asarray(self.table),
                jnp.asarray(self.lengths), jnp.asarray(self.prompt_buf),
                jnp.asarray(self.plens), jnp.asarray(self.stop_at),
                jnp.asarray(decode_ready), rng,
                jnp.asarray(self.cond_lengths), n=self.seg_len)
            self.eng.dispatches += 1
            self.decode_dispatches += 1
            self.steps += self.seg_len
            self.lengths = np.array(lengths)           # host copy
            self._collect(np.asarray(emitted))         # (slots, seg)
            if not self.chunked:
                self._register_prefixes()
        with tracing.span(tracing.RETIRE):
            finished.extend(self._retire())
        return rng, finished

    def run(self, rng=None) -> List[Request]:
        """Drain the queue; returns finished requests (ordered by rid)."""
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        finished = []
        while self.has_work():
            rng, fin = self.step(rng)
            finished.extend(fin)
        return sorted(finished, key=lambda r: r.rid)


# ---------------------------------------------------------------------------
# CLI driver
# ---------------------------------------------------------------------------

def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--blocks", type=int, default=4)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--scheduler", choices=("static", "continuous"),
                    default="static")
    ap.add_argument("--steps-per-block", type=int, default=1)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--precision", default="bf16")
    ap.add_argument("--kv-dtype", default=None,
                    choices=("int8", "bf16", "fp32", "auto"),
                    help="paged KV pool storage dtype: int8 quantizes pages "
                         "with one fp32 absmax scale per page per tensor "
                         "(halves pool bytes again vs bf16); default follows "
                         "--precision")
    ap.add_argument("--impl", default="auto",
                    help="attention impl: auto | kernels (Pallas flash-"
                         "decode + flash-prefill; interpret-mode on CPU)")
    ap.add_argument("--prefill", choices=("chunked", "per-token"),
                    default="chunked",
                    help="prompt ingest: chunked (C tokens per scan step) "
                         "or the per-token reference scan")
    ap.add_argument("--chunk-size", type=int, default=DEFAULT_CHUNK,
                    help="prompt tokens per chunked-prefill dispatch")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="continuous: share prompt-prefix pages across "
                         "requests (copy-on-write)")
    ap.add_argument("--page-size", type=int, default=KVC.DEFAULT_PAGE_SIZE)
    ap.add_argument("--num-slots", type=int, default=4,
                    help="continuous: concurrent request slots")
    ap.add_argument("--seg-len", type=int, default=16,
                    help="continuous: scan steps between host scheduling")
    ap.add_argument("--requests", type=int, default=12,
                    help="continuous: queued requests (ragged prompts)")
    ap.add_argument("--ragged", action="store_true",
                    help="vary prompt lengths across the batch/queue")
    ap.add_argument("--conditioned", action="store_true",
                    help="attach aux conditioning (VLM/audio archs): random "
                         "image/audio embeddings drawn from a small pool so "
                         "the conditioning-aware prefix cache can hit")
    ap.add_argument("--cond-pool", type=int, default=3,
                    help="distinct conditioning inputs in the pool")
    args = ap.parse_args()
    runtime.init_compile_cache()

    cfg = reduced(get_config(args.arch))
    n_units = DiffusionBlocksModel(cfg, DBConfig(num_blocks=1)).model.n_units
    db = DBConfig(num_blocks=min(args.blocks, n_units), overlap_gamma=0.1)
    dbm = DiffusionBlocksModel(cfg, db)
    params = dbm.init(jax.random.PRNGKey(0))
    if args.ckpt_dir:
        params = load_blocks(args.ckpt_dir, params, dbm.ranges)

    lm = MarkovLM(vocab_size=cfg.vocab_size, seed=7)
    rs = np.random.RandomState(1)
    aux_key, cond_pool = None, []
    if args.conditioned:
        specs = dbm.model.aux_input_specs(1)
        if not specs:
            raise SystemExit(f"--conditioned: family {cfg.family!r} takes "
                             "no aux inputs (pick a vlm/audio arch)")
        aux_key = next(iter(specs))
        Sk = dbm.model.max_cond_tokens
        cond_pool = [rs.randn(Sk, cfg.d_model).astype(np.float32)
                     for _ in range(args.cond_pool)]
    kw = dict(steps_per_block=args.steps_per_block,
              temperature=args.temperature, top_k=args.top_k,
              precision=args.precision, kv_dtype=args.kv_dtype,
              impl=args.impl,
              prefill=args.prefill,
              chunk_size=min(args.chunk_size, max(args.prompt_len, 1)))

    if args.scheduler == "static":
        prompts = jnp.asarray(lm.sample(rs, args.batch, args.prompt_len))
        plens = None
        if args.ragged:
            plens = rs.randint(max(2, args.prompt_len // 2),
                               args.prompt_len + 1, size=args.batch)
        aux = (None if aux_key is None else
               {aux_key: jnp.asarray(np.stack([cond_pool[0]] * args.batch))})
        eng = get_engine(dbm, **kw)
        t0 = time.time()
        out = eng.generate(params, prompts, args.max_new,
                           prompt_lengths=plens, page_size=args.page_size,
                           aux_inputs=aux)
        jax.block_until_ready(out)
        dt = time.time() - t0
        n_tok = args.batch * args.max_new
        pps = KVC.pages_for(args.prompt_len + args.max_new, args.page_size)
        pool_abstract = jax.eval_shape(          # report size; allocate nothing
            lambda: dbm.model.init_paged_cache(
                args.batch, 1 + args.batch * pps, args.page_size,
                precision_mod.with_kv_dtype(args.precision, args.kv_dtype)))
        print(f"[static] generated {args.batch}x{args.max_new} tokens in "
              f"{dt:.2f}s ({n_tok/dt:.1f} tok/s incl. compile) | "
              f"dispatches={eng.dispatches} "
              f"({eng.dispatches/n_tok:.3f}/token) | "
              f"prefill={args.prefill} "
              f"({eng.prefill_steps} serial steps for "
              f"{args.batch}x{args.prompt_len} prompt tokens) | "
              f"cache={KVC.cache_bytes(pool_abstract)/1e6:.1f}MB paged")
        rows = np.array(out)
        lens = (np.asarray(plens) if plens is not None
                else np.full(args.batch, args.prompt_len)) + args.max_new
        print("legal-transition rate:", _ragged_transition_accuracy(
            lm, [rows[b, :lens[b]] for b in range(args.batch)]))
    else:
        cb = ContinuousBatcher(dbm, params, num_slots=args.num_slots,
                               page_size=args.page_size,
                               max_prompt=args.prompt_len,
                               max_len=args.prompt_len + args.max_new,
                               seg_len=args.seg_len,
                               prefix_cache=args.prefix_cache, **kw)
        for i in range(args.requests):
            plen = (rs.randint(max(2, args.prompt_len // 2),
                               args.prompt_len + 1)
                    if args.ragged else args.prompt_len)
            aux = (None if aux_key is None else
                   {aux_key: cond_pool[i % len(cond_pool)]})
            cb.submit(lm.sample(rs, 1, plen)[0], args.max_new,
                      aux_inputs=aux)
        t0 = time.time()
        done = cb.run(jax.random.PRNGKey(0))
        dt = time.time() - t0
        n_tok = sum(len(r.out) for r in done)
        ttfts = [r.ttft for r in done if r.ttft is not None]
        shared = sum(r.shared_tokens for r in done)
        print(f"[continuous] served {len(done)} requests / {n_tok} tokens "
              f"in {dt:.2f}s ({n_tok/dt:.1f} tok/s incl. compile) | "
              f"slots={args.num_slots} pool={cb.total_pages} pages x "
              f"{args.page_size} | dispatches={cb.eng.dispatches} "
              f"({cb.eng.dispatches/max(n_tok,1):.3f}/token) | "
              f"mean TTFT {np.mean(ttfts):.3f}s | "
              f"cache={KVC.cache_bytes(cb.kv)/1e6:.1f}MB paged")
        if cb.prefix is not None:
            print(f"prefix cache: {cb.prefix.hits} hits, {shared} prompt "
                  f"tokens served from shared pages, {cb.cow_copies} "
                  f"copy-on-write page copies")
        seqs = [np.concatenate([r.prompt, np.asarray(r.out, np.int64)])
                for r in done]
        print("legal-transition rate:",
              _ragged_transition_accuracy(lm, seqs))


if __name__ == "__main__":
    main()
