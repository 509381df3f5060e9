"""Decode-engine tests: scan-fused generation parity with the per-token
reference loop (greedy; uniform and ragged prompts), paged-vs-dense decode
attention, flash-decode kernel routing, paged-commit vs prefill cache
consistency, precision-policy cache dtypes, continuous batching, and jit
compile-cache behavior."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs import DBConfig
from repro.configs.base import ModelConfig
from repro.core import DiffusionBlocksModel
from repro.launch.serve import ContinuousBatcher, generate, get_engine
from repro.nn import attention as A
from repro.nn import cache as KVC
from repro.nn import init as I

TINY = ModelConfig(name="tiny-decode", family="dense", n_layers=6, d_model=64,
                   n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=32)


def make_dbm(cfg=TINY, blocks=3):
    n_units = DiffusionBlocksModel(cfg, DBConfig(num_blocks=1)).model.n_units
    return DiffusionBlocksModel(
        cfg, DBConfig(num_blocks=min(blocks, n_units), overlap_gamma=0.1))


@pytest.fixture(scope="module")
def dbm_params():
    dbm = make_dbm()
    return dbm, dbm.init(jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# Scan-fused vs per-token reference loop: greedy must be bit-identical
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("S0", [3, 8])
def test_scan_matches_reference_loop(dbm_params, S0):
    dbm, params = dbm_params
    prompts = jax.random.randint(jax.random.PRNGKey(1), (4, S0), 0,
                                 TINY.vocab_size)
    kw = dict(rng=jax.random.PRNGKey(7))
    out_scan = generate(dbm, params, prompts, 6, **kw)
    out_loop = generate(dbm, params, prompts, 6, reference=True, **kw)
    np.testing.assert_array_equal(np.asarray(out_scan), np.asarray(out_loop))


def test_scan_matches_reference_loop_ragged(dbm_params):
    dbm, params = dbm_params
    prompts = jax.random.randint(jax.random.PRNGKey(2), (4, 8), 0,
                                 TINY.vocab_size)
    plens = np.array([3, 8, 5, 6])
    kw = dict(rng=jax.random.PRNGKey(7), prompt_lengths=plens)
    out_scan = generate(dbm, params, prompts, 6, **kw)
    out_loop = generate(dbm, params, prompts, 6, reference=True, **kw)
    np.testing.assert_array_equal(np.asarray(out_scan), np.asarray(out_loop))
    # generated tokens sit immediately after each slot's ragged prompt
    out = np.asarray(out_scan)
    for b, pl in enumerate(plens):
        np.testing.assert_array_equal(out[b, :pl],
                                      np.asarray(prompts)[b, :pl])


@pytest.mark.parametrize("impl", ["auto", "kernels"])
def test_next_token_logits_match_first_generated_token(dbm_params, impl):
    """next_token_logits exposes the logits the served programs sample from:
    greedy argmax equals the first token generate emits, ragged prompts,
    and the kernel route agrees with the reference route."""
    dbm, params = dbm_params
    prompts = jax.random.randint(jax.random.PRNGKey(4), (4, 8), 0,
                                 TINY.vocab_size)
    plens = np.array([3, 8, 5, 6])
    eng = get_engine(dbm, precision="fp32", impl=impl)
    rng = jax.random.PRNGKey(9)
    logits = np.asarray(eng.next_token_logits(params, prompts, rng,
                                              prompt_lengths=plens))
    assert logits.shape == (4, TINY.vocab_size)
    assert logits.dtype == np.float32 and np.isfinite(logits).all()
    out = np.asarray(eng.generate(params, prompts, 1, rng,
                                  prompt_lengths=plens))
    np.testing.assert_array_equal(logits.argmax(-1),
                                  out[np.arange(4), plens])
    if impl == "kernels":
        ref = get_engine(dbm, precision="fp32", impl="auto")
        np.testing.assert_allclose(
            logits, np.asarray(ref.next_token_logits(
                params, prompts, rng, prompt_lengths=plens)),
            rtol=1e-4, atol=1e-4)


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["zamba2-7b", "xlstm-125m",
                                  "h2o-danube-3-4b"])
def test_scan_matches_reference_loop_families(arch):
    """Recurrent-state masking (hybrid mamba / xlstm) and SWA window masking
    through the paged engine, ragged prompts."""
    cfg = configs.reduced(configs.get_config(arch))
    dbm = make_dbm(cfg, blocks=2)
    params = dbm.init(jax.random.PRNGKey(0))
    prompts = jax.random.randint(jax.random.PRNGKey(1), (3, 6), 0,
                                 cfg.vocab_size)
    plens = np.array([3, 6, 4])
    kw = dict(rng=jax.random.PRNGKey(7), prompt_lengths=plens)
    o1 = generate(dbm, params, prompts, 4, **kw)
    o2 = generate(dbm, params, prompts, 4, reference=True, **kw)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))


def test_sampling_traced_and_deterministic(dbm_params):
    dbm, params = dbm_params
    prompts = jax.random.randint(jax.random.PRNGKey(3), (2, 4), 0,
                                 TINY.vocab_size)
    kw = dict(rng=jax.random.PRNGKey(9), temperature=0.8, top_k=8)
    o1 = generate(dbm, params, prompts, 5, **kw)
    o2 = generate(dbm, params, prompts, 5, **kw)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
    assert np.all((np.asarray(o1) >= 0) & (np.asarray(o1) < TINY.vocab_size))


# ---------------------------------------------------------------------------
# Paged decode attention vs the dense reference path
# ---------------------------------------------------------------------------

def _attn_setup(B=2, S=12, d=64, heads=4, kv=2, key=0):
    dims = A.AttnDims(heads, kv, d // heads)
    p = I.init_params(jax.random.PRNGKey(key), A.attention_spec(d, dims))
    x = jax.random.normal(jax.random.PRNGKey(key + 1), (B, S, d))
    return dims, p, x


@pytest.mark.parametrize("impl", ["auto", "kernels"])
def test_paged_decode_matches_dense(impl):
    """Token-by-token: the paged path (uniform lengths) must reproduce the
    dense decode_attention outputs <=1e-4 fp32."""
    dims, p, x = _attn_setup()
    B, S, d = x.shape
    psz = 4
    pps = KVC.pages_for(S, psz)
    pkv = KVC.init_paged_kv(1 + B * pps, psz, dims, jnp.float32)
    table = KVC.identity_page_table(B, pps)
    dense = A.init_kv_cache(B, S, dims, jnp.float32)
    for t in range(S):
        xt = x[:, t:t + 1]
        o_dense, dense = A.decode_attention(p, xt, dims, dense, t)
        lengths = jnp.full((B,), t, jnp.int32)
        o_paged, pkv = KVC.paged_decode_attention(
            p, xt, dims, pkv, lengths=lengths, page_table=table, impl=impl)
        np.testing.assert_allclose(np.asarray(o_paged), np.asarray(o_dense),
                                   atol=1e-4, rtol=1e-4)


def test_dense_decode_attention_kernel_route():
    """decode_attention(impl='kernels') — the dense cache viewed as pages
    through the flash-decode kernel — matches the reference path <=1e-4."""
    dims, p, x = _attn_setup(key=3)
    B, S, _ = x.shape
    c_ref = A.init_kv_cache(B, S, dims, jnp.float32)
    c_ker = A.init_kv_cache(B, S, dims, jnp.float32)
    for t in range(S):
        o_ref, c_ref = A.decode_attention(p, x[:, t:t + 1], dims, c_ref, t)
        o_ker, c_ker = A.decode_attention(p, x[:, t:t + 1], dims, c_ker, t,
                                          impl="kernels")
        np.testing.assert_allclose(np.asarray(o_ker), np.asarray(o_ref),
                                   atol=1e-4, rtol=1e-4)


def test_dense_kernel_route_swa_ring_matches_reference():
    """The SWA ring buffer is un-rotated into absolute order and served
    through the paged flash-decode kernel; token-by-token outputs must match
    the reference masked attend over the ring — including the wrap-around
    steps (pos >= window) and the not-yet-full prefix (pos < window)."""
    dims, p, x = _attn_setup(S=14, key=4)
    B, S, _ = x.shape
    window = 6
    c_ref = A.init_kv_cache(B, window, dims, jnp.float32)
    c_ker = A.init_kv_cache(B, window, dims, jnp.float32)
    for t in range(S):
        o_ref, c_ref = A.decode_attention(p, x[:, t:t + 1], dims, c_ref, t,
                                          window=window)
        o_ker, c_ker = A.decode_attention(p, x[:, t:t + 1], dims, c_ker, t,
                                          window=window, impl="kernels")
        np.testing.assert_allclose(np.asarray(o_ker), np.asarray(o_ref),
                                   atol=1e-4, rtol=1e-4, err_msg=f"pos {t}")


def test_paged_append_trash_redirect():
    """Inactive slots must not corrupt live pages: their writes land on the
    reserved trash page."""
    dims = A.AttnDims(2, 2, 8)
    pkv = KVC.init_paged_kv(1 + 2, 4, dims, jnp.float32)
    table = KVC.identity_page_table(2, 1)
    k_new = jnp.ones((2, 2, 8))
    lengths = jnp.zeros((2,), jnp.int32)
    out = KVC.append_paged(pkv, k_new, k_new, table, lengths,
                           active=jnp.asarray([True, False]))
    assert float(jnp.sum(jnp.abs(out.k[1]))) > 0      # slot 0's page written
    assert float(jnp.sum(jnp.abs(out.k[2]))) == 0     # slot 1 redirected
    assert float(jnp.sum(jnp.abs(out.k[0, 0]))) > 0   # ... to the trash page


# ---------------------------------------------------------------------------
# Paged commit scan vs full-sequence prefill
# ---------------------------------------------------------------------------

def test_paged_commit_matches_prefill(dbm_params):
    """The engine's prefill (per-token commits into pages) must agree with
    the full-sequence prefill caches for the attention entries."""
    dbm, params = dbm_params
    B, S0, psz = 2, 8, 4
    prompts = jax.random.randint(jax.random.PRNGKey(5), (B, S0), 0,
                                 TINY.vocab_size)
    eng = get_engine(dbm, steps_per_block=1, temperature=0.0, top_k=0,
                     precision="fp32", impl="auto")
    pps = KVC.pages_for(S0, psz)
    kv = dbm.model.init_paged_cache(B, 1 + B * pps, psz, eng.pol)
    table = KVC.identity_page_table(B, pps)
    plens = jnp.full((B,), S0, jnp.int32)
    kv, lengths = eng._prefill(params, kv, table, jnp.zeros((B,), jnp.int32),
                               prompts.astype(jnp.int32), plens,
                               jnp.zeros((B,), jnp.int32))
    assert np.all(np.asarray(lengths) == S0)
    _, pre = dbm.prefill(params, prompts)
    # gather the paged pool back into logical (units, B, S, KV, hd)
    for paged, dense in ((kv, pre),):
        k_log = paged["k"] if isinstance(paged, dict) else paged.k
        k_log = k_log[:, table]               # (units, B, pps, KV, psz, hd)
        k_log = k_log.transpose(0, 1, 2, 4, 3, 5)
        k_log = k_log.reshape(k_log.shape[0], B, pps * psz,
                              *k_log.shape[4:])[:, :, :S0]
        np.testing.assert_allclose(np.asarray(k_log, np.float32),
                                   np.asarray(dense["k"], np.float32),
                                   atol=2e-3)


# ---------------------------------------------------------------------------
# Precision policy: bf16 KV storage, fp32 recurrent states
# ---------------------------------------------------------------------------

def test_paged_cache_dtype_follows_policy():
    dbm = make_dbm()
    kv16 = dbm.model.init_paged_cache(2, 5, 4, "bf16")
    assert kv16.k.dtype == jnp.bfloat16
    kv32 = dbm.model.init_paged_cache(2, 5, 4, "fp32")
    assert kv32.k.dtype == jnp.float32
    # default policy (None) is fp32 — serving passes bf16 explicitly
    assert dbm.model.init_paged_cache(2, 5, 4).k.dtype == jnp.float32


@pytest.mark.slow
def test_hybrid_paged_cache_states_stay_fp32():
    cfg = configs.reduced(configs.get_config("zamba2-7b"))
    dbm = make_dbm(cfg, blocks=2)
    kv = dbm.model.init_paged_cache(2, 5, 4, "bf16")
    assert kv["shared_kv"].k.dtype == jnp.bfloat16      # attention KV paged
    for leaf in jax.tree_util.tree_leaves(kv["mamba"]):
        assert leaf.dtype == jnp.float32                # recurrence override


# ---------------------------------------------------------------------------
# Continuous batching
# ---------------------------------------------------------------------------

def test_continuous_batching_completes_and_reclaims_pages(dbm_params):
    dbm, params = dbm_params
    cb = ContinuousBatcher(dbm, params, num_slots=2, max_prompt=8,
                           max_len=16, seg_len=4, page_size=4)
    d0 = cb.eng.dispatches       # engine is memoized across tests
    rs = np.random.RandomState(0)
    rids = [cb.submit(rs.randint(0, TINY.vocab_size, size=rs.randint(3, 9)),
                      max_new=6) for _ in range(5)]
    done = cb.run(jax.random.PRNGKey(3))
    assert [r.rid for r in done] == rids
    assert all(len(r.out) == 6 for r in done)
    assert all(0 <= t < TINY.vocab_size for r in done for t in r.out)
    # every page returned to the pool after retirement
    assert len(cb.free_pages) == cb.total_pages - 1
    # scan fusion: far fewer dispatches than scan steps executed
    assert (cb.eng.dispatches - d0) * 2 <= cb.steps


def test_reset_paged_slots_restores_init_state():
    """Recycling a slot must restore its recurrent state to the INIT values
    (xlstm max-stabilizers init to -1e30, not 0) without touching the other
    slots. Leaves are (units, B, ...)."""
    cfg = configs.reduced(configs.get_config("xlstm-125m"))
    dbm = make_dbm(cfg, blocks=2)
    kv = dbm.model.init_paged_cache(3, 4, 4, "bf16")
    dirty = jax.tree_util.tree_map(lambda x: x + 1.0, kv)
    out = dbm.model.reset_paged_slots(dirty,
                                      jnp.asarray([True, False, True]))
    for fresh, got, was in zip(jax.tree_util.tree_leaves(kv),
                               jax.tree_util.tree_leaves(out),
                               jax.tree_util.tree_leaves(dirty)):
        fresh, got, was = (np.asarray(x, np.float32)
                           for x in (fresh, got, was))
        np.testing.assert_array_equal(got[:, 0], fresh[:, 0])   # reset
        np.testing.assert_array_equal(got[:, 2], fresh[:, 2])
        np.testing.assert_array_equal(got[:, 1], was[:, 1])     # held


def test_reset_paged_slots_dense_noop_and_hybrid_axis():
    dbm = make_dbm()
    kv = dbm.model.init_paged_cache(2, 4, 4, "bf16")
    assert dbm.model.reset_paged_slots(kv, jnp.asarray([True, True])) is kv
    cfg = configs.reduced(configs.get_config("zamba2-7b"))
    hyb = make_dbm(cfg, blocks=2)
    kvh = hyb.model.init_paged_cache(2, 4, 4, "bf16")
    dirty = dict(kvh, mamba=jax.tree_util.tree_map(lambda x: x + 1.0,
                                                   kvh["mamba"]))
    out = hyb.model.reset_paged_slots(dirty, jnp.asarray([False, True]))
    for leaf in jax.tree_util.tree_leaves(out["mamba"]):
        arr = np.asarray(leaf, np.float32)      # (units, inner, B, ...)
        assert np.all(arr[:, :, 1] == 0) and np.all(arr[:, :, 0] == 1)
    assert out["shared_kv"] is dirty["shared_kv"]   # paged KV untouched


@pytest.mark.slow
def test_continuous_slot_reuse_does_not_leak_state():
    """A recycled slot's SECOND request must be independent of its first
    occupant: serve [p1, p2] and [p1', p2] (same lengths, different tokens)
    through ONE slot — p2's greedy output must be identical. Catches both
    stale recurrent state and stale KV pages leaking across requests."""
    cfg = configs.reduced(configs.get_config("xlstm-125m"))
    dbm = make_dbm(cfg, blocks=2)
    params = dbm.init(jax.random.PRNGKey(0))
    rs = np.random.RandomState(4)
    p1 = rs.randint(0, cfg.vocab_size, size=5)
    p1_alt = (p1 + 7) % cfg.vocab_size
    p2 = rs.randint(0, cfg.vocab_size, size=5)

    def serve(first):
        cb = ContinuousBatcher(dbm, params, num_slots=1, max_prompt=6,
                               max_len=12, seg_len=4, page_size=4)
        cb.submit(first, max_new=5)
        cb.submit(p2, max_new=5)
        done = cb.run(jax.random.PRNGKey(9))
        return done[1].out

    assert serve(p1) == serve(p1_alt)


def test_continuous_batching_rejects_oversized_request(dbm_params):
    dbm, params = dbm_params
    cb = ContinuousBatcher(dbm, params, num_slots=1, max_prompt=8,
                           max_len=16, seg_len=4, page_size=4, total_pages=2)
    cb.submit(np.arange(8) % TINY.vocab_size, max_new=8)   # needs 4 pages
    with pytest.raises(RuntimeError):
        cb.run(jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# Cancellation (PR 6): queued and admitted aborts must free pages exactly —
# these extend the leak tests above to the ``cancel(rid)`` path.
# ---------------------------------------------------------------------------

def test_cancel_queued_request_dropped_before_admission(dbm_params):
    dbm, params = dbm_params
    cb = ContinuousBatcher(dbm, params, num_slots=1, max_prompt=8,
                           max_len=16, seg_len=4, page_size=4)
    rs = np.random.RandomState(21)
    rids = [cb.submit(rs.randint(0, TINY.vocab_size, size=5), max_new=4)
            for _ in range(3)]
    assert cb.cancel(rids[1])
    done = cb.run(jax.random.PRNGKey(0))
    by_rid = {r.rid: r for r in done}
    assert set(by_rid) == set(rids)       # cancelled requests are reported
    assert by_rid[rids[1]].cancelled and by_rid[rids[1]].out == []
    assert len(by_rid[rids[0]].out) == 4 and len(by_rid[rids[2]].out) == 4
    assert cb.cancelled_count == 1
    assert len(cb.free_pages) == cb.total_pages - 1
    assert not cb.cancel(rids[1])         # unknown/finished rid -> False


def test_cancel_active_request_frees_pages_mid_flight(dbm_params):
    dbm, params = dbm_params
    cb = ContinuousBatcher(dbm, params, num_slots=2, max_prompt=8,
                           max_len=16, seg_len=4, page_size=4)
    rs = np.random.RandomState(22)
    rid_a = cb.submit(rs.randint(0, TINY.vocab_size, size=6), max_new=8)
    rid_b = cb.submit(rs.randint(0, TINY.vocab_size, size=6), max_new=8)
    rng = jax.random.PRNGKey(1)
    rng, fin = cb.step(rng)               # admit both + first decode segment
    assert not fin and int(cb.active.sum()) == 2
    assert cb.cancel(rid_a)
    rng, fin = cb.step(rng)               # cancel applies BEFORE the segment
    cancelled = [r for r in fin if r.rid == rid_a]
    assert cancelled and cancelled[0].cancelled
    assert 0 < len(cancelled[0].out) < 8  # aborted mid-generation
    assert not cancelled[0].pages         # its pages went back to the pool
    finished = list(fin)
    while cb.has_work():
        rng, fin = cb.step(rng)
        finished.extend(fin)
    b = [r for r in finished if r.rid == rid_b][0]
    assert not b.cancelled and len(b.out) == 8   # neighbor unaffected
    assert len(cb.free_pages) == cb.total_pages - 1
    assert not cb.active.any() and not cb.page_refs


def test_cancel_respects_prefix_cache_refcounts(dbm_params):
    """Cancelling a request that maps shared prefix pages must only drop the
    SLOT's refs: the cache-retained chain survives and still serves later
    requests."""
    dbm, params = dbm_params
    rs = np.random.RandomState(23)
    sys_p = rs.randint(0, TINY.vocab_size, size=16)    # 4 full pages of 4
    u1 = rs.randint(0, TINY.vocab_size, size=4)
    u2 = rs.randint(0, TINY.vocab_size, size=4)
    cb = ContinuousBatcher(dbm, params, num_slots=1, max_prompt=24,
                           max_len=32, seg_len=4, page_size=4,
                           chunk_size=8, prefix_cache=True,
                           precision="fp32")
    cb.submit(np.concatenate([sys_p, u1]), max_new=4)
    cb.run(jax.random.PRNGKey(0))
    retained = set(cb.page_refs)          # prefix pages held by the cache
    rid = cb.submit(np.concatenate([sys_p, u2]), max_new=8)
    rng = jax.random.PRNGKey(1)
    rng, fin = cb.step(rng)
    req = cb.slot_req[0]
    assert req is not None and req.shared_tokens == 16
    assert any(cb.page_refs.get(p, 0) > 1 for p in req.pages)  # truly shared
    assert cb.cancel(rid)
    rng, fin = cb.step(rng)
    assert fin and fin[0].cancelled
    # slot refs dropped, cache refs intact, nothing double-freed
    assert all(v == 1 for v in cb.page_refs.values())
    assert retained <= set(cb.page_refs)
    assert len(cb.free_pages) + len(cb.page_refs) == cb.total_pages - 1
    # the surviving chain still serves a later request end to end
    cb.submit(np.concatenate([sys_p, u2]), max_new=4)
    done = cb.run(jax.random.PRNGKey(2))
    assert done[0].shared_tokens >= 16 and len(done[0].out) == 4


def test_recycled_slot_after_cancel_no_leak(dbm_params):
    """The PR 3/4 leak property under cancellation: a slot recycled from a
    CANCELLED occupant must serve its next request identically regardless of
    what the cancelled request was."""
    dbm, params = dbm_params
    rs = np.random.RandomState(24)
    p1 = rs.randint(0, TINY.vocab_size, size=8)
    p1_alt = (p1 + 7) % TINY.vocab_size
    p2 = rs.randint(0, TINY.vocab_size, size=8)

    def serve(first):
        cb = ContinuousBatcher(dbm, params, num_slots=1, max_prompt=12,
                               max_len=20, seg_len=4, page_size=4,
                               chunk_size=4, precision="fp32")
        rid1 = cb.submit(first, max_new=8)
        rng = jax.random.PRNGKey(9)
        rng, _ = cb.step(rng)             # chunk 1 of the prompt
        rng, _ = cb.step(rng)             # chunk 2 + first decode segment
        assert len(cb.slot_req[0].out) == 4   # mid-generation
        cb.cancel(rid1)
        rng, fin = cb.step(rng)
        assert fin[0].cancelled
        cb.submit(p2, max_new=5)
        out = []
        while cb.has_work():
            rng, fin = cb.step(rng)
            out.extend(fin)
        assert len(cb.free_pages) == cb.total_pages - 1
        return out[0].out

    assert serve(p1) == serve(p1_alt)


# ---------------------------------------------------------------------------
# Compile-cache behavior (static steps_per_block / sampler config)
# ---------------------------------------------------------------------------

def test_engine_memoized_and_jit_cache_stable(dbm_params):
    dbm, params = dbm_params
    kw = dict(steps_per_block=1, temperature=0.0, top_k=0,
              precision="bf16", impl="auto")
    assert get_engine(dbm, **kw) is get_engine(dbm, **kw)
    assert get_engine(dbm, **dict(kw, steps_per_block=2)) is not \
        get_engine(dbm, **kw)
    eng = get_engine(dbm, **kw)
    prompts = jax.random.randint(jax.random.PRNGKey(6), (2, 4), 0,
                                 TINY.vocab_size)
    eng.generate(params, prompts, 3, jax.random.PRNGKey(0))
    if hasattr(eng._decode, "_cache_size"):
        n = eng._decode._cache_size()
        eng.generate(params, prompts, 3, jax.random.PRNGKey(1))
        assert eng._decode._cache_size() == n      # same shapes: no retrace


# ---------------------------------------------------------------------------
# Slot recycling under prefix sharing (PR 4): retiring a slot must only free
# pages whose refcount drops to zero, and a recycled slot must not observe a
# prior tenant's pages.
# ---------------------------------------------------------------------------

def test_retire_under_sharing_frees_only_zero_ref_pages(dbm_params):
    """Serve two prefix-sharing requests through ONE slot. Retiring the
    first must NOT free the shared prefix pages (the cache and later the
    second slot still hold refs); after both retire, exactly the
    cache-retained pages stay out of the free list."""
    dbm, params = dbm_params
    rs = np.random.RandomState(11)
    sys_p = rs.randint(0, TINY.vocab_size, size=16)    # 4 full pages of 4
    u1 = rs.randint(0, TINY.vocab_size, size=4)
    u2 = rs.randint(0, TINY.vocab_size, size=4)
    cb = ContinuousBatcher(dbm, params, num_slots=1, max_prompt=24,
                           max_len=32, seg_len=4, page_size=4,
                           chunk_size=8, prefix_cache=True,
                           precision="fp32")
    cb.submit(np.concatenate([sys_p, u1]), max_new=4)
    cb.run(jax.random.PRNGKey(0))
    # first request retired: its prefix pages survive as cache-held refs
    retained_after_1 = set(cb.page_refs)
    assert retained_after_1, "prefix pages should stay cache-retained"
    assert all(r == 1 for r in cb.page_refs.values())
    assert len(cb.free_pages) + len(cb.page_refs) == cb.total_pages - 1
    cb.submit(np.concatenate([sys_p, u2]), max_new=4)
    done = cb.run(jax.random.PRNGKey(1))
    assert done[0].shared_tokens == 16
    # second request retired too: shared pages still retained exactly once
    assert set(cb.page_refs) >= retained_after_1
    assert all(r == 1 for r in cb.page_refs.values())
    assert len(cb.free_pages) + len(cb.page_refs) == cb.total_pages - 1


def test_recycled_slot_no_leak_under_prefix_sharing(dbm_params):
    """PR 3's leak test, under prefix sharing: a recycled slot's SECOND
    request must be independent of its first occupant — serve [p1, p2] and
    [p1', p2] (same lengths, different tokens) through ONE slot with the
    prefix cache ON; p2's greedy output must be identical. Catches stale
    pages leaking through the recycled slot AND through the prefix trie."""
    dbm, params = dbm_params
    rs = np.random.RandomState(12)
    p1 = rs.randint(0, TINY.vocab_size, size=8)
    p1_alt = (p1 + 7) % TINY.vocab_size
    p2 = rs.randint(0, TINY.vocab_size, size=8)

    def serve(first):
        cb = ContinuousBatcher(dbm, params, num_slots=1, max_prompt=12,
                               max_len=20, seg_len=4, page_size=4,
                               chunk_size=4, prefix_cache=True,
                               precision="fp32")
        cb.submit(first, max_new=5)
        cb.submit(p2, max_new=5)
        done = cb.run(jax.random.PRNGKey(9))
        assert done[1].shared_tokens == 0     # p2 shares nothing with p1
        return done[1].out

    assert serve(p1) == serve(p1_alt)
