"""Pallas kernel sweeps: every kernel × shapes × dtypes vs the pure-jnp
oracle (interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.edm_loss import edm_loss
from repro.kernels import flash_attention as FA
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_decode import combine_self, flash_decode
from repro.kernels.fused_adaln import (fused_euler, fused_gate_residual,
                                       fused_ln_modulate)
from repro.nn import cache as KVC

DTYPES = [jnp.float32, jnp.bfloat16]


def tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 \
        else dict(atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("B,H,KV,Sq,Sk,hd", [
    (1, 2, 2, 64, 64, 32),
    (2, 4, 2, 128, 128, 64),     # GQA
    (1, 4, 1, 96, 200, 32),      # MQA, ragged (padding path)
    (2, 2, 2, 256, 256, 128),    # MXU-aligned
    (1, 4, 2, 192, 192, 32),     # GQA; causal skips tiles, full and partial
    (2, 2, 1, 200, 180, 32),     # Sq > Sk, both padded: a partial tail
])
@pytest.mark.parametrize("causal,window", [(True, None), (True, 32),
                                           (False, None)])
def test_flash_attention_sweep(B, H, KV, Sq, Sk, hd, dtype, causal, window):
    if not causal and window is not None:
        pytest.skip("window implies causal")
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(k1, (B, H, Sq, hd), dtype)
    k = jax.random.normal(k2, (B, KV, Sk, hd), dtype)
    v = jax.random.normal(k3, (B, KV, Sk, hd), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=64, block_k=64, interpret=True)
    expect = ref.mha_reference(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), **tol(dtype))


def _keep_mask(kind, Sq, Sk, mask_seq, window):
    """The (Sq, Sk) keep-mask of the model's own mask constructors (an
    independent definition from the kernel's ``_tile_mask``)."""
    from repro.models.common import two_pass_mask
    from repro.nn import attention as A
    mod = (A.bidirectional_mask if kind == "full"
           else A.causal_mask if kind == "causal"
           else A.sliding_window_mask(window) if kind == "window"
           else A.db_concat_mask(mask_seq) if kind == "db_concat"
           else two_pass_mask(mask_seq))
    return np.asarray(mod(jnp.arange(Sq), jnp.arange(Sk)), bool)


def _check_order(table, state, outer):
    """``table`` (rows q tile, k tile, flags) runs the live tiles of
    ``state`` (nq, nk: 0 dead, 1 partial, 2 full) grouped by the tile of
    row ``outer`` in increasing order, inner tiles increasing, and gives an
    outer tile no live tile one EMPTY entry."""
    inner = 1 - outer
    st = state if outer == 0 else state.T
    pos = 0
    for o in range(st.shape[0]):
        live = np.flatnonzero(st[o])
        n = max(live.size, 1)
        group = table[:, pos:pos + n]
        pos += n
        assert (group[outer] == o).all()
        flags = group[2]
        assert (flags & FA.FIRST).tolist() == [FA.FIRST] + [0] * (n - 1)
        assert (flags & FA.LAST).tolist() == [0] * (n - 1) + [FA.LAST]
        if live.size == 0:
            assert flags[0] & FA.EMPTY and not flags[0] & FA.PARTIAL
            assert 0 <= group[inner, 0] < st.shape[1]
            continue
        assert group[inner].tolist() == live.tolist()
        assert not (flags & FA.EMPTY).any()
        partial = (flags & FA.PARTIAL) != 0
        assert partial.tolist() == (st[o, live] == 1).tolist()
    assert pos == table.shape[1]


@pytest.mark.parametrize("kind,Sq,Sk,mask_seq,window,bq,bk", [
    ("full", 100, 96, None, None, 32, 32),        # padded q tail
    ("causal", 96, 200, None, None, 32, 32),      # k tiles no q reaches
    ("causal", 100, 100, None, None, 32, 64),     # rectangular tiles
    ("window", 130, 130, None, 80, 32, 32),
    ("db_concat", 200, 200, 100, None, 32, 32),   # S straddles a tile
    ("db_concat", 2048, 2048, 1024, None, 128, 128),  # the LM's shape
    ("two_pass", 100, 200, 100, None, 32, 64),
])
def test_flash_tile_schedule(kind, Sq, Sk, mask_seq, window, bq, bk):
    """The block-sparse schedule runs exactly the tiles in which the mask
    keeps some pair (a tile kept whole runs unmasked), every q tile in the
    q-major table and every k tile in the k-major one."""
    cfg = FA.FlashConfig(mask_kind=kind, window=window, mask_seq=mask_seq,
                         block_q=bq, block_k=bk)
    (q_major, k_major), share = FA.tile_schedule(cfg, Sq, Sk)
    nq, nk = -(-Sq // bq), -(-Sk // bk)
    keep = np.zeros((nq * bq, nk * bk), bool)
    keep[:Sq, :Sk] = _keep_mask(kind, Sq, Sk, mask_seq, window)
    tiles = keep.reshape(nq, bq, nk, bk)
    state = np.where(tiles.all(axis=(1, 3)), 2,
                     tiles.any(axis=(1, 3)).astype(int))
    assert (state == 2).any() and (state == 1).any()
    assert (state == 0).any() == (kind != "full")
    _check_order(q_major, state, outer=0)
    _check_order(k_major, state, outer=1)
    assert share == np.count_nonzero(state) / state.size
    if kind == "db_concat" and Sq == 2048:
        assert share == 80 / 256
        assert q_major.shape[1] == k_major.shape[1] == 80
        assert ((q_major[2] & FA.PARTIAL) != 0).sum() == 24


@pytest.mark.parametrize("seq,tile", [(2048, 512), (1024, 512), (768, 256),
                                      (384, 128), (197, 128), (64, 128)])
def test_flash_default_tile(seq, tile):
    """Tiles default to the longest of 512, 256, 128 dividing the sequence
    (else 128 over a padded tail; ``_fit`` clips it to a shorter one)."""
    assert FA.default_tile(seq) == tile


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("B,S,d", [(1, 64, 128), (2, 100, 256), (3, 513, 64)])
def test_fused_ln_modulate_sweep(B, S, d, dtype):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
    x = jax.random.normal(k1, (B, S, d), dtype)
    sc = (0.1 * jax.random.normal(k2, (B, d))).astype(dtype)
    sh = (0.1 * jax.random.normal(k3, (B, d))).astype(dtype)
    out = fused_ln_modulate(x, sc, sh, block_rows=64, interpret=True)
    expect = ref.ln_modulate_reference(x, sc, sh)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), **tol(dtype))


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("B,S,d", [(2, 64, 128), (1, 257, 64)])
def test_fused_gate_residual_sweep(B, S, d, dtype):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(2), 3)
    r = jax.random.normal(k1, (B, S, d), dtype)
    br = jax.random.normal(k2, (B, S, d), dtype)
    g = (0.1 * jax.random.normal(k3, (B, d))).astype(dtype)
    out = fused_gate_residual(r, br, g, block_rows=64, interpret=True)
    expect = ref.gate_residual_reference(r, br, g)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), **tol(dtype))


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("B,S,d", [(2, 64, 128), (1, 130, 64)])
def test_fused_euler_sweep(B, S, d, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    z = jax.random.normal(k1, (B, S, d), dtype)
    f = jax.random.normal(k2, (B, S, d), dtype)
    sig = jnp.linspace(0.5, 3.0, B)
    sig2 = sig * 0.3
    out = fused_euler(z, f, sig, sig2, 0.5, block_rows=64, interpret=True)
    expect = ref.euler_reference(z, f, sig, sig2, 0.5)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), **tol(dtype))


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("window", [None, 7])
@pytest.mark.parametrize("B,KV,G,hd,psz,npg", [
    (2, 2, 2, 32, 8, 4),      # GQA
    (1, 4, 1, 64, 16, 2),     # MQA-ish (G=1: group-pad path)
    (3, 1, 8, 32, 4, 8),      # wide group, many small pages
])
def test_flash_decode_sweep(B, KV, G, hd, psz, npg, window, dtype):
    """Split-KV paged decode kernel vs the gather reference: ragged lengths
    (incl. an EMPTY slot and a full slot), GQA grouping, window masking,
    bf16 pages with fp32 logsumexp. fp32 must match <=1e-4 (ISSUE gate)."""
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    P = 1 + B * npg
    pool = KVC.PagedKV(
        jax.random.normal(ks[0], (P, KV, psz, hd), dtype),
        jax.random.normal(ks[1], (P, KV, psz, hd), dtype))
    table = KVC.identity_page_table(B, npg)
    # ragged: slot 0 empty, last slot full, middle arbitrary
    lens = np.linspace(0, npg * psz, B).astype(np.int32)
    lengths = jnp.asarray(lens)
    q = jax.random.normal(ks[2], (B, KV, G, hd), dtype)
    k_self = jax.random.normal(ks[3], (B, KV, hd), dtype)
    v_self = jax.random.normal(ks[4], (B, KV, hd), dtype)
    out_p, lse = flash_decode(q, pool.k, pool.v, table, lengths,
                              window=window, interpret=True)
    scale = 1.0 / (hd ** 0.5)
    s_self = jnp.einsum("bkgd,bkd->bkg", q.astype(jnp.float32),
                        k_self.astype(jnp.float32)) * scale
    got = combine_self(out_p, lse, s_self, v_self.astype(jnp.float32))
    expect = KVC._attend_pages_ref(q, pool, table, lengths, k_self, v_self,
                                   window)
    tol_ = dict(atol=1e-4, rtol=1e-4) if dtype == jnp.float32 else tol(dtype)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(expect, np.float32), **tol_)


def test_flash_decode_trash_page_entries_inert():
    """Page-table entries past a slot's allocation point at the trash page;
    whatever garbage lives there must never leak into the output."""
    dims_kv, G, hd, psz, npg = 2, 2, 32, 4, 3
    P = 1 + npg
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    pool = KVC.PagedKV(jax.random.normal(k1, (P, dims_kv, psz, hd)),
                       jax.random.normal(k2, (P, dims_kv, psz, hd)))
    # slot uses only its first page (length 3 < psz); rest point at trash
    table = jnp.asarray([[1, KVC.TRASH_PAGE, KVC.TRASH_PAGE]], jnp.int32)
    lengths = jnp.asarray([3], jnp.int32)
    q = jax.random.normal(jax.random.PRNGKey(2), (1, dims_kv, G, hd))
    out1, lse1 = flash_decode(q, pool.k, pool.v, table, lengths,
                              interpret=True)
    poisoned = KVC.PagedKV(pool.k.at[KVC.TRASH_PAGE].set(1e3),
                           pool.v.at[KVC.TRASH_PAGE].set(1e3))
    out2, lse2 = flash_decode(q, poisoned.k, poisoned.v, table, lengths,
                              interpret=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2))
    np.testing.assert_allclose(np.asarray(lse1), np.asarray(lse2))


@pytest.mark.parametrize("dtype", [jnp.float32])
@pytest.mark.parametrize("B,S,d", [(2, 64, 128), (1, 300, 64)])
def test_edm_loss_sweep(B, S, d, dtype):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(4), 3)
    f = jax.random.normal(k1, (B, S, d), dtype)
    z = jax.random.normal(k2, (B, S, d), dtype)
    y = jax.random.normal(k3, (B, S, d), dtype)
    sig = jnp.linspace(0.3, 2.0, B)
    out = edm_loss(f, z, y, sig, 0.5, interpret=True)
    expect = ref.edm_loss_reference(f, z, y, sig, 0.5)
    np.testing.assert_allclose(float(out), float(expect), rtol=1e-5)


# ---------------------------------------------------------------------------
# int8 KV: quantize round-trip bounds + quantized kernels vs reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("psz,KV,hd", [(4, 2, 16), (8, 1, 32), (16, 4, 8)])
def test_quantize_roundtrip_error_bound(psz, KV, hd):
    """Per-page symmetric absmax int8: |dequant - x| <= scale/2 elementwise
    (half a quantization step), scales are fp32 with the page axis aligned
    to PAGE_AXIS, and an all-zero page round-trips exactly with scale 0."""
    rng = np.random.RandomState(0)
    P = 6
    x = jnp.asarray(rng.randn(P, KV, psz, hd) *
                    rng.uniform(0.1, 10.0, size=(P, 1, 1, 1)), jnp.float32)
    x = x.at[-1].set(0.0)                       # empty page
    q, s = KVC.quantize_pages(x)
    assert q.dtype == jnp.int8 and s.dtype == jnp.float32
    assert s.shape == (P, 1, 1, 1)              # broadcasts at PAGE_AXIS
    got = KVC.dequantize_pages(q, s)
    err = np.abs(np.asarray(got) - np.asarray(x))
    bound = np.asarray(s) / 2 + 1e-7
    assert (err <= bound).all(), (err.max(), np.asarray(s).ravel())
    np.testing.assert_array_equal(np.asarray(got[-1]), 0.0)
    assert float(s[-1].reshape(())) == 0.0
    # the max-magnitude element of each non-empty page hits the full range
    np.testing.assert_allclose(
        np.abs(np.asarray(q[:-1])).reshape(P - 1, -1).max(1), 127.0)


def _quantized_pool(rng, P, psz, KV, hd):
    kf = jnp.asarray(rng.randn(P, KV, psz, hd), jnp.float32)
    vf = jnp.asarray(rng.randn(P, KV, psz, hd), jnp.float32)
    qk, ks = KVC.quantize_pages(kf)
    qv, vs = KVC.quantize_pages(vf)
    return KVC.PagedKV(qk, qv, ks, vs)


@pytest.mark.parametrize("window", [None, 7])
@pytest.mark.parametrize("B,KV,G,hd,psz,npg", [
    (2, 2, 2, 32, 8, 4),      # GQA
    (1, 4, 1, 64, 16, 2),     # MQA-ish (G=1: group-pad path)
    (3, 1, 8, 32, 4, 8),      # wide group, many small pages
])
def test_flash_decode_int8_sweep(B, KV, G, hd, psz, npg, window):
    """int8 decode kernel (scales scalar-prefetched, dequant fused in
    registers) vs the quantized gather reference — the SAME dequantized
    values feed both, so parity is tight fp32."""
    rng = np.random.RandomState(3)
    pool = _quantized_pool(rng, 1 + B * npg, psz, KV, hd)
    assert pool.quantized
    table = KVC.identity_page_table(B, npg)
    lengths = jnp.asarray(np.linspace(0, npg * psz, B).astype(np.int32))
    q = jnp.asarray(rng.randn(B, KV, G, hd), jnp.float32)
    k_self = jnp.asarray(rng.randn(B, KV, hd), jnp.float32)
    v_self = jnp.asarray(rng.randn(B, KV, hd), jnp.float32)
    out_p, lse = flash_decode(q, pool.k, pool.v, table, lengths,
                              window=window, k_scale=pool.k_scale,
                              v_scale=pool.v_scale, interpret=True)
    scale = 1.0 / (hd ** 0.5)
    s_self = jnp.einsum("bkgd,bkd->bkg", q, k_self) * scale
    got = combine_self(out_p, lse, s_self, v_self)
    expect = KVC._attend_pages_ref(q, pool, table, lengths, k_self, v_self,
                                   window)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(expect, np.float32),
                               atol=1e-4, rtol=1e-4)


def test_flash_decode_int8_trash_page_inert():
    """Poisoned trash-page CONTENT and SCALE must never leak into output."""
    KV, G, hd, psz, npg = 2, 2, 32, 4, 3
    rng = np.random.RandomState(4)
    pool = _quantized_pool(rng, 1 + npg, psz, KV, hd)
    table = jnp.asarray([[1, KVC.TRASH_PAGE, KVC.TRASH_PAGE]], jnp.int32)
    lengths = jnp.asarray([3], jnp.int32)
    q = jnp.asarray(rng.randn(1, KV, G, hd), jnp.float32)
    out1, lse1 = flash_decode(q, pool.k, pool.v, table, lengths,
                              k_scale=pool.k_scale, v_scale=pool.v_scale,
                              interpret=True)
    poisoned = KVC.PagedKV(
        pool.k.at[KVC.TRASH_PAGE].set(127), pool.v.at[KVC.TRASH_PAGE].set(127),
        pool.k_scale.at[KVC.TRASH_PAGE].set(1e3),
        pool.v_scale.at[KVC.TRASH_PAGE].set(1e3))
    out2, lse2 = flash_decode(q, poisoned.k, poisoned.v, table, lengths,
                              k_scale=poisoned.k_scale,
                              v_scale=poisoned.v_scale, interpret=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2))
    np.testing.assert_allclose(np.asarray(lse1), np.asarray(lse2))


@pytest.mark.parametrize("window", [None, 5])
@pytest.mark.parametrize("G", [1, 2])
def test_flash_prefill_int8_matches_ref(window, G):
    """int8 chunked-prefill kernel vs the quantized gather reference over a
    pool built through the REAL quantized append paths (token + chunk)."""
    rng = np.random.RandomState(5)
    B, C, KV, hd, psz = 3, 6, 2, 16, 4
    from repro.nn import attention as A
    dims = A.AttnDims(KV * G, KV, hd)
    lengths = jnp.asarray([0, 3, 9], jnp.int32)
    pps = KVC.pages_for(16, psz)
    pkv = KVC.init_paged_kv(1 + B * pps, psz, dims, jnp.int8)
    assert pkv.quantized
    table = KVC.identity_page_table(B, pps)
    for t in range(int(jnp.max(lengths))):
        kt = jnp.asarray(rng.randn(B, KV, hd), jnp.float32)
        pkv = KVC.append_paged(pkv, kt, kt * 0.5, table,
                               jnp.minimum(lengths, t), active=t < lengths)
    k_new = jnp.asarray(rng.randn(B, C, KV, hd), jnp.float32)
    v_new = jnp.asarray(rng.randn(B, C, KV, hd), jnp.float32)
    n_valid = jnp.asarray([6, 4, 2], jnp.int32)
    pkv = KVC.append_paged_chunk(pkv, k_new, v_new, table, lengths, n_valid)
    q = jnp.asarray(rng.randn(B, C, KV, G, hd), jnp.float32)
    ref_out = KVC.attend_prefill(q, pkv, table, lengths, window=window,
                                 impl="auto")
    ker_out = KVC.attend_prefill(q, pkv, table, lengths, window=window,
                                 impl="kernels")
    for b in range(B):
        nv = int(n_valid[b])
        if nv:
            np.testing.assert_allclose(np.asarray(ker_out)[b, :nv],
                                       np.asarray(ref_out)[b, :nv],
                                       atol=1e-4, rtol=1e-4)
