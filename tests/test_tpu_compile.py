"""Every Pallas kernel compiled by Mosaic for a described TPU v5e, at the
paper's §5.4 LM widths (B=4, H=12, S=1024, hd=64, d=768).

Nothing runs: the TPU compiler is installed here and compiles for a chip
that is described, not attached, so these tests catch what interpret mode
cannot — block shapes the v5e tiling refuses, scratch layouts Mosaic cannot
lower, kernels that need more VMEM than a core has. Forward and backward for
the training kernels, bf16 and int8 pools for the paged serving kernels.

The topology is described inside a module-scoped fixture (never at import):
only one process may load the TPU library, and the test workers each import
this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import edm_loss as EL
from repro.kernels import flash_attention as FA
from repro.kernels import flash_decode as FD
from repro.kernels import flash_prefill as FP
from repro.kernels import fused_adaln as AD

# §5.4 LM (configs/paper.AR_LM): 12 heads of 64, d=768, 4 blocks, S=1024
B, H, S, HD, D = 4, 12, 1024, 64, 768
# serving: 8 slots, 12 KV heads (no GQA), 16-token pages, 512 tokens/slot
SLOTS, PSZ, NPG, CHUNK = 8, 16, 32, 64
POOL = 1 + SLOTS * NPG


@pytest.fixture(scope="module")
def topo():
    import os
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler, or the library is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"


def _grad_all(f, n):
    """Gradient of sum(f(*args)) w.r.t. the first n args (runs the VJP)."""
    def g(*args):
        def loss(*diff):
            out = f(*diff, *args[n:])
            return jnp.sum(out.astype(jnp.float32))
        return jax.grad(loss, argnums=tuple(range(n)))(*args[:n])
    return g


BF = jnp.bfloat16
F32 = jnp.float32


@pytest.mark.parametrize("mask_kind,seq", [("causal", S),
                                           ("db_concat", 2 * S)])
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_flash_attention_compiles(one_chip, no_persistent_cache, mask_kind,
                                  seq, direction):
    def f(q, k, v):
        return FA.flash_attention(q, k, v, mask_kind=mask_kind,
                                  mask_seq=S if mask_kind == "db_concat"
                                  else None)
    fn = f if direction == "fwd" else _grad_all(f, 3)
    shp = ((B, H, seq, HD), BF)
    _compile(fn, one_chip, shp, shp, shp)


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_fused_ln_modulate_compiles(one_chip, no_persistent_cache, direction):
    f = AD.fused_ln_modulate
    fn = f if direction == "fwd" else _grad_all(f, 3)
    _compile(fn, one_chip, ((B, 2 * S, D), BF), ((B, D), BF), ((B, D), BF))


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_fused_gate_residual_compiles(one_chip, no_persistent_cache,
                                      direction):
    f = AD.fused_gate_residual
    fn = f if direction == "fwd" else _grad_all(f, 3)
    _compile(fn, one_chip, ((B, 2 * S, D), BF), ((B, 2 * S, D), BF),
             ((B, D), BF))


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_fused_euler_compiles(one_chip, no_persistent_cache, direction):
    def f(z, fo, sig, sig_to):
        return AD.fused_euler(z, fo, sig, sig_to, 0.5)
    fn = f if direction == "fwd" else _grad_all(f, 2)
    _compile(fn, one_chip, ((B, S, D), BF), ((B, S, D), BF), ((B,), F32),
             ((B,), F32))


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_edm_loss_compiles(one_chip, no_persistent_cache, direction):
    def f(fo, z, y, sig):
        return EL.edm_loss(fo, z, y, sig, 0.5)
    fn = f if direction == "fwd" else _grad_all(f, 3)
    shp = ((B, S, D), F32)
    _compile(fn, one_chip, shp, shp, shp, ((B,), F32))


def _pool_shapes(kv_dtype):
    pages = ((POOL, H, PSZ, HD), kv_dtype)
    shapes = [pages, pages, ((SLOTS, NPG), jnp.int32), ((SLOTS,), jnp.int32)]
    if kv_dtype == jnp.int8:
        shapes += [((POOL, 1, 1, 1), F32)] * 2
    return shapes


@pytest.mark.parametrize("kv_dtype", [BF, jnp.int8], ids=["bf16", "int8"])
def test_flash_decode_compiles(one_chip, no_persistent_cache, kv_dtype):
    def f(q, kp, vp, tbl, lens, *scales):
        ks, vs = scales if scales else (None, None)
        return FD.flash_decode(q, kp, vp, tbl, lens, k_scale=ks, v_scale=vs)
    _compile(f, one_chip, ((SLOTS, H, 1, HD), BF), *_pool_shapes(kv_dtype))


@pytest.mark.parametrize("kv_dtype", [BF, jnp.int8], ids=["bf16", "int8"])
def test_flash_prefill_compiles(one_chip, no_persistent_cache, kv_dtype):
    def f(q, kp, vp, tbl, lens, *scales):
        ks, vs = scales if scales else (None, None)
        return FP.flash_prefill(q, kp, vp, tbl, lens, k_scale=ks, v_scale=vs)
    _compile(f, one_chip, ((SLOTS, CHUNK, H, 1, HD), BF),
             *_pool_shapes(kv_dtype))
