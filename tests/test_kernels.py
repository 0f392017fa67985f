"""Per-kernel allclose sweeps vs the pure-jnp oracles (interpret=True on
CPU), plus model-integration checks (kernel output == model attention)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_prefill import flash_prefill
from repro.kernels.paged_attention import paged_attention
from repro.kernels.ssd_scan import ssd_scan
from repro.kernels import ref

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _tol(dtype):
    return TOL[jnp.bfloat16 if dtype == jnp.bfloat16 else jnp.float32]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,Hq,Hkv,Sq,Skv,D,bq,bkv,window,q_offset",
    [
        (1, 2, 2, 32, 32, 16, 8, 8, None, 0),       # MHA causal
        (2, 8, 2, 64, 64, 32, 16, 16, None, 0),     # GQA
        (1, 4, 1, 128, 128, 64, 32, 32, None, 0),   # MQA larger
        (2, 4, 4, 64, 64, 16, 16, 16, 24, 0),       # sliding window
        (1, 8, 2, 32, 96, 32, 16, 16, None, 64),    # chunked prefill offset
        (1, 4, 2, 16, 80, 16, 8, 16, 32, 64),       # offset + window
    ])
def test_flash_prefill_sweep(B, Hq, Hkv, Sq, Skv, D, bq, bkv, window,
                             q_offset, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, Hq, Sq, D), dtype)
    k = jax.random.normal(ks[1], (B, Hkv, Skv, D), dtype)
    v = jax.random.normal(ks[2], (B, Hkv, Skv, D), dtype)
    out = flash_prefill(q, k, v, causal=True, window=window,
                        q_offset=q_offset, block_q=bq, block_kv=bkv,
                        interpret=True)
    want = ref.flash_prefill_ref(q, k, v, causal=True, window=window,
                                 q_offset=q_offset)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        rtol=_tol(dtype), atol=_tol(dtype))


def _paged_case(*dims, lens=None, kv_block=None):
    """(B, Hq, Hkv, D, page, pps), optional explicit lengths and block."""
    return pytest.param(*dims, lens, kv_block,
                        id="-".join(map(str, dims)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,Hq,Hkv,D,page,pps,lens,kv_block",
    [
        _paged_case(1, 2, 2, 16, 8, 2),
        _paged_case(3, 8, 2, 32, 8, 5),
        _paged_case(2, 4, 1, 64, 16, 4),
        _paged_case(4, 16, 8, 32, 4, 8),
        # 2-page blocks over a 7-page table (a tail block): rows end
        # mid-block, exactly on block edges, at one token, at the cap
        _paged_case(5, 8, 2, 16, 8, 7, lens=(21, 32, 1, 56, 16),
                    kv_block=16),
        # the block rule's 128 tokens over 20 pages (not a multiple):
        # Hkv 8 with G 4, Hkv 1 with G 6, Hkv 2 with G 6
        _paged_case(3, 32, 8, 16, 8, 20, lens=(150, 128, 5)),
        _paged_case(3, 6, 1, 32, 8, 20, lens=(129, 160, 64)),
        _paged_case(3, 12, 2, 16, 16, 9, lens=(100, 144, 32),
                    kv_block=32),
    ])
def test_paged_attention_sweep(B, Hq, Hkv, D, page, pps, lens, kv_block,
                               dtype):
    num_pages = B * pps + 3
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q = jax.random.normal(ks[0], (B, Hq, D), dtype)
    kp = jax.random.normal(ks[1], (num_pages, page, Hkv, D), dtype)
    vp = jax.random.normal(ks[2], (num_pages, page, Hkv, D), dtype)
    bt = jax.random.permutation(
        ks[3], num_pages)[:B * pps].reshape(B, pps).astype(jnp.int32)
    # ragged lengths incl. partially-filled last page and a 1-token seq
    sl = jnp.array(lens or [(i * 7) % (page * pps) + 1 for i in range(B)],
                   jnp.int32)
    out = paged_attention(q, kp, vp, bt, sl, interpret=True,
                          kv_block=kv_block)
    want = ref.paged_attention_ref(q, kp, vp, bt, sl)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        rtol=_tol(dtype), atol=_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,l,h,p,n,chunk",
    [
        (1, 64, 1, 8, 8, 16),
        (2, 128, 3, 16, 8, 32),
        (1, 256, 2, 64, 128, 64),   # production-shaped head
        (2, 96, 4, 32, 16, 32),     # chunk not power-of-two multiple
    ])
def test_ssd_scan_sweep(b, l, h, p, n, chunk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    X = (jax.random.normal(ks[0], (b, l, h, p)) * 0.5).astype(dtype)
    dA = -jnp.abs(jax.random.normal(ks[1], (b, l, h))) * 0.3
    B = (jax.random.normal(ks[2], (b, l, h, n)) * 0.5).astype(dtype)
    C = (jax.random.normal(ks[3], (b, l, h, n)) * 0.5).astype(dtype)
    Y, st = ssd_scan(X, dA.astype(dtype), B, C, chunk=chunk, interpret=True)
    Yr, str_ = ref.ssd_scan_ref(X.astype(jnp.float32), dA,
                                B.astype(jnp.float32),
                                C.astype(jnp.float32))
    tol = _tol(dtype) * 4  # recurrence accumulates error over l
    np.testing.assert_allclose(np.asarray(Y, np.float32),
                               np.asarray(Yr, np.float32),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(st), np.asarray(str_),
                               rtol=tol, atol=tol)


def test_ssd_kernel_matches_model_ssd():
    """The kernel agrees with the chunked jnp SSD used by the mamba2 model."""
    from repro.models.ssm import ssd_chunked
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    b, l, h, p, n = 2, 128, 2, 16, 8
    X = jax.random.normal(ks[0], (b, l, h, p)) * 0.5
    dA = -jnp.abs(jax.random.normal(ks[1], (b, l, h))) * 0.3
    B = jax.random.normal(ks[2], (b, l, h, n)) * 0.5
    C = jax.random.normal(ks[3], (b, l, h, n)) * 0.5
    Yk, stk = ssd_scan(X, dA, B, C, chunk=32, interpret=True)
    Ym, stm = ssd_chunked(X, dA, B, C, chunk=32)
    np.testing.assert_allclose(np.asarray(Yk), np.asarray(Ym),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(stk), np.asarray(stm),
                               rtol=1e-4, atol=1e-4)


def test_flash_matches_model_attention():
    """Kernel output == the model's einsum GQA attention path."""
    from repro.models.layers import attention_mask, gqa_attention
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    B, Hq, Hkv, S, D = 2, 8, 2, 64, 32
    q = jax.random.normal(ks[0], (B, S, Hq, D))
    k = jax.random.normal(ks[1], (B, S, Hkv, D))
    v = jax.random.normal(ks[2], (B, S, Hkv, D))
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    mask = attention_mask(pos, pos, causal=True, window=24)
    want = gqa_attention(q, k, v, mask)
    got = flash_prefill(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                        v.transpose(0, 2, 1, 3), causal=True, window=24,
                        block_q=16, block_kv=16,
                        interpret=True).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
