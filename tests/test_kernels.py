"""Pallas kernel validation: shape/dtype sweeps against the ref.py oracles,
all in interpret mode (CPU executes the kernel bodies)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import (flash_attention, flash_decode, flash_decode_stacked,
                           fused_rmsnorm, ssd_chunk_dual)
from repro.kernels import ref


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


def _flash_expected(q, k, v, causal, window=0):
    b, s, h, d = q.shape
    g = h // k.shape[2]
    kq = jnp.repeat(k, g, axis=2).transpose(0, 2, 1, 3).reshape(b * h, -1, d)
    vq = jnp.repeat(v, g, axis=2).transpose(0, 2, 1, 3).reshape(b * h, -1, d)
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    out = ref.flash_attention_ref(qf, kq, vq, causal=causal, window=window)
    return out.reshape(b, h, s, d).transpose(0, 2, 1, 3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("s,h,kh,d,bq,bk", [
    (128, 4, 4, 64, 64, 64),    # MHA
    (256, 4, 2, 64, 128, 128),  # GQA 2:1
    (256, 8, 1, 128, 128, 64),  # MQA, D=128, asymmetric blocks
])
def test_flash_attention_sweep(dtype, s, h, kh, d, bq, bk):
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(keys[0], (2, s, h, d), dtype)
    k = jax.random.normal(keys[1], (2, s, kh, d), dtype)
    v = jax.random.normal(keys[2], (2, s, kh, d), dtype)
    out = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk,
                          interpret=True)
    exp = _flash_expected(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), **_tol(dtype))


def test_flash_attention_non_causal_and_windowed():
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(keys[0], (1, 256, 2, 64))
    k = jax.random.normal(keys[1], (1, 256, 2, 64))
    v = jax.random.normal(keys[2], (1, 256, 2, 64))
    for kwargs in (dict(causal=False), dict(causal=True, window=64)):
        out = flash_attention(q, k, v, block_q=64, block_k=64, interpret=True,
                              **kwargs)
        exp = _flash_expected(q, k, v, kwargs.get("causal", True),
                              kwargs.get("window", 0))
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("t,h,kh,d,bk", [
    (512, 4, 4, 64, 128),
    (1024, 8, 2, 128, 256),
    (512, 4, 1, 64, 512),
])
def test_flash_decode_sweep(dtype, t, h, kh, d, bk):
    keys = jax.random.split(jax.random.PRNGKey(2), 4)
    b = 2
    q = jax.random.normal(keys[0], (b, 1, h, d), dtype)
    kc = jax.random.normal(keys[1], (b, t, kh, d), dtype)
    vc = jax.random.normal(keys[2], (b, t, kh, d), dtype)
    lengths = jnp.array([t // 3, t], jnp.int32)
    out = flash_decode(q, kc, vc, lengths, block_k=bk, interpret=True)
    g = h // kh
    qf = q[:, 0].reshape(b, kh, g, d).reshape(b * kh, g, d)
    kf = kc.transpose(0, 2, 1, 3).reshape(b * kh, t, d)
    vf = vc.transpose(0, 2, 1, 3).reshape(b * kh, t, d)
    exp = ref.decode_attention_ref(qf, kf, vf, jnp.repeat(lengths, kh))
    exp = exp.reshape(b, h, d)[:, None]
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("block_k", [256, 640])
@pytest.mark.parametrize("fill", ["1", "block_k", "block_k+1", "T"])
@pytest.mark.parametrize("n_layers,layer", [(1, 0), (3, 0), (3, 1), (3, 2)])
def test_flash_decode_stacked_sweep(n_layers, layer, fill, block_k, dtype):
    """One layer of a stacked (L,B,T,K,D) cache read in place: T = 1280,
    GQA with 4 KV heads of 8 query heads each; row 0 holds ``fill`` valid
    positions, row 1 all of them."""
    t, kh, g, d, b = 1280, 4, 8, 128, 2
    fill = {"1": 1, "block_k": block_k, "block_k+1": block_k + 1,
            "T": t}[fill]
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(keys[0], (b, 1, kh * g, d), dtype)
    ks = jax.random.normal(keys[1], (n_layers, b, t, kh, d), dtype)
    vs = jax.random.normal(keys[2], (n_layers, b, t, kh, d), dtype)
    lengths = jnp.array([fill, t], jnp.int32)
    out = flash_decode_stacked(q, ks, vs, jnp.int32(layer), lengths,
                               block_k=block_k, interpret=True)
    exp = _stacked_expected(q, ks, vs, layer, lengths)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), **_tol(dtype))


def _stacked_expected(q, ks, vs, layer, lengths):
    b, _, h, d = q.shape
    t, kh = ks.shape[2], ks.shape[3]
    qf = q[:, 0].reshape(b * kh, h // kh, d)
    kf = ks[layer].transpose(0, 2, 1, 3).reshape(b * kh, t, d)
    vf = vs[layer].transpose(0, 2, 1, 3).reshape(b * kh, t, d)
    exp = ref.decode_attention_ref(qf, kf, vf, jnp.repeat(lengths, kh))
    return exp.reshape(b, h, d)[:, None]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("fill", ["1", "block_k", "block_k+1", "T"])
@pytest.mark.parametrize("t,kh,g,d", [
    (1027, 4, 8, 128),  # yi-9b heads, a cache length no block divides
    (1027, 4, 12, 128),  # starcoder2-15b: G = 12
    (600, 32, 1, 80),  # zamba2-2.7b: 32 KV heads of 80
])
def test_flash_decode_stacked_default_blocks(t, kh, g, d, fill, dtype):
    """The default block size at published head widths, where the cache
    length leaves a partial last block: positions past T are masked."""
    from repro.kernels.decode_attention import default_block_k
    b, n_layers, layer = 2, 3, 2
    bk = default_block_k(t, kh, d, kh * g, jnp.dtype(dtype).itemsize)
    assert t % bk and bk < t
    fill = {"1": 1, "block_k": bk, "block_k+1": bk + 1, "T": t}[fill]
    keys = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(keys[0], (b, 1, kh * g, d), dtype)
    ks = jax.random.normal(keys[1], (n_layers, b, t, kh, d), dtype)
    vs = jax.random.normal(keys[2], (n_layers, b, t, kh, d), dtype)
    lengths = jnp.array([fill, t], jnp.int32)
    out = flash_decode_stacked(q, ks, vs, jnp.int32(layer), lengths,
                               interpret=True)
    exp = _stacked_expected(q, ks, vs, layer, lengths)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), **_tol(dtype))


@pytest.mark.parametrize("t,kh,g,d,itemsize,expected", [
    (1280, 4, 8, 128, 2, 640),  # the serve cell: two equal blocks
    (1027, 4, 8, 128, 2, 528),  # two blocks, the last partial
    (512, 4, 8, 64, 2, 512),  # the whole cache in one block
    (4096, 32, 1, 80, 2, 256),  # zamba2-2.7b's ring: 32 KV heads
    (2048, 16, 1, 128, 2, 512),  # deepseek-moe-16b
    (1280, 32, 1, 128, 4, 128),  # float32, 32 KV heads: the smallest block
])
def test_decode_block_k_follows_vmem_budget(t, kh, g, d, itemsize, expected):
    from repro.kernels.decode_attention import (MIN_BLOCK, VMEM_BUDGET,
                                                _bytes_per_position,
                                                default_block_k, max_block_k)
    bk = default_block_k(t, kh, d, kh * g, itemsize)
    assert bk == expected
    assert bk * _bytes_per_position(kh, d, kh * g, itemsize) <= VMEM_BUDGET
    assert max_block_k(kh, d, kh * g, itemsize) % MIN_BLOCK == 0
    # Widths at which not even MIN_BLOCK positions fit take the XLA read.
    assert max_block_k(64, 256, 64, 4) == 0


@pytest.mark.parametrize("q,p,n,h", [(32, 32, 16, 2), (64, 64, 32, 3),
                                     (128, 32, 64, 1)])
def test_ssd_intra_chunk_sweep(q, p, n, h):
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    b, nc = 2, 2
    xdt = jax.random.normal(keys[0], (b, nc, h, q, p)) * 0.1
    cum = -jnp.cumsum(jax.random.uniform(keys[1], (b, nc, h, q)), axis=-1)
    bm = jax.random.normal(keys[2], (b, nc, q, n)) * 0.3
    cm = jax.random.normal(keys[3], (b, nc, q, n)) * 0.3
    y, st = ssd_chunk_dual(xdt, cum, bm, cm, interpret=True)
    ye, ste = ref.ssd_intra_chunk_ref(xdt, cum, bm, cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ye), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(ste), rtol=1e-4, atol=1e-4)


def test_ssd_kernel_matches_model_reference():
    """Kernel-based chunked SSD == the model's jnp ssd_chunked path."""
    from repro.models.mamba2 import ssd_chunked

    keys = jax.random.split(jax.random.PRNGKey(4), 5)
    b, s, h, p, n, chunk = 2, 128, 2, 32, 16, 32
    x = jax.random.normal(keys[0], (b, s, h, p)) * 0.2
    dt = jax.nn.softplus(jax.random.normal(keys[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(keys[2], (h,)) * 0.2)
    bm = jax.random.normal(keys[3], (b, s, n)) * 0.3
    cm = jax.random.normal(keys[4], (b, s, n)) * 0.3

    y_ref, final_ref = ssd_chunked(x, dt, A, bm, cm, chunk)

    # Assemble the same quantities through the kernel path.
    nc = s // chunk
    xc = x.reshape(b, nc, chunk, h, p)
    dtc = dt.reshape(b, nc, chunk, h)
    cum = jnp.cumsum(dtc * A, axis=2)  # (b,nc,Q,h)
    xdt = (xc * dtc[..., None]).transpose(0, 1, 3, 2, 4)  # (b,nc,h,Q,p)
    cumh = cum.transpose(0, 1, 3, 2)  # (b,nc,h,Q)
    bmc = bm.reshape(b, nc, chunk, n)
    cmc = cm.reshape(b, nc, chunk, n)
    y_intra, states = ssd_chunk_dual(xdt, cumh, bmc, cmc, interpret=True)

    # Inter-chunk recurrence (identical to the model's).
    def body(h_prev, inp):
        cdecay, cstate = inp
        return cdecay[..., None, None] * h_prev + cstate, h_prev

    chunk_decay = jnp.exp(cum[:, :, -1, :])  # (b,nc,h)
    h_last, h_prevs = jax.lax.scan(
        body, jnp.zeros((b, h, n, p)),
        (jnp.moveaxis(chunk_decay, 1, 0),
         jnp.moveaxis(states.astype(jnp.float32), 1, 0)))
    h_prevs = jnp.moveaxis(h_prevs, 0, 1)
    y_inter = jnp.einsum("bcin,bcih,bchnp->bcihp", cmc, jnp.exp(cum), h_prevs)
    y_kernel = (y_intra.transpose(0, 1, 3, 2, 4) + y_inter).reshape(b, s, h, p)

    np.testing.assert_allclose(np.asarray(y_kernel), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h_last), np.asarray(final_ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(8, 128), (4, 32, 256), (3, 5, 64)])
def test_rmsnorm_sweep(dtype, shape):
    keys = jax.random.split(jax.random.PRNGKey(5), 2)
    x = jax.random.normal(keys[0], shape, dtype)
    w = jax.random.normal(keys[1], (shape[-1],), jnp.float32)
    out = fused_rmsnorm(x, w, interpret=True)
    exp = ref.rmsnorm_ref(x, w)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), **_tol(dtype))


def test_flash_attention_matches_model_attention():
    """Kernel output == the model's chunked_attention (the XLA fallback)."""
    from repro.models.layers import chunked_attention

    keys = jax.random.split(jax.random.PRNGKey(6), 3)
    q = jax.random.normal(keys[0], (2, 128, 4, 64))
    k = jax.random.normal(keys[1], (2, 128, 2, 64))
    v = jax.random.normal(keys[2], (2, 128, 2, 64))
    out_kernel = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                                 interpret=True)
    out_model = chunked_attention(q, k, v, chunk=64, causal=True)
    np.testing.assert_allclose(np.asarray(out_kernel), np.asarray(out_model),
                               rtol=2e-5, atol=2e-5)
