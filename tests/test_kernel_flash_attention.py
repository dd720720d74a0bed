"""Flash-attention Pallas kernel vs jnp oracle: shape/dtype/mask sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import use_backend
from repro.kernels.flash_attention import attention, attention_ref
from repro.kernels.flash_attention.ref import attention_ref_naive


def rand(rng, shape, dtype):
    x = rng.normal(size=shape).astype(np.float32)
    return jnp.asarray(x, dtype=dtype)


@pytest.mark.parametrize(
    "B,Hq,Hkv,Tq,Tk,d",
    [
        (1, 2, 2, 64, 64, 32),      # MHA, block-aligned? (Tq<bq -> 1 block)
        (2, 4, 2, 128, 128, 64),    # GQA group 2
        (1, 8, 1, 100, 100, 16),    # MQA, ragged seq (padding path)
        (1, 4, 4, 256, 256, 32),    # multi-block kv loop
        (2, 2, 2, 1, 192, 32),      # decode: 1 query vs long kv
    ],
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matches_ref_causal(B, Hq, Hkv, Tq, Tk, d, dtype):
    rng = np.random.default_rng(0)
    q = rand(rng, (B, Hq, Tq, d), dtype)
    k = rand(rng, (B, Hkv, Tk, d), dtype)
    v = rand(rng, (B, Hkv, Tk, d), dtype)
    q_offset = Tk - Tq  # decode-style: query sits at the cache tail
    want = attention_ref(q, k, v, causal=True, q_offset=q_offset)
    with use_backend("pallas_interpret"):
        got = attention(q, k, v, causal=True, q_offset=q_offset,
                        block_q=64, block_k=64)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol,
    )


@pytest.mark.parametrize("window", [16, 64])
def test_sliding_window(window):
    rng = np.random.default_rng(1)
    B, H, T, d = 1, 2, 160, 32
    q = rand(rng, (B, H, T, d), jnp.float32)
    k = rand(rng, (B, H, T, d), jnp.float32)
    v = rand(rng, (B, H, T, d), jnp.float32)
    want = attention_ref(q, k, v, causal=True, window=window)
    with use_backend("pallas_interpret"):
        got = attention(q, k, v, causal=True, window=window,
                        block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_kv_len_padding_mask():
    """Entries past kv_len must not contribute (serving: cache padded)."""
    rng = np.random.default_rng(2)
    B, H, T, d = 1, 2, 64, 32
    q = rand(rng, (B, H, 1, d), jnp.float32)
    k = rand(rng, (B, H, T, d), jnp.float32)
    v = rand(rng, (B, H, T, d), jnp.float32)
    kv_len = 37
    want = attention_ref(q, k[:, :, :kv_len], v[:, :, :kv_len],
                         causal=False)
    with use_backend("pallas_interpret"):
        got = attention(q, k, v, causal=False, kv_len=kv_len,
                        block_q=8, block_k=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_kv_len_traced_decode():
    """A decode step's cache length is traced: the kernel takes it (and
    the query offset) as operands, as serving's decode passes them."""
    rng = np.random.default_rng(3)
    B, H, T, d = 1, 2, 64, 32
    q = rand(rng, (B, H, 1, d), jnp.float32)
    k = rand(rng, (B, H, T, d), jnp.float32)
    v = rand(rng, (B, H, T, d), jnp.float32)
    kv_len = 37
    want = attention_ref(q, k, v, causal=True, kv_len=kv_len,
                         q_offset=kv_len - 1)
    with use_backend("pallas_interpret"):
        got = jax.jit(
            lambda n: attention(q, k, v, causal=True, kv_len=n,
                                q_offset=n - 1, block_q=8, block_k=64)
        )(jnp.int32(kv_len))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("Tq,Tk,chunk_gt", [(64, 300, True), (1, 4000, True)])
def test_chunked_ref_matches_naive(Tq, Tk, chunk_gt):
    """The chunked (scan) reference == naive reference on long KV."""
    rng = np.random.default_rng(4)
    B, Hq, Hkv, d = 1, 4, 2, 32
    q = rand(rng, (B, Hq, Tq, d), jnp.float32)
    k = rand(rng, (B, Hkv, Tk, d), jnp.float32)
    v = rand(rng, (B, Hkv, Tk, d), jnp.float32)
    kv_len = Tk - 17
    want = attention_ref_naive(q, k, v, causal=True, q_offset=kv_len - Tq,
                               kv_len=kv_len, window=128)
    got = attention_ref(q, k, v, causal=True, q_offset=kv_len - Tq,
                        kv_len=kv_len, window=128, chunk=256)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
