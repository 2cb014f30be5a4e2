"""Per-kernel shape/dtype sweeps against the pure-jnp oracles (ref.py),
executed with interpret=True on CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.matmul import matmul
from repro.kernels.rg_lru import rg_lru

KEY = jax.random.PRNGKey(0)


def _zero_padded(a, b, blocks, k_inner):
    """The product as matmul once computed it where the blocks do not divide
    the shape: both operands zero-padded to whole blocks (the k-outer
    kernel's rounded up to (8, 128) tiles), the output sliced back."""
    (M, K), N = a.shape, b.shape[1]
    bm, bn, bk = (min(blk, d) for blk, d in zip(blocks, (M, N, K)))
    if not k_inner:
        bm, bn = -(-bm // 8) * 8, -(-bn // 128) * 128
    pm, pn, pk = (-M) % bm, (-N) % bn, (-K) % bk
    out = matmul(jnp.pad(a, ((0, pm), (0, pk))), jnp.pad(b, ((0, pk), (0, pn))),
                 block_m=bm, block_n=bn, block_k=bk, k_inner=k_inner,
                 interpret=True)
    return out[:M, :N]


def _check_matmul(shape, blocks, dtype, k_inner):
    """Against the oracle, and bitwise against the zero-padded operands: the
    edge blocks read what lies past the arrays (NaN in interpret mode), so
    an edge that is not masked or not dropped shows here."""
    M, N, K = shape
    bm, bn, bk = blocks
    k1, k2 = jax.random.split(KEY)
    a = jax.random.normal(k1, (M, K), jnp.float32).astype(dtype)
    b = jax.random.normal(k2, (K, N), jnp.float32).astype(dtype)
    out = matmul(a, b, block_m=bm, block_n=bn, block_k=bk, k_inner=k_inner,
                 interpret=True)
    assert out.shape == (M, N)
    want = ref.matmul_ref(a, b)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol * np.abs(want).max())
    padded = _zero_padded(a, b, blocks, k_inner)
    assert np.asarray(out).tobytes() == np.asarray(padded).tobytes()


# with blocks of 32 x 32 x 16: the last four shapes leave a ragged edge in
# M alone, N alone, K alone, and in all three
@pytest.mark.parametrize("shape", [(64, 64, 64), (128, 96, 32), (100, 60, 36),
                                   (33, 17, 9), (256, 128, 64), (80, 64, 64),
                                   (64, 80, 64), (64, 64, 40), (72, 48, 24)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("k_inner", [True, False])
def test_matmul_sweep(shape, dtype, k_inner):
    _check_matmul(shape, (32, 32, 16), dtype, k_inner)


# GLM-4-9B's decode matmuls at a 32nd of their widths, in the same number
# of blocks: ffn_in's N 27392 in blocks of 1024 (26.75), ffn_out's K 13696
# in blocks of 2048 (6.6875)
@pytest.mark.parametrize("shape,blocks", [((64, 856, 128), (64, 32, 64)),
                                          ((64, 128, 428), (64, 32, 64))],
                         ids=["ffn_in", "ffn_out"])
@pytest.mark.parametrize("k_inner", [True, False])
def test_matmul_ragged_like_glm(shape, blocks, k_inner):
    _check_matmul(shape, blocks, jnp.bfloat16, k_inner)


@pytest.mark.parametrize("blocks", [(64, 64), (128, 32), (32, 128)])
def test_matmul_block_configs(blocks):
    bm, bn = blocks
    a = jax.random.normal(KEY, (192, 96))
    b = jax.random.normal(KEY, (96, 160))
    out = matmul(a, b, block_m=bm, block_n=bn, block_k=32, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref.matmul_ref(a, b)),
                               rtol=1e-5, atol=1e-4)


def test_matmul_out_bf16():
    a = jax.random.normal(KEY, (64, 48))
    b = jax.random.normal(KEY, (48, 64))
    out = matmul(a, b, block_m=32, block_n=32, block_k=16, out_bf16=True,
                 interpret=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref.matmul_ref(a, b)),
                               rtol=2e-2, atol=2e-1)


@pytest.mark.parametrize("S", [64, 100, 128])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 16), (False, 0)])
def test_flash_attention_sweep(S, causal, window):
    B, D = 2, 32
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = jax.random.normal(k1, (B, S, D))
    k = jax.random.normal(k2, (B, S, D))
    v = jax.random.normal(k3, (B, S, D))
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=32, block_kv=32, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    B, S, D = 1, 64, 16
    q = jax.random.normal(KEY, (B, S, D)).astype(dtype)
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, S, D)).astype(dtype)
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, S, D)).astype(dtype)
    out = flash_attention(q, k, v, block_q=32, block_kv=32, interpret=True)
    want = ref.flash_attention_ref(q, k, v)
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=tol,
                               atol=tol)


@pytest.mark.parametrize("shape", [(2, 64, 64), (1, 50, 100), (3, 33, 17)])
@pytest.mark.parametrize("chunk,block_w", [(16, 32), (64, 64), (8, 128)])
def test_rg_lru_sweep(shape, chunk, block_w):
    B, S, W = shape
    k1, k2 = jax.random.split(KEY)
    a = jax.nn.sigmoid(jax.random.normal(k1, (B, S, W))) * 0.98
    x = jax.random.normal(k2, (B, S, W))
    out = rg_lru(a, x, chunk=chunk, block_w=block_w, interpret=True)
    want = ref.rg_lru_ref(a, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_tuned_ops_use_registry(tmp_path):
    """ops.py dispatches the registry's tuned config end-to-end."""
    from repro.autotune.registry import Registry
    from repro.autotune.space import ProgramConfig, Workload
    from repro.kernels import ops

    reg = Registry(path=str(tmp_path / "reg.json"))
    wl = Workload("matmul", (64, 48, 32))
    reg.put("tpu_v5e", wl, ProgramConfig.make(
        block_m=32, block_n=16, block_k=16, k_inner=0, unroll=1, out_bf16=0),
        100.0)
    reg.save()
    ops.set_registry(Registry(path=str(tmp_path / "reg.json")))
    a = jax.random.normal(KEY, (64, 32))
    b = jax.random.normal(KEY, (32, 48))
    out = ops.tuned_matmul(a, b, device="tpu_v5e", interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.matmul_ref(a, b)),
                               rtol=1e-5, atol=1e-4)
    ops.set_registry(None)
