"""Pallas kernel validation (deliverable c): interpret-mode execution vs the
pure-jnp oracles in ref.py, swept across shapes/dtypes including tile-size
non-multiples; hypothesis property sweeps for the streaming kernels.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

# Module-level gate ON PURPOSE (one skip row, not one per test).
# Unblock condition: hypothesis importable — it ships in
# requirements-dev.txt, so CI always runs these; locally they activate
# the moment `hypothesis` is installed, no code change needed.
pytest.importorskip("hypothesis", reason="needs hypothesis "
                                         "(requirements-dev.txt; CI runs "
                                         "these)")
from hypothesis import given, settings, strategies as st

from repro.kernels import bitpack as kb
from repro.kernels import powersgd as kp
from repro.kernels import qsgd as kq
from repro.kernels import ref
from repro.kernels import topk as kt


# ------------------------------------------------------------- powersgd
@pytest.mark.parametrize("rows,cols,rank", [
    (8, 128, 1), (256, 512, 4), (300, 700, 4), (1000, 130, 16),
    (7, 3, 2), (513, 1025, 8),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_powersgd_encode_decode(rows, cols, rank, dtype):
    k1, k2, k3 = jax.random.split(jax.random.key(0), 3)
    m = jax.random.normal(k1, (rows, cols), dtype)
    q = jax.random.normal(k2, (cols, rank), jnp.float32)
    p = jax.random.normal(k3, (rows, rank), jnp.float32)
    enc = kp.encode(m, q, interpret=True)
    np.testing.assert_allclose(enc, ref.powersgd_encode(m, q),
                               rtol=2e-3, atol=2e-3)
    dec = kp.decode(p, q, interpret=True)
    np.testing.assert_allclose(dec, ref.powersgd_decode(p, q),
                               rtol=2e-3, atol=2e-3)


def test_powersgd_block_shapes():
    m = jax.random.normal(jax.random.key(0), (1000, 1000))
    q = jax.random.normal(jax.random.key(1), (1000, 4))
    for bm, bk in [(64, 128), (256, 512), (8, 1024)]:
        out = kp.encode(m, q, bm=bm, bk=bk, interpret=True)
        np.testing.assert_allclose(out, ref.powersgd_encode(m, q),
                                   rtol=2e-3, atol=2e-3)


# ------------------------------------------------------------- bitpack
@settings(max_examples=20, deadline=None)
@given(n=st.integers(1, 5000), seed=st.integers(0, 2**30))
def test_pack_signs_matches_ref(n, seed):
    g = jax.random.normal(jax.random.key(seed), (n,))
    np.testing.assert_array_equal(kb.pack_signs(g, interpret=True),
                                  ref.pack_signs(g))


@pytest.mark.parametrize("p,n", [(1, 33), (3, 1000), (8, 4096), (5, 31)])
def test_popcount_votes_matches_ref(p, n):
    words = -(-n // 32)
    g = jax.random.bits(jax.random.key(p), (p, words), jnp.uint32)
    np.testing.assert_array_equal(
        kb.popcount_votes(g, n, interpret=True), ref.popcount_votes(g, n))


# ------------------------------------------------------------- topk mask
@settings(max_examples=20, deadline=None)
@given(n=st.integers(1, 8192), thr=st.floats(0.0, 3.0),
       seed=st.integers(0, 2**30))
def test_threshold_mask_matches_ref(n, thr, seed):
    g = jax.random.normal(jax.random.key(seed), (n,))
    np.testing.assert_array_equal(
        kt.threshold_mask(g, jnp.float32(thr), interpret=True),
        ref.topk_threshold_mask(g, jnp.float32(thr)))


def test_sampled_threshold_keeps_about_k():
    g = jax.random.normal(jax.random.key(0), (100_000,))
    k = 1000
    t = ref.sampled_threshold(g, k, jax.random.key(1))
    kept = int(jnp.sum(jnp.abs(g) >= t))
    assert 0.5 * k <= kept <= 2.0 * k, kept


# ------------------------------------------------------------- qsgd
@pytest.mark.parametrize("n,levels", [(33, 1), (1000, 7), (70000, 127)])
def test_qsgd_quantize_matches_ref(n, levels):
    g = jax.random.normal(jax.random.key(n), (n,))
    norm = jnp.linalg.norm(g)
    key = jax.random.key(42)
    np.testing.assert_array_equal(
        kq.quantize(g, norm, levels, key, interpret=True),
        ref.qsgd_quantize(g, norm, levels, key))


# ------------------------------------------------------------- flash attention
# Interpret mode at S=256, hd 64.  The kernel feeds the MXU bf16 operands
# and accumulates in fp32, as the jnp path does on TPU (its DEFAULT-precision
# dots round fp32 operands to bf16): both are compared at two bf16 steps
# (2 x 2^-7) of the reference's largest magnitude.
BF16_TOL = 2 * 2.0 ** -7


def _attn_inputs(h, kvh, s=256, hd=64, b=1):
    kq, kk, kv, kd = jax.random.split(jax.random.key(h * 10 + kvh), 4)
    q = jax.random.normal(kq, (b, s, h, hd), jnp.bfloat16)
    k = jax.random.normal(kk, (b, s, kvh, hd), jnp.bfloat16)
    v = jax.random.normal(kv, (b, s, kvh, hd), jnp.bfloat16)
    do = jax.random.normal(kd, (b, s, h, hd), jnp.bfloat16)
    return q, k, v, do


def _close(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=BF16_TOL * np.abs(want).max())


@pytest.mark.parametrize("h,kvh,causal,blocks", [
    (6, 2, True, (128, 128, 128, 128)),
    (6, 2, False, (128, 128, 128, 128)),
    (4, 4, True, (128, 128, 128, 128)),
    (4, 4, False, (128, 128, 128, 128)),
    (6, 2, True, (256, 128, 128, 256)),
], ids=["gqa-causal", "gqa-full", "mha-causal", "mha-full",
        "gqa-causal-uneven-blocks"])
def test_flash_attention_matches_jnp(h, kvh, causal, blocks):
    """Output and q/k/v gradients of the kernel against the jnp loop."""
    from repro.kernels import flash_attention as fa
    from repro.models.attention import chunked_attention
    q, k, v, do = _attn_inputs(h, kvh)
    hm = (0, 2, 1, 3)

    def kernel(q, k, v):
        return fa.flash_attention(
            q.transpose(hm), k.transpose(hm), v.transpose(hm),
            causal=causal, blocks=blocks, interpret=True).transpose(hm)

    def loop(q, k, v):
        return chunked_attention(q, k, v, causal=causal, force="ref")

    out, vjp = jax.vjp(kernel, q, k, v)
    want, want_vjp = jax.vjp(loop, q, k, v)
    _close(out, want)
    for got_g, want_g in zip(vjp(do), want_vjp(do)):
        _close(got_g, want_g)


def test_flash_attention_dispatch_takes_kernel():
    """Default positions, Sq == Sk and a tiled length: the kernel path,
    counted in ops.ATTENTION_PATHS, with the kernel's own result."""
    from repro.kernels import flash_attention as fa
    from repro.kernels import ops
    from repro.models.attention import chunked_attention
    q, k, v, _ = _attn_inputs(6, 2)
    before = ops.ATTENTION_PATHS.copy()
    out = chunked_attention(q, k, v, causal=True, force="pallas")
    assert ops.ATTENTION_PATHS["pallas"] == before["pallas"] + 1
    assert ops.ATTENTION_PATHS["jnp"] == before["jnp"]
    hm = (0, 2, 1, 3)
    direct = fa.flash_attention(q.transpose(hm), k.transpose(hm),
                                v.transpose(hm), causal=True,
                                interpret=True).transpose(hm)
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(direct, np.float32))


@pytest.mark.parametrize("case", ["ragged-length", "cross-attention",
                                  "explicit-positions"])
def test_flash_attention_dispatch_falls_back(case):
    """What the kernel does not take stays on the jnp path, bit for bit
    the result of the jnp path itself."""
    from repro.kernels import ops
    from repro.models.attention import chunked_attention
    if case == "ragged-length":
        q, k, v, _ = _attn_inputs(6, 2, s=200)
        kw = dict(causal=True)
    elif case == "cross-attention":
        q = _attn_inputs(6, 2, s=128)[0]
        _, k, v, _ = _attn_inputs(6, 2, s=256)
        kw = dict(causal=False)
    else:
        q, k, v, _ = _attn_inputs(6, 2)
        pos = jnp.broadcast_to(jnp.arange(256) * 2, (1, 256))
        kw = dict(causal=True, q_positions=pos, k_positions=pos)
    before = ops.ATTENTION_PATHS.copy()
    out = chunked_attention(q, k, v, force="pallas", **kw)
    assert ops.ATTENTION_PATHS["jnp"] == before["jnp"] + 1
    assert ops.ATTENTION_PATHS["pallas"] == before["pallas"]
    want = chunked_attention(q, k, v, force="ref", **kw)
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(want, np.float32))
