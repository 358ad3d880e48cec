"""Compile the main-path Pallas kernels for a described TPU v5e chip at
the widths the train step feeds them, without a chip.

The TPU compiler refuses here what the chip would refuse (an unsupported
reduction, a misaligned block, too much VMEM), which interpret-mode tests
cannot show.  Nothing runs: the results are checked on the chip by
``chip_smoke.py``.  The topology is described inside a module fixture, so
only the test worker that runs this file loads libtpu.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import bitpack, powersgd, qsgd, topk

#: a 25 MB fp32 gradient bucket (the DDP default bucket size)
N = 25 * 2**20 // 4
#: tinyllama-1.1b's largest leaf, d_model x d_ff, at PowerSGD rank 4
ROWS, COLS, RANK = 2048, 5632, 4


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                 # no libtpu / cannot describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


KERNELS = {
    "pack_signs": (lambda g: bitpack.pack_signs(g),
                   [((N,), jnp.float32)]),
    "popcount_votes": (lambda w: bitpack.popcount_votes(w, N),
                       [((4, -(-N // 32)), jnp.uint32)]),
    "powersgd_encode": (powersgd.encode,
                        [((ROWS, COLS), jnp.float32),
                         ((COLS, RANK), jnp.float32)]),
    "powersgd_decode": (powersgd.decode,
                        [((ROWS, RANK), jnp.float32),
                         ((COLS, RANK), jnp.float32)]),
    "qsgd_quantize": (lambda g, norm, key: qsgd.quantize(g, norm, 127, key),
                      [((N,), jnp.float32), ((), jnp.float32), "key"]),
    "threshold_mask": (topk.threshold_mask,
                       [((N,), jnp.float32), ((), jnp.float32)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = KERNELS[name]
    args = [jax.ShapeDtypeStruct((), jax.random.key(0).dtype,
                                 sharding=one_chip) if s == "key"
            else jax.ShapeDtypeStruct(*s, sharding=one_chip)
            for s in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), name


#: the benchmark cells' attention: (batch per chip, q heads, kv heads) at
#: seq 2048, head size 64 — smollm2-360m (GQA 15/5) and smollm2-1.7b (MHA)
ATTENTION = {"smollm2-360m": (4, 15, 5), "smollm2-1.7b": (2, 32, 32)}


@pytest.mark.parametrize("cell", sorted(ATTENTION))
def test_flash_attention_compiles_for_v5e(cell, one_chip):
    """Forward, dK/dV and dQ kernels under remat, as the train step runs
    them: the compiler accepts their tiling and VMEM, and all are Mosaic
    calls named by the ``attention`` scope."""
    import re

    from repro.kernels import flash_attention as fa
    b, h, kvh = ATTENTION[cell]
    q = jax.ShapeDtypeStruct((b, h, 2048, 64), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, kvh, 2048, 64), jnp.bfloat16,
                              sharding=one_chip)

    def grads(q, k, v):
        def loss(q, k, v):
            o = jax.checkpoint(lambda *a: fa.flash_attention(
                *a, causal=True))(q, k, v)
            return jnp.sum(o.astype(jnp.float32) ** 2)
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    text = jax.jit(grads).lower(q, kv, kv).compile().as_text()
    calls = [ln.split(" = ", 1)[0].strip() for ln in text.splitlines()
             if "tpu_custom_call" in ln and " = " in ln]
    # forward, its recompute, dK/dV, dQ
    assert len(calls) == 4, calls
    assert all(re.fullmatch(r"%attention\.\d+", c) for c in calls), calls


def test_mla_flash_attention_compiles_for_v5e(one_chip):
    """Latent attention as the Moonlight cell runs it (2 x 8192 tokens,
    16 heads, q/k 192 and v 128) under remat: four Mosaic calls named
    ``attention``."""
    import re

    from repro.kernels import flash_attention as fa
    qk = jax.ShapeDtypeStruct((2, 16, 8192, 192), jnp.bfloat16,
                              sharding=one_chip)
    v = jax.ShapeDtypeStruct((2, 16, 8192, 128), jnp.bfloat16,
                             sharding=one_chip)

    def grads(q, k, v):
        def loss(q, k, v):
            o = jax.checkpoint(lambda *a: fa.flash_attention(
                *a, causal=True))(q, k, v)
            return jnp.sum(o.astype(jnp.float32) ** 2)
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    text = jax.jit(grads).lower(qk, qk, v).compile().as_text()
    calls = [ln.split(" = ", 1)[0].strip() for ln in text.splitlines()
             if "tpu_custom_call" in ln and " = " in ln]
    assert len(calls) == 4, calls
    assert all(re.fullmatch(r"%attention\.\d+", c) for c in calls), calls


@pytest.mark.parametrize("k,n", [(2048, 1408), (1408, 2048)],
                         ids=["gate-up", "down"])
def test_grouped_matmul_compiles_for_v5e(k, n, one_chip):
    """The drop-free layer's grouped matmul at Moonlight's widths (8 held
    experts, the worst-case row buffer of 2 x 8192 tokens at top-6),
    forward and both backward products: three Mosaic calls named
    ``moe_gmm``."""
    import re

    from repro.kernels import gmm
    tm, g = 256, 8
    rows = -(-(16384 * 6 + g * (tm - 1)) // tm) * tm
    x = jax.ShapeDtypeStruct((rows, k), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((g, k, n), jnp.bfloat16, sharding=one_chip)
    tiles = jax.ShapeDtypeStruct((g,), jnp.int32, sharding=one_chip)

    def fwd_bwd(x, w, tiles):
        y, vjp = jax.vjp(lambda x, w: gmm.grouped_matmul(x, w, tiles, tm),
                         x, w)
        return y, vjp(y)

    text = jax.jit(fwd_bwd).lower(x, w, tiles).compile().as_text()
    calls = [ln.split(" = ", 1)[0].strip() for ln in text.splitlines()
             if "tpu_custom_call" in ln and " = " in ln]
    assert len(calls) == 3, calls
    assert all(re.fullmatch(r"%moe_gmm(\.\d+)?", c) for c in calls), calls
