"""Error feedback rescues a biased compressor: 4-device convergence test.

Seeded quadratic f(x) = 1/2 ||x - x*||^2 with per-device mean-zero
gradient noise, optimized with aggressively sparse randomk (2% of
coordinates per step).  Plain randomk discards the unselected 98% of
every gradient, so each coordinate only contracts by (1 - lr) at its
~1-in-50 selection times — over the step budget the loss barely moves
(a plateau).  The ef: wrapper (docs/adaptive.md) keeps the discarded
mass in a per-device residual and re-injects it, so each selection
delivers the ACCUMULATED gradient — an effective per-selection step of
~lr * n/k — and the iterate converges to a small fraction of the
initial loss on the same budget.

Assertions:
  * ef:randomk final loss <= 1e-2 * L0            (converged)
  * plain randomk final loss >= 0.5 * L0          (plateaued)
  * plain final / three-quarter-loss ratio >= 0.8 (near-flat tail)
  * ef beats plain by >= 20x

The budget is T = 1000 steps.  At 500 steps the ef:randomk iterate is
still decaying (about 0.3 L0 at step 100, 0.03 L0 at step 400) and its
tail is spiky, so the step-500 loss depends on the random draws: over six
seeded streams it spans 0.0036-0.0111 L0, and the 1e-2 bound sat inside
that spread.  (JAX 0.5 made ``jax_threefry_partitionable`` the default,
which changed every seeded draw: with it off, step 500 gives 0.0059 L0;
with it on, 0.0111 L0.)  At 1000 steps the same six streams end at or below
0.0005 L0 (at most 0.0037 L0 anywhere in the last 100 steps), and plain
randomk ends near 0.68 L0 with a final / step-750 ratio near 0.90.
"""
import harness

harness.setup_devices(4)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.core.compression import base as cbase  # noqa: E402
from repro.parallel.compat import make_mesh, shard_map  # noqa: E402

N = 512
N_DEV = 4
T = 1000
LR = 0.01
FRAC = 0.02


def run(name, kw, x_star):
    comp = cbase.make(name, **kw)
    state = comp.init_state(N, jax.random.key(3))
    st_dev = jax.tree.map(lambda s: jnp.broadcast_to(s[None],
                                                     (N_DEV,) + s.shape),
                          state)
    st_spec = jax.tree.map(lambda _: P("data"), st_dev)
    mesh = make_mesh((N_DEV,), ("data",))

    def step_fn(x, st, noise):
        st_l = jax.tree.map(lambda s: s[0], st)
        g = (x - x_star) + noise[0]          # this device's noisy gradient
        out, new = comp.aggregate(g, st_l, ("data",))
        return x - LR * out, jax.tree.map(lambda s: s[None], new)

    # jit the shard_map: un-jitted it re-traces on every loop iteration
    f = jax.jit(shard_map(step_fn, mesh,
                          in_specs=(P(None), st_spec, P("data")),
                          out_specs=(P(None), st_spec)))
    x = jnp.zeros((N,))
    losses = []
    for t in range(T):
        noise = jax.random.normal(jax.random.key(100 + t), (N_DEV, N))
        noise = noise - noise.mean(0)        # mean-zero across the mesh
        x, st_dev = f(x, st_dev, noise)
        losses.append(float(0.5 * jnp.sum((x - x_star) ** 2)))
    return losses


def main():
    x_star = jax.random.normal(jax.random.key(0), (N,))
    l0 = float(0.5 * jnp.sum(x_star ** 2))

    plain = run("randomk", dict(frac=FRAC, error_feedback=False), x_star)
    ef = run("ef:randomk", dict(frac=FRAC), x_star)

    plateau = plain[-1] / plain[3 * T // 4 - 1]
    print(f"  L0 {l0:.2f}")
    print(f"  plain randomk   final {plain[-1]:.3f} "
          f"({plain[-1] / l0:.3f} L0), tail ratio {plateau:.3f}")
    print(f"  ef:randomk      final {ef[-1]:.4f} "
          f"({ef[-1] / l0:.5f} L0)")

    assert ef[-1] <= 1e-2 * l0, (ef[-1], l0)
    assert plain[-1] >= 0.5 * l0, (plain[-1], l0)
    assert plateau >= 0.8, plateau
    assert plain[-1] / ef[-1] >= 20.0, (plain[-1], ef[-1])


if __name__ == "__main__":
    harness.run_main("dist_ef_convergence", main)
