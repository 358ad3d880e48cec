"""The encode -> Payload -> reduce -> decode contract (docs/compression_api.md).

Wire-format truthfulness: the perf model's ``compressed_bytes`` must equal
the bytes of the payloads ``encode`` actually produces — for EVERY
registered compressor, so a payload change can never silently drift from
the analytical model.  Plus: three-phase composition == ``aggregate``,
registry/plan plumbing, and the matrix_shape degenerate sizes.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import aggregator as agg_mod
from repro.core.compression import base as cbase
from repro.core.compression.powersgd import matrix_shape
from repro.core.perfmodel.model import CompressionSpec
from repro.parallel.compat import make_mesh, shard_map

N = 1000


def _as_np(x):
    if jnp.issubdtype(x.dtype, jax.dtypes.prng_key):
        x = jax.random.key_data(x)
    return np.asarray(x)

# every registered compressor, with small-bucket-friendly kwargs
METHODS = [
    ("none", {}),
    ("powersgd", dict(rank=4, min_cols=16)),
    ("signsgd", {}),
    ("signsgd", dict(error_feedback=False)),
    ("mstopk", dict(frac=0.01)),
    ("randomk", {}),
    ("qsgd", dict(bits=8)),
    ("qsgd", dict(bits=4, error_feedback=True)),
    ("terngrad", {}),
]


@pytest.fixture(scope="module")
def g():
    return jax.random.normal(jax.random.key(0), (N,))


def test_every_registered_compressor_is_covered():
    assert {name for name, _ in METHODS} == set(cbase.registry())


# ------------------------------------------------------------- wire truth
@pytest.mark.parametrize("name,kw", METHODS,
                         ids=[f"{n}-{i}" for i, (n, _) in enumerate(METHODS)])
def test_compressed_bytes_equals_actual_payload_nbytes(name, kw, g):
    """Runtime payload == perf-model bytes, for each compressor."""
    comp = cbase.make(name, **kw)
    st = comp.init_state(N, jax.random.key(1))
    # encode (and wire_rounds) are collective-free by contract: call direct
    payloads = comp.wire_rounds(g, st)
    actual = sum(p.nbytes for p in payloads)
    assert comp.compressed_bytes(N) == actual
    # per-round accounting agrees with the concrete rounds too
    assert comp.wire_round_bytes(N) == tuple(p.nbytes for p in payloads)
    # and the perf-model spec is built from the same numbers
    spec = CompressionSpec.for_compressor(comp, N, t_encode_decode=0.0)
    assert spec.total_payload == actual
    assert spec.associative == comp.associative
    assert len(spec.payload_bytes) == len(payloads)


@pytest.mark.parametrize("name,kw", METHODS,
                         ids=[f"{n}-{i}" for i, (n, _) in enumerate(METHODS)])
def test_payload_declares_its_wire_format(name, kw, g):
    comp = cbase.make(name, **kw)
    st = comp.init_state(N, jax.random.key(1))
    for payload in comp.wire_rounds(g, st):
        assert payload.associative == comp.associative
        assert not payload.reduced
        spec = payload.wire_spec()
        assert spec, "wire_spec must name at least one tensor"
        assert sum(e["nbytes"] for e in spec.values()) == payload.nbytes
        for entry in spec.values():
            np.dtype(entry["dtype"])          # parseable dtype string


# -------------------------------------------- three-phase == aggregate
@pytest.mark.parametrize("name,kw", METHODS,
                         ids=[f"{n}-{i}" for i, (n, _) in enumerate(METHODS)])
def test_three_phase_composition_matches_aggregate(name, kw, g):
    """aggregate() and the manual encode_and_reduce -> decode pipeline (as
    GradAggregator runs it) produce identical outputs and states under a
    1-device mesh."""
    comp = cbase.make(name, **kw)
    st = comp.init_state(N, jax.random.key(1))
    st_spec = jax.tree.map(lambda _: P(), st)
    mesh = make_mesh((1,), ("data",))

    def fused(b, s):
        return comp.aggregate(b, s, ("data",))

    def phased(b, s):
        payload = comp.encode_and_reduce(b, s, ("data",))
        return comp.decode(payload, b, s)

    outs = {}
    for tag, fn in (("fused", fused), ("phased", phased)):
        f = shard_map(fn, mesh, in_specs=(P(None), st_spec),
                      out_specs=(P(None), st_spec))
        outs[tag] = f(g, st)
    np.testing.assert_array_equal(np.asarray(outs["fused"][0]),
                                  np.asarray(outs["phased"][0]))
    for a, b in zip(jax.tree.leaves(outs["fused"][1]),
                    jax.tree.leaves(outs["phased"][1])):
        np.testing.assert_array_equal(_as_np(a), _as_np(b))


def test_reduce_payload_is_identity_mean_on_one_device(g):
    """Associative reduce over a singleton axis is a no-op mean; the
    non-associative gather grows a leading peer axis of size 1 and stashes
    the pre-reduce tensors in .local."""
    mesh = make_mesh((1,), ("data",))

    def run(b):
        assoc = cbase.reduce_payload(
            cbase.Payload({"x": b}, associative=True), ("data",))
        gathered = cbase.reduce_payload(
            cbase.Payload({"x": b}, associative=False), ("data",))
        return assoc.tensors["x"], gathered.tensors["x"], \
            gathered.local["x"]

    f = shard_map(run, mesh, in_specs=(P(None),),
                  out_specs=(P(None), P(None, None), P(None)))
    mean, gath, local = f(g)
    np.testing.assert_allclose(np.asarray(mean), np.asarray(g), rtol=1e-6)
    assert gath.shape == (1, N)
    np.testing.assert_array_equal(np.asarray(gath[0]), np.asarray(local))


# ------------------------------------------------------------- aggregator
def test_aggregator_reduce_selects_collective_from_payload(g):
    """GradAggregator.reduce consumes the payload's associativity: the
    associative path keeps local shape, the gather path adds the peer axis."""
    cfg = agg_mod.AggregatorConfig(compressor="signsgd",
                                   compress_axes=("data",), raw_axes=())
    agg = agg_mod.GradAggregator(cfg)
    mesh = make_mesh((1,), ("data",))

    def run(b):
        red = agg.reduce(cbase.Payload({"x": b}, associative=False))
        return red.tensors["x"]

    f = shard_map(run, mesh, in_specs=(P(None),), out_specs=P(None, None))
    assert f(g).shape == (1, N)


# --------------------------------------------------------------- registry
def test_registry_covers_builtins_and_plan_kwargs():
    reg = cbase.registry()
    assert set(reg) == {"none", "powersgd", "signsgd", "mstopk", "randomk",
                        "qsgd", "terngrad"}
    # the one plan->kwargs mapping in the codebase
    plan = dataclasses.make_dataclass(
        "PlanStub", ["compression", "powersgd_rank", "topk_frac",
                     "qsgd_bits", "error_feedback"])
    assert cbase.plan_kwargs(plan("powersgd", 7, 0.5, 4, False)) == \
        {"rank": 7}
    assert cbase.plan_kwargs(plan("mstopk", 7, 0.5, 4, False)) == \
        {"frac": 0.5, "error_feedback": False}
    assert cbase.plan_kwargs(plan("qsgd", 7, 0.5, 4, True)) == \
        {"bits": 4, "error_feedback": True}
    assert cbase.plan_kwargs(plan("none", 7, 0.5, 4, True)) == {}
    comp = cbase.from_plan(plan("powersgd", 7, 0.5, 4, False))
    assert comp.rank == 7


def test_third_party_registration_without_editing_core():
    @cbase.register_compressor("_test_identity")
    class Identity(cbase.Compressor):
        name = "_test_identity"

        def encode(self, bucket, state, rank=None):
            return cbase.Payload({"b": bucket}, associative=True)

        def decode(self, payload, bucket, state):
            return payload.tensors["b"].astype(bucket.dtype), state

    try:
        comp = cbase.make("_test_identity")
        assert comp.compressed_bytes(128) == 128 * 4
        assert comp.registry_name == "_test_identity"
    finally:
        cbase._REGISTRY.pop("_test_identity", None)


# ----------------------------------------------- comm-plan wire accounting
def test_reduce_payload_takes_a_comm_plan(g):
    """The collective schedule is an explicit CommPlan argument; the
    ring decomposition returns the same mean as the historic dispatch and
    illegal (plan, payload) combinations raise."""
    from repro.parallel import commplan as cp
    mesh = make_mesh((1,), ("data",))

    def run(b):
        auto = cbase.Payload({"x": b}).reduce(("data",))
        ring = cbase.Payload({"x": b}).reduce(
            ("data",), cp.CommPlan("reduce_scatter_allgather"))
        return auto.tensors["x"], ring.tensors["x"]

    f = shard_map(run, mesh, in_specs=(P(None),),
                  out_specs=(P(None), P(None)))
    auto, ring = f(g)
    np.testing.assert_array_equal(np.asarray(auto), np.asarray(ring))
    with pytest.raises(cp.CommPlanError):
        cbase.reduce_payload(cbase.Payload({"x": g}, associative=False),
                             ("data",), cp.CommPlan("allreduce"))


def _abstract_rounds(comp, n):
    """Shape-faithful wire payloads without running the encode math
    (``wire_spec`` reads only shapes/dtypes, so eval_shape suffices)."""
    def f(key):
        bucket = jnp.zeros((n,), jnp.float32)
        return comp.wire_rounds(bucket, comp.init_state(n, key))
    return jax.eval_shape(f, jax.random.key(0))


def _check_plan_bytes_round_trip(n: int, p: int, congestion: float):
    """The ISSUE-5 invariant: for EVERY registered compressor × EVERY
    legal CommPlan, the bytes declared by the runtime payloads'
    ``wire_spec`` feed the per-plan byte formula to exactly the same
    number the perf model computes from its derived ``CompressionSpec`` —
    so per-plan analytic bytes can never drift from what the runtime
    would put on the wire.  Illegal combinations raise on BOTH sides."""
    from repro.core.perfmodel import costs
    from repro.parallel import commplan as cp
    for name, kw in METHODS:
        comp = cbase.make(name, **kw)
        payloads = _abstract_rounds(comp, n)
        runtime_rounds = [
            sum(e["nbytes"] for e in pl.wire_spec().values())
            for pl in payloads]
        cspec = CompressionSpec.for_compressor(comp, n,
                                               t_encode_decode=0.0)
        assert tuple(runtime_rounds) == comp.wire_round_bytes(n) \
            == cspec.payload_bytes
        for kind in cp.KINDS:
            plan = cp.CommPlan(kind)
            if not plan.legal_for(comp.associative):
                with pytest.raises(cp.CommPlanError):
                    costs.plan_collective(plan, comp.associative,
                                          float(n), p, 1e9, 1e-6)
                continue
            resolved = plan.resolve(comp.associative)
            runtime_bytes = sum(resolved.wire_bytes(b, p, congestion)
                                for b in runtime_rounds)
            model_bytes = sum(resolved.wire_bytes(b, p, congestion)
                              for b in cspec.payload_bytes)
            assert runtime_bytes == model_bytes
            # the closed forms apply the formula to the SUM of the rounds,
            # the runtime sums per-round formulas: equal up to float
            # rounding (a few ulps), not bit for bit
            if kind in ("allreduce", "reduce_scatter_allgather"):
                assert runtime_bytes == pytest.approx(
                    2.0 * sum(runtime_rounds) * (p - 1) / p, rel=1e-12)
            if kind == "gather_all":
                assert runtime_bytes == pytest.approx(
                    congestion * sum(runtime_rounds) * (p - 1), rel=1e-12)


def test_plan_bytes_round_trip_fixed_point():
    """One pinned instance of the property (runs even without the
    dev-only hypothesis dep)."""
    _check_plan_bytes_round_trip(n=1000, p=96, congestion=2.0)


try:
    from hypothesis import given, settings, strategies as hst
    HAVE_HYPOTHESIS = True
except ImportError:                               # dev-only dep
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    @settings(max_examples=15, deadline=None)
    @given(n=hst.integers(min_value=200, max_value=4096),
           p=hst.sampled_from([2, 4, 16, 96]),
           congestion=hst.floats(min_value=1.0, max_value=2.0))
    def test_every_compressor_every_legal_plan_bytes_round_trip(
            n, p, congestion):
        _check_plan_bytes_round_trip(n, p, congestion)


# ------------------------------------------------- error feedback (ef:)
def _check_ef_none_is_identity(n: int, kind: str, seed: int,
                               steps: int = 2):
    """The ISSUE-7 satellite property: wrapping the identity compressor
    in error feedback (``ef:none``, repro.adaptive.feedback) is a no-op —
    after every step the residual is EXACTLY zero and the applied update
    is bitwise-equal to the plain aggregated gradient — under every legal
    CommPlan, compared like-for-like (both sides ride the same plan)."""
    from repro.parallel import commplan as cp
    plain = cbase.make("none")
    wrapped = cbase.make("ef:none")
    plan = cp.CommPlan(kind) if kind != "auto" else None
    if plan is not None:
        assert plan.legal_for(wrapped.associative)
    mesh = make_mesh((1,), ("data",))
    st_w = wrapped.init_state(n, jax.random.key(seed))
    st_w_spec = jax.tree.map(lambda _: P(), st_w)
    st_p = plain.init_state(n, jax.random.key(seed))
    st_p_spec = jax.tree.map(lambda _: P(), st_p)
    for i in range(steps):
        g = jax.random.normal(jax.random.key(seed + i), (n,))
        f_w = shard_map(
            lambda b, s: wrapped.aggregate(b, s, ("data",), plan),
            mesh, in_specs=(P(None), st_w_spec),
            out_specs=(P(None), st_w_spec))
        f_p = shard_map(
            lambda b, s: plain.aggregate(b, s, ("data",), plan),
            mesh, in_specs=(P(None), st_p_spec),
            out_specs=(P(None), st_p_spec))
        out_w, st_w = f_w(g, st_w)
        out_p, st_p = f_p(g, st_p)
        np.testing.assert_array_equal(np.asarray(out_w), np.asarray(out_p))
        assert not np.asarray(st_w.residual).any(), \
            f"ef:none residual must stay exactly zero (plan {kind!r})"


def test_ef_none_identity_fixed_point():
    """One pinned instance per legal plan (runs without hypothesis)."""
    from repro.parallel import commplan as cp
    for kind in cp.KINDS + ("auto",):
        _check_ef_none_is_identity(n=257, kind=kind, seed=3)


if HAVE_HYPOTHESIS:
    @settings(max_examples=8, deadline=None)
    @given(n=hst.integers(min_value=8, max_value=2048),
           kind=hst.sampled_from(("allreduce", "reduce_scatter_allgather",
                                  "reduce_to_owner_broadcast", "gather_all",
                                  "hierarchical", "auto")),
           seed=hst.integers(min_value=0, max_value=2 ** 16))
    def test_ef_none_identity_every_legal_plan(n, kind, seed):
        _check_ef_none_is_identity(n, kind, seed)


# ------------------------------------------------------------ matrix_shape
@pytest.mark.parametrize("n", [1, 2, 3, 5, 16, 127, 128, 129, 1000, 4096,
                               1 << 20])
def test_matrix_shape_degenerate_sizes(n):
    rows, cols = matrix_shape(n)
    assert rows >= 1 and cols >= 1
    assert rows * cols >= n                   # bucket fits
    assert (rows - 1) * cols < n              # no wasted full rows
    assert cols <= max(128, n)                # tiny buckets: cols == n
    if n < 128:
        assert (rows, cols) == (1, n)


def test_matrix_shape_respects_min_cols_lane_width():
    for n in (1000, 4096, 100_000):
        _, cols = matrix_shape(n, min_cols=128)
        if n >= 128:
            assert cols % 128 == 0
