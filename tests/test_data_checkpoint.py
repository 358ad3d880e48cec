"""Data determinism / pipeline cursor exactness; checkpoint atomicity,
rotation and restore round-trips (single device — elastic reshard is in
tests/dist/).
"""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import checkpoint as ckpt
from repro.data.pipeline import Pipeline
from repro.data.synthetic import DataConfig, batch_at


def test_data_deterministic():
    cfg = DataConfig(vocab=97, seq_len=16, global_batch=4, seed=3)
    a = batch_at(cfg, 5)
    b = batch_at(cfg, 5)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    c = batch_at(cfg, 6)
    assert not np.array_equal(a["tokens"], c["tokens"])


def test_data_next_token_structure():
    cfg = DataConfig(vocab=97, seq_len=64, global_batch=8, seed=0,
                     noise=0.0)
    b = batch_at(cfg, 0)
    # with zero noise, labels are exactly perm[tokens]
    from repro.data.synthetic import _perm
    perm = _perm(cfg)
    np.testing.assert_array_equal(b["labels"], perm[b["tokens"]])


def test_pipeline_cursor_exact_restart():
    cfg = DataConfig(vocab=11, seq_len=8, global_batch=2)
    p1 = Pipeline(cfg, prefetch=2)
    batches = [next(p1) for _ in range(5)]
    cur = p1.cursor()
    assert cur == 5
    p2 = Pipeline(cfg, prefetch=2)
    p2.seek(3)
    b3 = next(p2)
    np.testing.assert_array_equal(np.asarray(b3["tokens"]),
                                  np.asarray(batches[3]["tokens"]))


def test_pipeline_close_stops_prefetch_thread():
    """No prefetch thread may outlive ``close`` (a daemon thread inside a
    device transfer at interpreter exit aborts the process)."""
    import threading
    before = threading.active_count()
    p = Pipeline(DataConfig(vocab=11, seq_len=8, global_batch=2),
                 prefetch=2)
    next(p)
    assert threading.active_count() == before + 1
    p.close()
    assert threading.active_count() == before


@pytest.mark.parametrize("prefetch", [0, 2])
def test_pipeline_wait_is_a_trace_span(tmp_path, prefetch):
    """Each ``next()`` writes one ``data.wait`` host span into a profiler
    trace: the consumer's wait, where the data layer measures it."""
    import glob

    from jax.profiler import ProfileData
    p = Pipeline(DataConfig(vocab=11, seq_len=8, global_batch=2),
                 prefetch=prefetch)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(3):
            next(p)
    finally:
        jax.profiler.stop_trace()
        p.close()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    spans = [ev for plane in ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events
             if ev.name == "data.wait"]
    assert len(spans) == 3


def test_host_sharding_partitions_batch():
    cfg = DataConfig(vocab=11, seq_len=8, global_batch=4)
    h0 = batch_at(cfg, 0, host=0, num_hosts=2)
    h1 = batch_at(cfg, 0, host=1, num_hosts=2)
    assert h0["tokens"].shape == (2, 8)
    assert not np.array_equal(h0["tokens"], h1["tokens"])


def test_checkpoint_roundtrip_and_rotation():
    state = {"step": jnp.int32(7),
             "params": {"w": jnp.arange(6.0).reshape(2, 3),
                        "emb": jnp.ones((4, 2), jnp.bfloat16)},
             "opt": (jnp.zeros((3,)),)}
    with tempfile.TemporaryDirectory() as d:
        for s in (1, 2, 3):
            ckpt.save(d, s, state, cursor=s * 10)
        steps = ckpt.list_steps(d)
        assert steps == [1, 2, 3]
        like = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
        restored, cursor = ckpt.restore(d, 3, like)
        assert cursor == 30
        for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))


def test_checkpoint_incomplete_dir_ignored():
    state = {"w": jnp.ones((2,))}
    with tempfile.TemporaryDirectory() as d:
        ckpt.save(d, 1, state)
        # simulate a crashed writer: step_2 dir without meta
        os.makedirs(os.path.join(d, "step_000000002"))
        assert ckpt.list_steps(d) == [1]


def test_checkpoint_shape_mismatch_policy():
    state = {"dev_state": jnp.zeros((8, 3))}
    with tempfile.TemporaryDirectory() as d:
        ckpt.save(d, 1, state)
        like = {"dev_state": jax.ShapeDtypeStruct((4, 3), jnp.float32)}
        try:
            ckpt.restore(d, 1, like)
            assert False, "should raise without reset_device_state"
        except ValueError:
            pass
        restored, _ = ckpt.restore(d, 1, like, reset_device_state=True)
        assert restored["dev_state"].shape == (4, 3)
        np.testing.assert_array_equal(restored["dev_state"], 0.0)
