"""``attention_kernel_ms`` on hand-built traces: the flash-attention
kernels' Mosaic calls (HLO name ``attention.<n>``) are grouped under the
label ``attention``, apart from the compression kernels."""
import pytest

from bench import spec, trace_reduce
from bench.tests.test_bench_trace import _xspace


def _call(name: str) -> str:
    return (f"%{name} = bf16[4,15,2048,64] custom-call(bf16[4,15,2048,64] "
            f"%q), custom_call_target=\\\"tpu_custom_call\\\"")


def _trace(kernels: list[tuple[str, float, float]]):
    host = [("bench.input_wait", 0, 1), ("bench.dispatch", 1, 1),
            ("bench.block", 2, 998)]
    return trace_reduce.reduce(_xspace([
        ("/device:TPU:0", {"XLA Ops": [(_call(n), t, d)
                                       for n, t, d in kernels]}),
        ("/host:CPU", {"python": host})]))


@pytest.mark.parametrize("with_attention", [True, False],
                         ids=["kernel-path", "jnp-path"])
def test_attention_kernel_ms(with_attention):
    kernels = [("grad_sync.7", 10, 40)]
    if with_attention:
        kernels += [("attention.3", 100, 200), ("attention.12", 400, 300)]
    summary = _trace(kernels)
    assert summary["kernel_s"]["grad_sync"] == pytest.approx(40e-6)
    reader = spec.Spec().reader("attention_kernel_ms")
    got = reader.read(summary, {"steps": 2})
    if with_attention:
        assert summary["kernel_s"]["attention"] == pytest.approx(500e-6)
        assert got == pytest.approx(1e3 * 500e-6 / 2)
    else:
        assert "attention" not in summary["kernel_s"] and got is None


def _powersgd_record(steps: int) -> dict:
    sp = spec.Spec()
    w = sp.workload("smollm2-360m.powersgd.1chip")
    cfg = sp.config(w["config"])
    return {"steps": steps, "workload": w, "cfg": cfg, "arch": sp.arch(cfg),
            "peak": sp.peak("TPU v5 lite")}


@pytest.mark.parametrize("metric", ["compress_kernel_ms",
                                    "powersgd_roofline"])
def test_compression_readers_read_only_grad_sync(metric):
    """The PowerSGD readers take the kernels labelled ``grad_sync`` and
    leave the ``attention`` kernels out; with none of their own they read
    nothing."""
    reader = spec.Spec().reader(metric)
    record = _powersgd_record(2)
    both = _trace([("grad_sync.7", 10, 40), ("attention.3", 100, 500)])
    alone = _trace([("grad_sync.7", 10, 40)])
    got = reader.read(both, record)
    assert got is not None and got == reader.read(alone, record)
    if metric == "compress_kernel_ms":
        assert got == pytest.approx(1e3 * 40e-6 / 2)
    else:
        least, _ = reader.least_seconds(record)
        assert got == pytest.approx(100.0 * least / (40e-6 / 2))
    assert reader.read(_trace([("attention.3", 100, 500)]), record) is None
