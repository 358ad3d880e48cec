"""PowerSGD's share of its roofline: the least time the chip could take
for one step's PowerSGD matmuls (work counts in ``bench/flops.py``) over
the device time per step of the Pallas kernels labelled by the program's
``grad_sync`` scope (``compress_kernel_ms``'s; the flash-attention calls,
labelled ``attention``, are not counted).  Each bucket's least time
is the larger of its FLOPs over peak and its bytes over HBM bandwidth;
the least times add up.  Which bound sets them goes to stderr (at rank 4
every bucket is bound by HBM)."""
import sys

LAYER = "compression kernels"
UNIT = "%"
MOVES = "tokens_per_s"


def least_seconds(record: dict) -> tuple[float, str]:
    from bench import flops, reference
    w = record["workload"]
    rank = w["plan"]["powersgd_rank"]
    t, bounds = 0.0, set()
    for bkt in reference.buckets(record["cfg"], w, record["arch"]):
        rows, cols = flops.powersgd_matrix_shape(sum(l[2] for l in bkt))
        least, bound = flops.roofline_seconds(
            *flops.powersgd_work(rows, cols, rank), record["peak"])
        t += least
        bounds.add(bound)
    return t, "+".join(sorted(bounds))


def read(trace: dict, record: dict):
    t = trace["kernel_s"].get("grad_sync", 0.0)
    if t <= 0 or not record["steps"] or \
            record["workload"]["plan"].get("compression") != "powersgd":
        return None
    least, bound = least_seconds(record)
    print(f"[bench] powersgd_roofline: least {least!r} s a step, bound "
          f"{bound}; kernels {t / record['steps']!r} s a step",
          file=sys.stderr)
    return 100.0 * least / (t / record["steps"])
