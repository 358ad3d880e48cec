"""The flash-attention kernels' share of their roofline: the least time the
chip could take for one step's attention (``attention_work(cfg, seq)`` of
the configuration's architecture module: FLOPs and bytes of causal
attention, forward and backward, per sequence, times the sequences a chip
trains a step) over the device time per step of the Mosaic calls named
``attention``.  The least time is the larger of FLOPs over peak and bytes
over HBM bandwidth; which bound sets it goes to stderr.  Under full remat
the kernels also run the forward a second time, which the work does not
count.  None where the step runs no such kernel or the architecture module
counts no attention work."""
import sys

LAYER = "train step"
UNIT = "%"
MOVES = "tokens_per_s"


def read(trace: dict, record: dict):
    t = trace["kernel_s"].get("attention", 0.0)
    work = getattr(record["arch"], "attention_work", None)
    if t <= 0 or not record["steps"] or work is None:
        return None
    from bench import flops
    w = record["workload"]
    ops, bytes_ = work(record["cfg"], w["seq"])
    least, bound = flops.roofline_seconds(ops * w["seqs_per_chip"],
                                          bytes_ * w["seqs_per_chip"],
                                          record["peak"])
    per_step = t / record["steps"]
    print(f"[bench] attention_roofline: least {least!r} s a step, bound "
          f"{bound}; kernels {per_step!r} s a step", file=sys.stderr)
    return 100.0 * least / per_step
