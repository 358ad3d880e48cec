"""Device time per step of the compression kernels: the Pallas kernels
(Mosaic custom calls) labelled by the program's ``grad_sync`` scope, per
chip; in the PowerSGD cell, its encode and decode matmuls.  Kernels under
other labels, such as the flash-attention calls named ``attention``, are
not counted.  None where the step runs no such kernel."""
LAYER = "compression kernels"
UNIT = "ms"
MOVES = "tokens_per_s"


def read(trace: dict, record: dict):
    t = trace["kernel_s"].get("grad_sync", 0.0)
    if t <= 0 or not record["steps"]:
        return None
    return 1e3 * t / record["steps"]
