"""Device time per step of the Pallas kernels (Mosaic custom calls) the
step runs: in the PowerSGD cell, its encode and decode matmuls."""
LAYER = "compression kernels"
UNIT = "ms"
MOVES = "tokens_per_s"


def read(trace: dict, record: dict):
    t = sum(trace["kernel_s"].values())
    if t <= 0 or not record["steps"]:
        return None
    return 1e3 * t / record["steps"]
