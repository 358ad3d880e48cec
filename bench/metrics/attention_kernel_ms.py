"""Device time per step of the Pallas flash-attention kernels: the Mosaic
calls named ``attention`` (forward, its recompute under remat, dK/dV and
dQ), per chip.  None where the step runs no such kernel."""
LAYER = "train step"
UNIT = "ms"
MOVES = "tokens_per_s"


def read(trace: dict, record: dict):
    t = trace["kernel_s"].get("attention", 0.0)
    if t <= 0 or not record["steps"]:
        return None
    return 1e3 * t / record["steps"]
