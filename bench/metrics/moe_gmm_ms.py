"""Device time per step of the drop-free expert layer's grouped matmuls:
the Mosaic calls named ``moe_gmm`` (the gate, up and down products, their
recompute under remat, and the dx and dw products of the backward), per
chip.  None where the step runs no such kernel."""
LAYER = "train step"
UNIT = "ms"
MOVES = "tokens_per_s"


def read(trace: dict, record: dict):
    t = trace["kernel_s"].get("moe_gmm", 0.0)
    if t <= 0 or not record["steps"]:
        return None
    return 1e3 * t / record["steps"]
