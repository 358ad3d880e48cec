"""Device time of the collective operations per step (the gradient
exchange and the ZeRO-1 parameter all-gather), averaged over the chips."""
LAYER = "exchange"
UNIT = "ms"
MOVES = "tokens_per_s"


def read(trace: dict, record: dict):
    if trace["collective_s"] <= 0 or not record["steps"]:
        return None
    return 1e3 * trace["collective_s"] / record["steps"]
