"""Share of the traced window in which no operation ran on the device
(averaged over the chips): 100 x (1 - busy / window)."""
LAYER = "device"
UNIT = "%"
MOVES = "tokens_per_s"


def read(trace: dict, record: dict):
    if trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
