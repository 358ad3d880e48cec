"""Mean host-clock wait per step for the next batch from the program's
data pipeline (``bench.input_wait`` span), over the traced window."""
LAYER = "data pipeline"
UNIT = "ms"
MOVES = "tokens_per_s"


def read(trace: dict, record: dict):
    waits = record["input_wait_s"]
    return 1e3 * sum(waits) / len(waits) if waits else None
