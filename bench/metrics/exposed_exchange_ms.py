"""The part of ``exchange_ms`` during which no other operation ran on
that chip, per step: the exchange that nothing hides."""
LAYER = "exchange"
UNIT = "ms"
MOVES = "tokens_per_s"


def read(trace: dict, record: dict):
    if trace["collective_s"] <= 0 or not record["steps"]:
        return None
    return 1e3 * trace["exposed_collective_s"] / record["steps"]
