"""The whole train step's model FLOP utilization over the traced window:
model FLOPs per token (``bench/flops.py``) x tokens per second, over chips
x the chip's bf16 peak (``bench/peaks.json``)."""
LAYER = "train step"
UNIT = "%"
MOVES = "tokens_per_s"


def read(trace: dict, record: dict):
    if not record["steps"]:
        return None
    tps = record["tokens_per_step"] * record["steps"] / record["window_s"]
    peak = record["chips"] * record["peak"]["bf16_flops_per_s"]
    return 100.0 * record["flops_per_token"] * tps / peak
