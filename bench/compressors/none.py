"""No compression: the reference's step passes the mean gradient on as it
is (synchronous SGD)."""


def init(key, cfg: dict, workload: dict, arch):
    return ()


def apply(grads: dict, state, cfg: dict, workload: dict, arch):
    return grads, state
