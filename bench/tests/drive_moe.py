"""Drives a tiny benchmark run (``bench/tests/drive.py``) with a fault
planted in the program's drop-free expert layer:

    python bench/tests/drive_moe.py <root> <cell> <fault>

``<fault>`` is ``no_routed_scale`` (the routed weights not scaled by
``routed_scale``) or ``no_shared`` (the shared experts left out).
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))


def main() -> None:
    root, cell, fault = sys.argv[1:4]
    import dataclasses

    from repro.models import moe
    if fault == "no_routed_scale":
        route = moe.route

        def unscaled(logits, mc, bias):
            return route(logits, dataclasses.replace(mc, routed_scale=1.0),
                         bias)
        moe.route = unscaled
    elif fault == "no_shared":
        apply = moe.dropfree_apply

        def no_shared(params, x, ctx, cfg, tm=moe.TILE_ROWS):
            cfg = dataclasses.replace(
                cfg, moe=dataclasses.replace(cfg.moe, n_shared=0))
            return apply(params, x, ctx, cfg, tm)
        moe.dropfree_apply = no_shared
    else:
        raise SystemExit(f"unknown fault {fault!r}")
    from bench.tests import drive
    sys.argv = [sys.argv[0], root, cell, "none"]
    drive.main()


if __name__ == "__main__":
    main()
