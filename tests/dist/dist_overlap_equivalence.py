"""Overlap-equivalence oracle on a 4-device CPU mesh.

The overlapped schedule (bucket collectives issued between backward
stages, barrier-pinned) and the serial schedule (all collectives after the
full backward) run the SAME per-bucket math — only the issue order
differs.  Training results must therefore be bit-identical, for the raw
`none` baseline and for compressed schemes.  Also checks:

  * non-associative schemes (signsgd) degrade to the serial schedule
    (`effective_schedule`) and are still bit-identical;
  * the classic scan-based step agrees with the segmented step to fp
    tolerance (different XLA programs — unrolled vs scanned — so only
    allclose, not bitwise);
  * the unfused two-dispatch strawman agrees to fp tolerance;
  * the enc-dec (audio) family — two segmented stacks, decoder then
    encoder, under its default ZeRO-1 plan — is bit-identical
    serial-vs-overlapped and fp-agrees with the classic step;
  * ``make_local_mesh`` spans all four devices on the data axis.

(The ZeRO-1 × accum regime matrix has its own oracle:
tests/dist/dist_zero1_accum.py.)
"""
import harness

harness.setup_devices(4)

import dataclasses  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import base  # noqa: E402
from repro.launch.mesh import make_local_mesh  # noqa: E402
from repro.parallel.compat import make_mesh  # noqa: E402
from repro.train import overlap  # noqa: E402
from repro.train import train_step as ts  # noqa: E402

STEPS = 3
METHODS = ["none", "randomk", "signsgd"]


def local_mesh_spans_devices():
    """``--mesh local`` (``make_local_mesh``) puts every visible device on
    the data axis: here the four virtual devices, on a host the chips."""
    mesh = make_local_mesh()
    assert dict(zip(mesh.axis_names, mesh.devices.shape)) == \
        {"data": 4, "model": 1}, mesh
    assert set(mesh.devices.flat) == set(jax.devices())
    print("  make_local_mesh: data=4 over all 4 devices")


def main():
    local_mesh_spans_devices()
    batches = harness.make_batches(STEPS)

    for method in METHODS:
        setup = harness.build_setup(method, zero1=False)
        comp_assoc = (method == "none"
                      or setup.agg_cfg.build().associative)
        eff = overlap.effective_schedule(setup)
        assert eff == ("overlap" if comp_assoc else "serial"), (method, eff)

        s_ser, m_ser, _ = harness.run(
            setup, overlap.make_step(setup, "serial"), batches)
        s_ovl, m_ovl, _ = harness.run(
            setup, overlap.make_step(setup, "overlap"), batches)
        harness.assert_bit_identical(s_ser, s_ovl, m_ser, m_ovl,
                                     f"{method}: serial vs overlapped")
        print(f"  {method}: serial == overlapped bit-identical "
              f"({STEPS} steps, effective={eff})")

    # classic scan-based step vs segmented: same math, different XLA
    # program -> fp-tolerance agreement on the training trajectory
    setup = harness.build_setup("none", zero1=False)
    s_seg, m_seg, _ = harness.run(
        setup, overlap.make_step(setup, "serial"), batches)
    classic = dataclasses.replace(
        setup.arch, plan=dataclasses.replace(setup.arch.plan,
                                             overlap=False))
    setup_c = ts.build(classic, setup.mesh)
    s_cls, m_cls, _ = harness.run(setup_c, ts.make_step(setup_c), batches)
    for a, b in zip(m_seg, m_cls):
        np.testing.assert_allclose(a["loss"], b["loss"], rtol=1e-3,
                                   err_msg="segmented vs classic loss")
    print("  none: segmented vs classic scan step loss agrees (fp tol)")

    # the unfused strawman computes the same training step across two
    # dispatches — fp-tolerance agreement
    s_unf, m_unf, _ = harness.run(setup, overlap.make_unfused_step(setup),
                                  batches)
    for a, b in zip(m_seg, m_unf):
        np.testing.assert_allclose(a["loss"], b["loss"], rtol=1e-4,
                                   err_msg="segmented vs unfused loss")
    print("  none: fused vs unfused strawman loss agrees (fp tol)")

    # enc-dec: two segmented stacks (decoder then encoder) under the
    # arch's default ZeRO-1 plan
    audio_equivalence()


def audio_batches():
    cfg = base.reduced(base.get("seamless-m4t-medium"))
    cfg = dataclasses.replace(cfg, vocab=64, plan=dataclasses.replace(
        cfg.plan, bucket_mb=1, overlap=True))
    key = jax.random.key(1)
    B, S = 8, 32
    out = []
    for i in range(STEPS):
        k = jax.random.fold_in(key, i)
        toks = jax.random.randint(k, (B, S + 1), 0, 64)
        enc = jax.random.normal(jax.random.fold_in(k, 99),
                                (B, S, cfg.d_model))
        out.append({"enc_embeds": enc, "tokens": toks[:, :S],
                    "labels": toks[:, 1:]})
    return cfg, out


def audio_equivalence():
    cfg, batches = audio_batches()
    assert cfg.plan.zero1         # seamless ships ZeRO-1 by default
    mesh = make_mesh((4, 1), ("data", "model"))
    setup = ts.build(cfg, mesh)
    s_ser, m_ser, _ = harness.run(
        setup, overlap.make_step(setup, "serial"), batches)
    s_ovl, m_ovl, _ = harness.run(
        setup, overlap.make_step(setup, "overlap"), batches)
    harness.assert_bit_identical(s_ser, s_ovl, m_ser, m_ovl,
                                 "audio: serial vs overlapped")
    print(f"  audio (enc-dec, zero1): serial == overlapped bit-identical "
          f"({STEPS} steps)")

    classic = dataclasses.replace(
        cfg, plan=dataclasses.replace(cfg.plan, overlap=False))
    setup_c = ts.build(classic, mesh)
    s_cls, m_cls, _ = harness.run(setup_c, ts.make_step(setup_c), batches)
    for a, b in zip(m_ser, m_cls):
        np.testing.assert_allclose(a["loss"], b["loss"], rtol=1e-3,
                                   err_msg="audio segmented vs classic")
    print("  audio: segmented vs classic scan step loss agrees (fp tol)")


if __name__ == "__main__":
    harness.run_main("dist_overlap_equivalence", main)
