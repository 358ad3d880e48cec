"""The Moonlight (DeepSeek-V3 block) configuration at a tiny size on the CPU,
in float32: the program's loss and gradients equal
``bench/models/deepseek_v3.py``'s, the shares of the expert layer add up to
the uncut layer, the overlapped step's buckets follow the reference's
order over two stacks, and a tiny cell reads correct while a fault planted
in the expert layer does not."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference, spec, weights
from bench.tests.conftest import BENCH, add_cell, copy_bench

#: a Moonlight-shaped model small enough for the CPU: 1 dense + 2 expert
#: layers, 8 experts of which 4 are held, top-3
TINY_MOE = dict(name="tiny-moe", num_hidden_layers=3, hidden_size=128,
                num_attention_heads=4, num_key_value_heads=4,
                qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
                kv_lora_rank=64, intermediate_size=256,
                moe_intermediate_size=64, router_experts=8,
                n_routed_experts=4, first_held=0, num_experts_per_tok=3,
                n_shared_experts=2, vocab_size=256, vocab=256,
                init_std=0.05)
#: between what a sound tiny run reads on the CPU (loss 3.4e-4, grad
#: 0.0016, change 0.0011) and what the planted faults read (the routed
#: scale left out: grad 0.54; the shared experts left out: grad 1.0).  No
#: ``grad_err``: with 48 slots an expert, a token whose top-3 flips under
#: bfloat16 moves an expert's gradient by a few percent (a sound run reads
#: 0.074)
TINY_MOE_LIMITS = {"loss_gap": 1e-3, "grad_gap": 0.02, "delta_gap": 0.03}


def tiny_cfg(**kw) -> dict:
    with open(os.path.join(BENCH, "configs", "moonlight-16b-a3b.json")) as f:
        return dict(json.load(f), **{**TINY_MOE, **kw})


def _arch():
    return spec.Spec().arch({"arch": "deepseek_v3"})


def _program(cfg: dict, plan: dict):
    from repro.configs.base import ArchConfig, ParallelPlan
    return ArchConfig(plan=ParallelPlan(**plan),
                      **_arch().arch_config(cfg))


def _tree(arch_cfg, flat: dict):
    """The program's parameter tree holding ``flat`` (leaves by path)."""
    from repro.models import Model, ShardCtx
    abstract, _ = Model(arch_cfg).abstract_init(ShardCtx())
    paths, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    names = ["/".join(str(getattr(k, "key", k)) for k in p)
             for p, _ in paths]
    assert sorted(names) == sorted(flat)
    return jax.tree_util.tree_unflatten(treedef, [flat[n] for n in names])


def _batch(cfg, b=2, s=64, seed=3):
    from bench import data
    return data.batch(cfg["vocab"], s, b, seed, 0, "markov", 0.15)


def test_program_loss_and_gradients_equal_the_reference():
    """Model.loss in float32 (compute dtype too) against ``loss_sum``, on
    the seed's weights: the loss and every leaf's gradient."""
    from repro.models import Model, ShardCtx
    arch = _arch()
    cfg = tiny_cfg()
    arch_cfg = _program(cfg, {"dp_mode": "ddp", "remat": "full"})
    key = weights.seed_key(2**33 + 7)
    w = weights.make(key, cfg, jnp.float32, arch)
    bt = _batch(cfg)
    ctx = ShardCtx(compute_dtype=jnp.float32)
    model = Model(arch_cfg)
    es = reference._einsum_fn("highest")

    def prog(flat):
        loss, n, _, _ = model.loss(_tree(arch_cfg, flat), bt, ctx)
        return loss

    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(prog)(w)
        lr, gr = jax.value_and_grad(
            lambda p: arch.loss_sum(p, bt["tokens"], bt["labels"], cfg,
                                    es))(w)
    np.testing.assert_allclose(lp, lr, rtol=1e-5)
    for p in w:
        np.testing.assert_allclose(gp[p], gr[p], rtol=2e-4,
                                   atol=2e-4 * float(jnp.max(jnp.abs(gr[p]))),
                                   err_msg=p)


def test_shares_add_up_to_the_uncut_layer():
    """Two disjoint shares of the experts (0-3 and 4-7) through the
    program's drop-free layer, without their shared experts, plus the
    shared experts once, equal the reference's layer holding all 8."""
    from repro.models import ShardCtx, moe
    arch = _arch()
    whole = tiny_cfg(n_routed_experts=8)
    ww = weights.make(weights.seed_key(11), whole, jnp.float32, arch)
    layer = {n[len("moe_blocks/"):]: v[0] for n, v in ww.items()
             if n.startswith("moe_blocks/moe/")}
    h = jax.random.normal(jax.random.key(5), (2, 48, 128), jnp.float32)
    ctx = ShardCtx(compute_dtype=jnp.float32)
    es = reference._einsum_fn("highest")
    out = 0.0
    for first in (0, 4):
        cfg = tiny_cfg(first_held=first, n_shared_experts=0)
        params = {"router": layer["moe/router"],
                  "experts": {k: layer[f"moe/experts/{k}"][first:first + 4]
                              for k in ("gate", "up", "down")}}
        with jax.default_matmul_precision("highest"):
            y, stats = moe.dropfree_apply(params, h, ctx,
                                          _program(cfg, {}), tm=8)
        assert int(stats["dropped_slots"]) == 0
        out = out + y
    with jax.default_matmul_precision("highest"):
        shared = arch._swiglu(es, h.reshape(-1, 128),
                              layer["moe/shared/gate/w"],
                              layer["moe/shared/up/w"],
                              layer["moe/shared/down/w"])
        whole_ref = arch.expert_layer(whole, es, h.reshape(-1, 128), layer)
    np.testing.assert_allclose(out.reshape(-1, 128) + shared, whole_ref,
                               rtol=1e-4, atol=1e-4)


def test_bucket_order_of_two_stacks_equals_the_reference():
    """The overlapped step's leaf order (the MoE stack's layers last
    first, then the dense stack's, then the rest) and bucket boundaries
    equal ``reference.buckets``."""
    from bench import program
    arch = _arch()
    cfg = tiny_cfg()
    with open(os.path.join(BENCH, "workloads",
                           "moonlight-16b-a3b.syncsgd.1chip.json")) as f:
        w = json.load(f)
    w["plan"]["bucket_mb"] = 0.05
    prog = program.Program(cfg, w, arch)
    labels = [lab for lab, _ in prog._labels()]
    ref = [f"{p}#{l}" if l is not None else p
           for p, l, _ in reference.ordered_leaves(cfg, arch)]
    assert labels == ref
    from repro.train import overlap
    ov = overlap.build_layout(prog.setup)
    ref_buckets = reference.buckets(cfg, w, arch)
    assert ov.layout.n_buckets == len(ref_buckets)
    assert [ov.layout.bucket_leaves(b)[1] - ov.layout.bucket_leaves(b)[0]
            for b in range(ov.layout.n_buckets)] == \
        [len(b) for b in ref_buckets]
    assert [s.key for s in ov.stacks] == list(arch.STACKS)


@pytest.fixture(scope="module")
def moe_root(tmp_path_factory):
    """A benchmark tree with ``tiny-moe.syncsgd``: the Moonlight cell's
    plan on a tiny Moonlight-shaped model, one device."""
    root = copy_bench(str(tmp_path_factory.mktemp("moe_root")))
    with open(os.path.join(root, "bench", "configs", "tiny-moe.json"),
              "w") as f:
        json.dump(tiny_cfg(), f)
    with open(os.path.join(BENCH, "workloads",
                           "moonlight-16b-a3b.syncsgd.1chip.json")) as f:
        w = json.load(f)
    w["plan"]["bucket_mb"] = 0.05
    w.update(config="tiny-moe", chips=1, seq=64, seqs_per_chip=2,
             limits=TINY_MOE_LIMITS)
    add_cell(root, "tiny-moe.syncsgd", w)
    return root


def _drive(root, script, *args, cache):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=cache)
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "tests", script), root,
         "tiny-moe.syncsgd", *args], capture_output=True, text=True,
        env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_tiny_moonlight_cell_is_correct(moe_root, tmp_path):
    res = _drive(moe_root, "drive.py", "none", "1", cache=str(tmp_path))
    assert res["correct"] is True, res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0


@pytest.mark.parametrize("fault", ["no_routed_scale", "no_shared"])
def test_planted_expert_fault_is_not_correct(moe_root, tmp_path, fault):
    res = _drive(moe_root, "drive_moe.py", fault, cache=str(tmp_path))
    assert res["correct"] is False, res["compared"]
