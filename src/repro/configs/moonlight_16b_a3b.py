"""moonlight-16b-a3b  [moe] — DeepSeek-V3 block: latent attention (MLA), one
dense layer, then 26 layers of 64 sigmoid-routed experts (top-6, 2 shared).

[hf:moonshotai/Moonlight-16B-A3B config.json; arXiv:2502.16982; block as
in DeepSeek-V3, arXiv:2412.19437]

Routing is ``noaux_tc`` with one group: experts are chosen by sigmoid score
plus ``e_score_correction_bias`` (a buffer that stays zero here: its
balancing update is not in ``config.json``), weighted by the chosen scores
renormalised to sum 1 and scaled by ``routed_scaling_factor``.  The
drop-free expert layer routes every token-slot; a chip holds ``n_held`` of
the experts (all of them at this, the published, size).
"""
from repro.configs.base import (ArchConfig, MLAConfig, MoEConfig,
                                ParallelPlan, register)

CONFIG = register(ArchConfig(
    name="moonlight-16b-a3b",
    family="moe",
    n_layers=27,
    first_k_dense=1,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    head_dim=128,
    d_ff=11264,
    vocab=163840,
    rope="rope",
    rope_theta=50000.0,
    norm_eps=1e-5,
    mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128),
    moe=MoEConfig(n_experts=64, top_k=6, n_shared=2, d_expert=1408,
                  routed_scale=2.446, shared_gate=False, drop_free=True),
    plan=ParallelPlan(dp_mode="ddp", zero1=True, optimizer="adamw",
                      remat="full", overlap=True),
))
