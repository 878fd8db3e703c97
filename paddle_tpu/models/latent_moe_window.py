"""The latent-attention / shared-expert MoE LM with WINDOW LAYERS of
latent attention beside a full layer under its own indexer, a head-wise
output gate on both kinds and the low-rank latents rescaled behind their
norms (the ``dots3_note`` family: :mod:`latent_moe` with ``layer_types``;
``ops/mla_ops.py``'s ``latent_window_attention`` / ``head_gate``): the
first layer attends the 4 rows its indexer keeps, the two behind it the 8
rows of their band.

Registered in ``ZOO_MODELS`` so the lint gate, distribute/pipeline
splits, and the opt pipeline cover the window form, the gate and their
gradients; no builder of its own: the programs are :mod:`latent_moe`'s.
"""

from paddle_tpu.models import latent_moe

__all__ = ["WindowLatentConfig", "latent_moe_window_train_program"]


class WindowLatentConfig(latent_moe.LatentMoEConfig):
    """``LatentMoEConfig`` with a full layer under an indexer of its own
    and two sliding layers (a window of 8 rows, their own ranks, heads
    and rotary base), gated head-wise, the latents rescaled."""
    layer_types = ("full_attention", "sliding_attention",
                   "sliding_attention")
    sliding_window_size = 8
    ring = 8
    swa_num_attention_heads = 2
    swa_q_lora_rank = 40
    swa_kv_lora_rank = 48
    swa_qk_nope_head_dim = 24
    swa_rope_theta = 50000.0
    attention_gate_type = "headwise"
    swa_attention_gate_type = "headwise"
    apply_mla_qkv_lora_rescale = True
    index_topk = 4


def latent_moe_window_train_program(seq_len, hp: WindowLatentConfig = None):
    """Teacher-forced training forward over one sequence; returns
    ``(avg_cost, feed_names)`` like
    :func:`latent_moe.latent_moe_train_program`."""
    return latent_moe.latent_moe_train_program(seq_len,
                                               hp or WindowLatentConfig())
