"""The latent-attention / shared-expert MoE LM under HYPER-CONNECTIONS
with its multi-token-prediction module loaded (the ``xing4_0`` family:
:mod:`latent_moe` with ``hc_mult`` and ``num_nextn_predict_layers``;
``ops/mhc_ops.py``, ``decoder.hc_sublayer`` / ``draft_turn``): four
residual streams, a Sinkhorn-balanced mixing matrix a sublayer, and a
decode turn of two rows a slot through the absorbed latent kernel.

Registered in ``ZOO_MODELS`` so the lint gate, distribute/pipeline
splits, and the opt pipeline cover the wrapper's two ops and their
gradients; no builder of its own: the programs are :mod:`latent_moe`'s.
"""

from paddle_tpu.models import latent_moe

__all__ = ["StreamsLatentConfig", "latent_moe_streams_train_program"]


class StreamsLatentConfig(latent_moe.LatentMoEConfig):
    """``LatentMoEConfig`` over four residual streams (five Sinkhorn
    rounds a wrapper at toy scale), its MTP module drafting."""
    hc_mult = 4
    hc_sinkhorn_iters = 5
    num_nextn_predict_layers = 1


def latent_moe_streams_train_program(seq_len, hp: StreamsLatentConfig = None):
    """Teacher-forced training forward over one sequence (the main model:
    the MTP module serves, it is not trained here); returns ``(avg_cost,
    feed_names)`` like :func:`latent_moe.latent_moe_train_program`."""
    return latent_moe.latent_moe_train_program(seq_len,
                                               hp or StreamsLatentConfig())
