"""The latent-attention / shared-expert MoE LM under LEARNED SPARSE
ATTENTION (the ``glm_moe_dsa`` family: :mod:`latent_moe` with
``index_topk``; ``ops/dsa_ops.py``): the first layer's indexer scores the
rows before a query row and keeps the ``index_topk`` best, and all three
layers attend under that selection.

Registered in ``ZOO_MODELS`` so the lint gate, distribute/pipeline
splits, and the opt pipeline cover the sparse ops.  The selection carries
no gradient, so this teacher-forced view trains everything but the
indexer's own weights (the published recipe trains those against the
dense attention's distribution, a loss of its own).
"""

from paddle_tpu.models import latent_moe

__all__ = ["SparseLatentConfig", "latent_moe_sparse_train_program"]


class SparseLatentConfig(latent_moe.LatentMoEConfig):
    """``LatentMoEConfig`` with an indexer in its first layer whose
    selection of 4 rows the two layers behind it share."""
    index_topk = 4
    indexer_types = ("full", "shared", "shared")


def latent_moe_sparse_train_program(seq_len, hp: SparseLatentConfig = None):
    """Teacher-forced training forward over one sequence; returns
    ``(avg_cost, feed_names)`` like
    :func:`latent_moe.latent_moe_train_program`."""
    return latent_moe.latent_moe_train_program(seq_len,
                                               hp or SparseLatentConfig())
