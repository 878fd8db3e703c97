"""Operations and bytes computed from shapes.  These are what the
ALGORITHM needs, not what a compiler emitted: recomputation does not
count, and a kernel's padding does not either.  Every function takes
plain numbers (a configuration dict's fields), never a program object.

Cross-checked in ``benchmark/tests`` against
``paddle_tpu.models.transformer.train_flops_per_token`` (same count).
"""

from __future__ import annotations


# -- Transformer (encoder-decoder, Vaswani et al. 2017) ---------------------

def transformer_matmul_params(cfg):
    """Parameters that take part in matrix multiplications: per encoder
    layer Q,K,V,O (4 d^2) + FFN (2 d d_ff); per decoder layer self and
    cross attention (8 d^2) + FFN; the output projection (d x V).  The
    input embeddings are gathers and are left out."""
    d, dff = cfg["d_model"], cfg["d_inner_hid"]
    per_enc = 4 * d * d + 2 * d * dff
    per_dec = 8 * d * d + 2 * d * dff
    return cfg["n_layer"] * (per_enc + per_dec) + d * cfg["trg_vocab_size"]


def transformer_attention_flops_per_token(cfg, seq, backward=True):
    """QK^T and AV of the three attention modules per layer pair
    (encoder self, decoder self, cross): 2*S*d each per token forward
    (4*S*d a module), and twice that again backward.  Causal masking is
    NOT discounted (the composed path computes the full square; the
    convention of the program's own count)."""
    modules = 3 * cfg["n_layer"]
    fwd = 4 * seq * cfg["d_model"] * modules
    return fwd * (3 if backward else 1)


def transformer_train_flops_per_token(cfg, seq):
    """6 FLOPs per matmul parameter per token (2 forward, 4 backward)
    plus attention."""
    return (6 * transformer_matmul_params(cfg)
            + transformer_attention_flops_per_token(cfg, seq))


def flash_attention_train_flops_per_step(cfg, batch, seq):
    """FLOPs the attention kernels' work needs in one training step at
    ``batch`` x ``seq`` (all three modules, forward + backward), the
    numerator of the flash kernels' roofline share."""
    return batch * seq * transformer_attention_flops_per_token(cfg, seq)


# -- decoder-only LM serving (gen_lm at OPT widths) ------------------------

def genlm_weight_bytes(cfg, bytes_per_param=4):
    """Bytes of weights one decode step has to read: every layer's
    Q,K,V,O and FFN matrices and biases, the layer norms, and the output
    head.  Of the token embedding only the looked-up rows are read."""
    d, dff, v = cfg["hidden_size"], cfg["ffn_dim"], cfg["vocab_size"]
    per_layer = 4 * d * d + 2 * d * dff + dff + d + 4 * d
    return (cfg["num_hidden_layers"] * per_layer + d * v) * bytes_per_param


def paged_attention_bytes_per_step(cfg, live_rows, bytes_per_elem=4):
    """Bytes of K and V the paged decode kernel has to read in one step:
    ``live_rows`` = the sum over live slots of their prefix lengths."""
    return (2 * cfg["num_hidden_layers"] * live_rows * cfg["hidden_size"]
            * bytes_per_elem)
