"""Op registry + all op lowerings.

Importing this package registers every op type (the analogue of the
reference's ``USE_OP`` generated pybind stubs,
``paddle/fluid/operators/CMakeLists.txt:6-8``).
"""

from paddle_tpu.ops import registry  # noqa: F401
from paddle_tpu.ops import (  # noqa: F401
    csp_ops,
    detection_ops,
    reader_ops,
    sparse_ops,
    math_ops,
    tensor_ops,
    activation_ops,
    nn_ops,
    loss_ops,
    optimizer_ops,
    logic_ops,
    metric_ops,
    io_ops,
    persist_ops,
    control_flow_ops,
    sequence_ops,
    rnn_ops,
    attention_ops,
    crf_ops,
    ctc_ops,
    beam_search_ops,
    fused_ops,
    ssm_ops,
    kda_ops,
    moe_ops,
    mla_ops,
    dsa_ops,
    block_ops,
    window_ops,
    spec_ops,
    diff_attention_ops,
    mhc_ops,
)
