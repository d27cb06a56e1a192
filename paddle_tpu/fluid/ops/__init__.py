"""Op lowering library — importing this module registers all op rules.

Role parity: reference ``paddle/fluid/operators/`` (341 registered op types).
Each submodule groups ops like the reference's operator directories.
"""

from . import (  # noqa: F401
    activations,
    autodiff,
    collective,
    control_flow,
    creation,
    detection_ops,
    distributed_ops,
    elementwise,
    embedding_ops,
    linear_attention,
    loss,
    math,
    metrics,
    misc_ops,
    moe_ops,
    nn,
    optimizer_ops,
    quant_ops,
    rnn_ops,
    sequence_ops,
    sparse_attention,
    structured_loss_ops,
    tensor_ops,
)
