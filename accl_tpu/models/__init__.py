"""Demo model family: workloads that exercise the framework end to end.

The reference ships example kernels (vadd_put: compute fused with a
collective, kernels/plugins/vadd_put/vadd_put.cpp:25-87) rather than
models. Here the same role at TPU scale: a transformer LM whose tensor-
parallel reductions, sequence-parallel attention and data-parallel
gradient sync all run through the framework's own schedule bodies inside
one compiled training step.
"""

from .transformer import (  # noqa: F401
    FLAGSHIP_BATCH,
    FLAGSHIP_CONFIG,
    FLAGSHIP_SEQ,
    TransformerConfig,
    init_kv_cache,
    init_params,
    make_decode_step,
    make_decode_step_program,
    make_forward,
    make_train_step,
    record_decode_step,
    run_decode_step_eager,
)
from .moe import (  # noqa: F401
    MoEConfig,
    init_moe_params,
    make_moe_forward,
    make_moe_train_step,
)
from .serve import (  # noqa: F401
    DecodeRequest,
    DecodeServer,
    generate,
)
