"""accl-tpu: a TPU-native collective-communication offload framework.

A ground-up re-expression of the Xilinx/ACCL architecture (an MPI-like
collectives library whose control and data planes run on the accelerator)
for TPU: collective schedules compile to single XLA device programs over a
jax mesh (ICI), arithmetic/compression plugins are Pallas/VPU kernels, and
a native C++ multi-rank emulator preserves the reference's CPU-only test
topology. See SURVEY.md for the structural analysis of the reference.
"""

from .constants import (  # noqa: F401
    ACCLError,
    CfgFunc,
    CompressionFlags,
    DataType,
    ErrorCode,
    HostFlags,
    Operation,
    OperationStatus,
    ReduceFunction,
    StreamFlags,
    TAG_ANY,
    Transport,
    TuningParams,
    error_code_to_string,
)
from .errors import (  # noqa: F401
    ACCLValidationError,
    DtypeMismatchError,
    InvalidRootError,
    LintError,
    SequenceReuseError,
    ZeroLengthBufferError,
)
from .arithconfig import ArithConfig, DEFAULT_ARITH_CONFIG  # noqa: F401
from .communicator import Communicator, Rank, generate_ranks  # noqa: F401
from .descriptor import CallOptions, SequenceDescriptor  # noqa: F401
from .sequencer import (  # noqa: F401
    Algorithm,
    Plan,
    Protocol,
    SequencePlan,
    select_algorithm,
)

__version__ = "0.1.0"


def __getattr__(name):
    # Lazy import of the driver facade to keep `import accl_tpu` light.
    if name in ("ACCL", "SequenceRecorder"):
        try:
            from . import accl as _accl_mod
        except ImportError as e:
            raise AttributeError(f"ACCL facade unavailable: {e}") from e
        return getattr(_accl_mod, name)
    raise AttributeError(name)
