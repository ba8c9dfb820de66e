"""Op library: importing this package registers every lowering.

The analog of the reference's paddle/operators/ (342 files, ~170 ops —
SURVEY §2.2), with each op implemented as a JAX lowering rather than paired
CPU/CUDA kernels.  Grad ops do not exist: jax.vjp differentiates lowerings.
"""

from ..core.registry import register_op, registered_ops

from . import math_ops        # noqa: F401
from . import activation_ops  # noqa: F401
from . import tensor_ops      # noqa: F401
from . import nn_ops          # noqa: F401
from . import loss_ops        # noqa: F401
from . import metric_ops      # noqa: F401
from . import optimizer_ops   # noqa: F401
from . import sequence_ops    # noqa: F401
from . import control_flow_ops  # noqa: F401
from . import embedding_ops   # noqa: F401
from . import io_ops          # noqa: F401
from . import detection_ops   # noqa: F401
from . import crf_ops         # noqa: F401
from . import generation_ops  # noqa: F401
from . import pallas_kernels  # noqa: F401
from . import moe_ops         # noqa: F401
from . import ssd_ops         # noqa: F401


@register_op("backward")
def _backward_stub(ctx, ins, attrs):
    raise RuntimeError(
        "the `backward` pseudo-op must appear at the top level of the global "
        "block; it is lowered specially by the Executor "
        "(core/executor.py interpret_block_with_backward)")
