"""paddle_tpu — a TPU-native deep-learning framework.

A from-scratch rebuild of the capabilities of PaddlePaddle (reference:
/root/reference, circa v0.10/v0.11) designed TPU-first on JAX/XLA:

* The *program-as-data* spine of the reference's Fluid generation
  (reference: paddle/framework/program_desc.h:28, executor.cc:73) is kept as
  the user-facing IR — a ``Program`` of ``Block``s of ``Op``s — but instead of
  a serial per-op C++ interpreter, the whole program is traced into a single
  XLA computation with ``jax.jit`` and compiled once per (program, feed-shape)
  signature.  The MXU sees one fused graph, not 170 kernel launches.
* Autograd does not reimplement per-op grad makers (reference:
  framework/backward.cc:353) — ``append_backward`` marks gradient variables
  and the executor derives them with ``jax.value_and_grad`` over the traced
  forward section.  Every op in the library is therefore differentiable for
  free.
* Distribution replaces the reference's four communication backends (v1
  pserver sockets, Go pserver/master, fluid gRPC send/recv, NCCL — SURVEY.md
  §2.6) with XLA collectives over a ``jax.sharding.Mesh`` (``paddle_tpu.parallel``).
* Variable-length sequences (the reference's LoD, lod_tensor.h:34-83) become
  padded-plus-length tensors with masked sequence ops — static shapes that XLA
  can tile onto the MXU.

Public API intentionally mirrors the reference's fluid Python surface
(python/paddle/v2/fluid/__init__.py): ``layers``, ``optimizer``, ``Executor``,
``Program``, ``default_main_program`` ...
"""

# the phase log's ``process/import`` (core/compile_cache.py PHASE_NAMES):
# from here to the last statement of this file, on time.perf_counter()
import sys as _sys
import time as _time
_IMPORT_T0 = _time.perf_counter()
_JAX_PREIMPORTED = "jax" in _sys.modules    # a benchmark imports jax first

from . import compat
from . import core
from .core import (
    stack_feeds,
    Program,
    Block,
    Operator,
    Variable,
    Parameter,
    default_main_program,
    default_startup_program,
    program_guard,
    pipeline_stage,
    unique_name,
    Executor,
    Scope,
    global_scope,
    scope_guard,
    CPUPlace,
    TPUPlace,
)
from . import ops  # noqa: F401  (registers every op implementation)
from . import layers
from . import nets
from . import initializer
from . import optimizer
from . import regularizer
from . import clip
from . import backward
from .backward import append_backward
from . import evaluator
from . import metrics
from . import io
from .io import save_params, load_params, save_persistables, load_persistables, \
    save_inference_model, load_inference_model
from . import export_model
from .export_model import export_compiled_model, load_compiled_model
from .data_feeder import DataFeeder
from .param_attr import ParamAttr
from . import observability
from . import profiler
from . import parallel
from . import distributed
from . import reader
from . import dataset
from . import lr_decay
from . import net_drawer
from . import flags
from . import trainer
from . import image
from . import utils
from . import api
from . import models
from .trainer import infer
from . import framework  # compat alias namespace
from . import faults
from .faults import EXIT_PREEMPTED, Preempted, RetryPolicy
from . import train_state
from .train_state import TrainState
from . import testing

# NOTE: the version is folded into every compile-cache fingerprint
# (core/compile_cache.environment_key) — bump it whenever compiled-step
# calling conventions change (0.2.0: check_nan_inf variants stopped
# donating state buffers; older persisted executables still alias them)
__version__ = "0.2.0"

__all__ = [
    "Program", "Block", "Operator", "Variable", "Parameter",
    "default_main_program", "default_startup_program", "program_guard",
    "unique_name", "Executor", "Scope", "global_scope", "scope_guard",
    "CPUPlace", "TPUPlace", "layers", "nets", "initializer", "optimizer",
    "regularizer", "clip", "backward", "append_backward", "evaluator",
    "metrics", "io", "save_params", "load_params", "save_persistables",
    "load_persistables", "save_inference_model", "load_inference_model",
    "DataFeeder", "ParamAttr", "observability", "profiler", "parallel",
    "distributed",
    "reader", "dataset", "trainer", "models", "infer", "image", "utils",
    "compat", "stack_feeds",
    "faults", "EXIT_PREEMPTED", "Preempted", "RetryPolicy",
    "train_state", "TrainState", "testing",
]

core.compile_cache.stats().record_phase(
    "process/import", _IMPORT_T0, _time.perf_counter(),
    jax_preimported=_JAX_PREIMPORTED)
