"""deepspeed_tpu — a TPU-native large-scale training framework.

Re-implements the capabilities of DeepSpeed (reference:
``deepspeed/__init__.py``) with a JAX/XLA/Pallas architecture designed for
TPU hardware: one SPMD device mesh, GSPMD shardings in place of NCCL
process groups, a single jitted train step in place of imperative
forward/backward/step, and Pallas kernels in place of CUDA extensions.

Public entrypoints mirror the reference:

- :func:`initialize` — build a :class:`~deepspeed_tpu.engine.TrainingEngine`
  from a model + DeepSpeed-style JSON config (ref: deepspeed/__init__.py
  ``initialize``).
- :func:`init_distributed` — multi-host bring-up over
  ``jax.distributed`` (ref: deepspeed/comm/comm.py ``init_distributed``).
- :func:`init_inference` — build an inference engine
  (ref: deepspeed/inference/engine.py).
"""

import time as _time

_T_IMPORT = _time.perf_counter()

__version__ = "0.1.0"

from deepspeed_tpu import mesh
from deepspeed_tpu.config import Config
from deepspeed_tpu.topology import MeshSpec, default_mesh
from deepspeed_tpu.engine import TrainingEngine, TrainState, initialize
from deepspeed_tpu.comm import init_distributed
from deepspeed_tpu import comm
from deepspeed_tpu import ops
from deepspeed_tpu import zero
from deepspeed_tpu import lr_schedules
from deepspeed_tpu import telemetry
from deepspeed_tpu import request_trace


def init_inference(*args, **kwargs):
    """Build an InferenceEngine (ref: deepspeed/inference/engine.py)."""
    from deepspeed_tpu.inference.engine import init_inference as _ii

    return _ii(*args, **kwargs)


def init_hybrid_engine(engine, model_cfg, **kw):
    """Build a train+generate :class:`~deepspeed_tpu.hybrid.HybridEngine`
    for RLHF loops (ref: deepspeed/runtime/hybrid_engine.py)."""
    from deepspeed_tpu.hybrid import llama_hybrid_engine

    return llama_hybrid_engine(engine, model_cfg, **kw)


def add_config_arguments(parser):
    """Add ``--deepspeed``-style CLI args (ref: deepspeed/__init__.py)."""
    group = parser.add_argument_group("DeepSpeed-TPU", "configuration")
    group.add_argument(
        "--deepspeed_config", default=None, type=str,
        help="Path to the framework JSON config file.",
    )
    group.add_argument(
        "--local_rank", default=0, type=int,
        help="Accepted for launcher compatibility; ranks come from JAX.",
    )
    return parser

# what this file's imports cost (the gauge ``package_import_seconds``)
IMPORT_SECONDS = _time.perf_counter() - _T_IMPORT
