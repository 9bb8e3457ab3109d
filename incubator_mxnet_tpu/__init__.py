"""incubator_mxnet_tpu — a TPU-native deep-learning framework with the
capabilities of Apache MXNet (reference: andrei5055/incubator-mxnet).

Brand-new design, not a port: the compute path is JAX/XLA/Pallas/pjit
(SPMD over `jax.sharding.Mesh`), the API surface is Gluon-shaped so
reference user code moves over with minimal edits.  See SURVEY.md for
the reference analysis this build follows.

    import incubator_mxnet_tpu as mx
    net = mx.gluon.nn.Dense(10)
    net.initialize()
    with mx.autograd.record():
        loss = net(mx.nd.ones((2, 3))).sum()
    loss.backward()
"""
from __future__ import annotations

import time as _time

_T_IMPORT = _time.monotonic()    # the package's import is a phase of a start

__version__ = "0.1.0"

from . import base
from .base import MXNetError
from . import telemetry
from .context import Context, cpu, cpu_pinned, current_context, gpu, num_gpus, num_tpus, tpu
from . import ndarray
from . import ndarray as nd
from .ndarray.ndarray import NDArray
from . import autograd
from . import random
from . import initializer
from .initializer import init  # noqa: F401 (alias namespace)
from . import optimizer
from . import lr_scheduler
from . import metric
from . import gluon
from . import kvstore
from . import kvstore as kv
from . import io
from . import recordio
from . import image
from . import profiler
from . import onnx
from . import operator
from . import library
from . import contrib
from . import amp
from . import parallel
from . import ops
from . import models
from . import runtime
from . import symbol
from . import symbol as sym
from . import callback
from . import test_utils
from . import util
from . import numpy as np  # NumPy-semantics array API (mx.np)
from . import numpy_extension as npx  # DL extensions (mx.npx)

mod = None  # legacy Module API lives in .module
from . import module  # noqa: E402
mod = module
from . import visualization  # noqa: E402
viz = visualization
from . import monitor as _monitor_mod  # noqa: E402
mon = _monitor_mod

__all__ = [
    "nd", "np", "npx", "sym", "symbol", "gluon", "autograd", "optimizer",
    "lr_scheduler", "initializer", "init", "metric", "kvstore", "kv", "io",
    "recordio", "image", "profiler", "amp", "parallel", "ops", "models",
    "runtime", "module", "mod", "random", "callback", "test_utils",
    "visualization", "viz", "mon", "telemetry",
    "Context", "cpu", "gpu", "tpu", "cpu_pinned", "current_context",
    "num_gpus", "num_tpus", "NDArray", "MXNetError",
]

telemetry.profiler.record_setup_span("import", _T_IMPORT, _time.monotonic())
