"""mxnet_tpu: a TPU-native deep-learning framework.

Brand-new framework with the capabilities of Apache MXNet (the reference,
see SURVEY.md), re-designed for TPU: jax/XLA is the compute substrate
(no dependency engine, no manual memory planner — SURVEY.md §1 "TPU
translation at a glance"), Pallas for hot kernels, pjit/shard_map over
device meshes for parallelism, collectives over ICI/DCN for distribution.

Public surface mirrors the reference Python frontend (mx.nd, mx.autograd,
mx.gluon, mx.sym, mx.mod, mx.optimizer, mx.metric, mx.io, mx.kv, ...).
"""
__version__ = "0.1.0"

import os as _os

# Escape hatch for EXTERNAL helper processes that must never open the
# accelerator (embedding hosts, cluster sidecars — a chip belongs to one
# process): with MXTPU_FORCE_CPU_BACKEND=1 in the environment, the jax
# platform list is pinned to cpu BEFORE any import below could
# initialize a backend. In-repo helpers don't need it (package import
# is backend-free since the RNG key went lazy; spawn DataLoader workers
# pin the platform in _worker_entry), but the hatch is kept and tested
# (tests/test_aux_runtime.py) for embedders.
if _os.environ.get("MXTPU_FORCE_CPU_BACKEND") == "1":
    _os.environ["JAX_PLATFORMS"] = "cpu"
    import jax as _jax_cpu

    try:
        _jax_cpu.config.update("jax_platforms", "cpu")
    except Exception:
        pass

# Large-tensor support (ref: the INT64_TENSOR_SIZE build flag +
# MXNET_USE_INT64_TENSOR_SIZE, docs/faq/env_var.md; tests/nightly/
# test_large_array.py): int64 element indexing needs jax x64 mode,
# which must be set before the first jax import. Opt-in, like the
# reference's off-by-default build flag — x64 also widens python-float
# weak types, so it is not the default.
if _os.environ.get("MXNET_USE_INT64_TENSOR_SIZE", "0").lower() in (
        "1", "true", "yes", "on"):
    import jax as _jax
    _jax.config.update("jax_enable_x64", True)


# Wire this process into a multi-worker job before anything touches the
# XLA backend, when launched by tools/launch.py (ref role: the DMLC_ROLE
# bootstrap that runs on `import mxnet`, python/mxnet/kvstore_server.py:76).
from .base import initialize_distributed as _init_dist

_init_dist()


def _maybe_install_signal_handler():
    """Crash backtraces for hard faults (ref: src/initialize.cc:62,226 —
    the SIGSEGV/SIGABRT backtrace handler behind MXNET_USE_SIGNAL_HANDLER).
    faulthandler is the CPython-native equivalent; on by default like the
    reference's release builds, disabled with MXNET_USE_SIGNAL_HANDLER=0."""
    from . import config as _config
    if _config.get("MXNET_USE_SIGNAL_HANDLER"):
        import faulthandler
        try:
            faulthandler.enable()
        except Exception:  # non-main thread / closed stderr
            pass


_maybe_install_signal_handler()
from . import config  # noqa: F401,E402  (typed MXNET_* flag registry)

from .base import MXNetError  # noqa: F401
from .context import Context, cpu, gpu, tpu, current_context, num_gpus  # noqa: F401

from . import ndarray  # noqa: F401
from . import ndarray as nd  # noqa: F401
from . import numpy as np  # noqa: F401
from . import numpy_extension as npx  # noqa: F401
from .ndarray.ndarray import NDArray  # noqa: F401

from . import autograd  # noqa: F401
from . import random  # noqa: F401
from . import rnn  # noqa: F401
from . import engine  # noqa: F401
from . import operator  # noqa: F401
from . import amp  # noqa: F401
from . import contrib  # noqa: F401

from . import initializer  # noqa: F401
from . import initializer as init  # noqa: F401  (ref: __init__.py:55)
from . import optimizer  # noqa: F401
from .optimizer import Optimizer  # noqa: F401
from . import lr_scheduler  # noqa: F401
from . import metric  # noqa: F401
from . import callback  # noqa: F401

from . import symbol  # noqa: F401
from . import symbol as sym  # noqa: F401
from .symbol.symbol import Symbol  # noqa: F401
from .executor import Executor  # noqa: F401

from . import io  # noqa: F401
from . import recordio  # noqa: F401
from . import gluon  # noqa: F401
from . import module  # noqa: F401
from . import module as mod  # noqa: F401
from . import kvstore  # noqa: F401
from . import kvstore as kv  # noqa: F401
from . import model  # noqa: F401
from .model import save_checkpoint, load_checkpoint  # noqa: F401
from . import monitor  # noqa: F401
from .monitor import Monitor  # noqa: F401
from . import profiler  # noqa: F401
from . import telemetry  # noqa: F401  (op tracing, recompile/memory accounting, metrics)
from . import step  # noqa: F401  (fused whole-train-step compiler)

# persistent XLA compilation cache (MXNET_COMPILE_CACHE_DIR, yielding
# to JAX_COMPILATION_CACHE_DIR): point jax at the on-disk cache before
# any jit runs so the fused train step's warmup survives process
# restarts (docs/performance.md)
step.maybe_enable_compile_cache()
from . import shard  # noqa: F401  (GSPMD sharded training over a named mesh)
from . import serve  # noqa: F401  (dynamic-batching inference serving)
from . import serve2  # noqa: F401  (routed continuous-batching serving, paged KV-cache)
from . import resil  # noqa: F401  (fault injection, retry policies, preemption guard, watchdogs)
from . import pod  # noqa: F401  (multi-host process-group runtime: bootstrap, host-loss recovery)
from . import rtc  # noqa: F401
from . import subgraph  # noqa: F401
from . import executor_manager  # noqa: F401
from . import operator_tune  # noqa: F401
from .model import FeedForward  # noqa: F401
from . import runtime  # noqa: F401
from . import checkpoint  # noqa: F401
from . import tensor_inspector  # noqa: F401
from . import name  # noqa: F401
from . import attribute  # noqa: F401
from .attribute import AttrScope  # noqa: F401
from . import libinfo  # noqa: F401
from . import log  # noqa: F401
from . import library  # noqa: F401
from . import test_utils  # noqa: F401
from . import image  # noqa: F401
from . import image as img  # noqa: F401
from . import registry  # noqa: F401
from . import symbol_doc  # noqa: F401
from . import ndarray_doc  # noqa: F401
from . import notebook  # noqa: F401
from . import torch  # noqa: F401  (gated Torch7-bridge surface)
from . import misc  # noqa: F401  (legacy scheduler shims)
from . import util  # noqa: F401
from . import visualization  # noqa: F401
from . import visualization as viz  # noqa: F401

from .util import is_np_array, set_np, use_np  # noqa: F401


def cpu_pinned(device_id: int = 0) -> Context:
    return Context("cpu", device_id)
