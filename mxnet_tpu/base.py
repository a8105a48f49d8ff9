"""Core base utilities: errors, registries, dtype handling, env config.

TPU-native re-design of the roles played by dmlc-core in the reference
(ref: 3rdparty/dmlc-core as consumed per SURVEY.md Appendix B): logging,
`dmlc::Parameter` param reflection, `dmlc::GetEnv` env flags, and the
`dmlc::Registry` factory pattern (ref: src/c_api/c_api_error.cc for the
error surface). Here these collapse into small Python-native pieces;
numeric work never passes through this layer (XLA owns it).
"""
from __future__ import annotations

import dataclasses
import logging
import os
from typing import Any, Callable, Dict, Optional, Type

import numpy as onp

__all__ = [
    "MXNetError",
    "Registry",
    "get_env",
    "numeric_types",
    "string_types",
    "data_dir",
]

numeric_types = (float, int, onp.generic)
string_types = (str,)


class MXNetError(RuntimeError):
    """Framework error type (ref: dmlc::Error surfaced via src/c_api/c_api_error.cc)."""


def get_env(name: str, default, dtype: Optional[type] = None):
    """Typed env lookup (ref: dmlc::GetEnv use sites, e.g.
    src/engine/threaded_engine_perdevice.cc:84; docs/faq/env_var.md).

    Delegates to the typed flag registry (mxnet_tpu.config) so runtime
    overrides via config.set_flag are honored everywhere. For names
    registered in the flag registry the registry's type and default are
    canonical; `default`/`dtype` only apply to unregistered names."""
    from . import config as _config
    return _config.get(name, default, dtype=dtype)


def data_dir() -> str:
    return get_env("MXNET_HOME", os.path.join(os.path.expanduser("~"), ".mxnet_tpu"))


class Registry:
    """Name → object registry with alias support.

    One registration mechanism covering what the reference splits across
    NNVM_REGISTER_OP, MXNET_REGISTER_OP_PROPERTY, MXNET_REGISTER_IO_ITER,
    and dmlc::Registry (SURVEY.md Appendix A "Legacy-registered ops").
    """

    _registries: Dict[str, "Registry"] = {}

    def __init__(self, name: str):
        self.name = name
        self._entries: Dict[str, Any] = {}
        Registry._registries[name] = self

    @classmethod
    def get_registry(cls, name: str) -> "Registry":
        if name not in cls._registries:
            Registry(name)
        return cls._registries[name]

    def register(self, name: Optional[str] = None, *aliases: str):
        def _do(obj, key):
            self._entries[key] = obj
            for a in aliases:
                self._entries[a] = obj
            return obj

        if callable(name) and not isinstance(name, str):
            # used as bare decorator
            obj = name
            return _do(obj, getattr(obj, "__name__", str(obj)).lower())

        def deco(obj):
            key = name or getattr(obj, "__name__", str(obj)).lower()
            return _do(obj, key)

        return deco

    def alias(self, existing: str, *names: str):
        for n in names:
            self._entries[n] = self._entries[existing]

    def get(self, name: str):
        if name not in self._entries:
            raise MXNetError(
                f"{self.name} registry has no entry '{name}'. "
                f"Known: {sorted(set(self._entries))[:50]}"
            )
        return self._entries[name]

    def find(self, name: str):
        return self._entries.get(name)

    def __contains__(self, name: str) -> bool:
        return name in self._entries

    def keys(self):
        return self._entries.keys()

    def create(self, name: str, *args, **kwargs):
        return self.get(name)(*args, **kwargs)


def classproperty(fn):
    class _CP:
        def __get__(self, obj, owner):
            return fn(owner)

    return _CP()


# ---------------------------------------------------------------------------
# Parameter reflection (ref: dmlc::Parameter / DMLC_DECLARE_PARAMETER, used by
# every op/iterator param struct, SURVEY.md §5.6). Python dataclasses already
# give declare/parse/doc in one place; this adds kwargs-parsing with type
# coercion so string kwargs (symbol attrs / iterator configs) round-trip.
# ---------------------------------------------------------------------------

def parameter(cls):
    cls = dataclasses.dataclass(cls)

    def from_kwargs(klass, **kwargs):
        fields = {f.name: f for f in dataclasses.fields(klass)}
        clean = {}
        for k, v in kwargs.items():
            if k not in fields:
                raise MXNetError(f"{klass.__name__} got unknown parameter '{k}'")
            ty = fields[k].type
            if isinstance(v, str):
                if ty in ("int", int):
                    v = int(v)
                elif ty in ("float", float):
                    v = float(v)
                elif ty in ("bool", bool):
                    v = v in ("1", "true", "True")
            clean[k] = v
        return klass(**clean)

    cls.from_kwargs = classmethod(from_kwargs)
    return cls


_LOGGER = None


def get_logger(name: str = "mxnet_tpu", level=logging.INFO) -> logging.Logger:
    """Rank-tagged logger (ref: python/mxnet/log.py and kvstore_server.py:47-49)."""
    global _LOGGER
    logger = logging.getLogger(name)
    if _LOGGER is None:
        handler = logging.StreamHandler()
        handler.setFormatter(
            logging.Formatter("%(asctime)s %(levelname)s %(name)s: %(message)s")
        )
        logger.addHandler(handler)
        logger.setLevel(level)
        logger.propagate = False
        _LOGGER = logger
    return logger


def worker_rank(default=0):
    """This process's worker rank: MX_WORKER_ID (tools/launch.py
    local/ssh/sge), else the MPI runtime env (--launcher mpi), else the
    YARN container id (--launcher yarn: CONTAINER_ID ends in a
    sequential suffix; the ApplicationMaster is 000001, workers start
    at 000002), else `default`."""
    import os
    for var in ("MX_WORKER_ID", "OMPI_COMM_WORLD_RANK", "PMI_RANK",
                "PMIX_RANK"):
        if var in os.environ:
            return int(os.environ[var])
    if os.environ.get("MX_WORKER_ID_FROM") == "YARN_CONTAINER_ID"             and "CONTAINER_ID" in os.environ:
        try:
            return max(0, int(os.environ["CONTAINER_ID"]
                              .rsplit("_", 1)[-1]) - 2)
        except ValueError:
            pass
    return default


def initialize_distributed(coordinator_address=None, num_processes=None,
                           process_id=None, **kwargs):
    """Wire this process into a multi-worker jax.distributed job.

    Single implementation behind both the import-time bootstrap
    (mxnet_tpu/__init__.py) and parallel.initialize_distributed (ref role:
    the DMLC_ROLE/DMLC_PS_ROOT_URI wiring of the ps-lite tracker,
    python/mxnet/kvstore_server.py:76 and tools/launch.py:29). Explicit
    arguments win; otherwise the MX_COORDINATOR / MX_NUM_WORKERS /
    MX_WORKER_ID env set by tools/launch.py is used; unset values stay
    None so jax can auto-detect cluster shape (TPU pod runtimes).
    Idempotent; no-op when no coordinator is known."""
    import os
    import jax
    if jax.distributed.is_initialized():
        return
    if coordinator_address is None:
        coordinator_address = os.environ.get("MX_COORDINATOR")
    if coordinator_address is None:
        return
    if num_processes is None and "MX_NUM_WORKERS" in os.environ:
        num_processes = int(os.environ["MX_NUM_WORKERS"])
    if process_id is None:
        # MX_WORKER_ID (local/ssh launcher) or the MPI runtime env
        # (--launcher mpi, where rank is not a per-process export)
        process_id = worker_rank(default=None)
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id, **kwargs)
    # Complete the COLLECTIVE backend bring-up now, while every rank is
    # at the same point (import/bootstrap): under jax.distributed the
    # first backend touch exchanges local topologies across ALL ranks,
    # and deferring it invites a distributed deadlock — e.g. rank 0
    # stuck in lazy backend init waiting for peers' topology while the
    # peers block on rank 0's kvstore server before ever touching jax.
    jax.devices()
