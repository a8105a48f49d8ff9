"""Device context API.

TPU-native analog of the reference Context (ref: include/mxnet/base.h:102-115
`Context{dev_type, dev_id}` with kCPU/kGPU/kCPUPinned/kCPUShared). Here a
Context names a jax.Device; `gpu()` is kept as an alias for the accelerator
so reference scripts port unchanged. There is no pinned/shared CPU variant —
PJRT owns host staging buffers.
"""
from __future__ import annotations

import threading
from typing import Optional

import jax

from .base import MXNetError

__all__ = ["Context", "cpu", "gpu", "tpu", "current_context", "num_gpus", "num_tpus"]


class Context:
    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 5: "cpu_shared", 6: "tpu"}
    devstr2type = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "cpu_shared": 5, "tpu": 6}

    _default_ctx = threading.local()

    def __init__(self, device_type: str, device_id: int = 0):
        if isinstance(device_type, Context):
            device_type, device_id = device_type.device_type, device_type.device_id
        if device_type not in self.devstr2type:
            raise ValueError(f"unknown device type {device_type}")
        self.device_type = device_type
        self.device_id = device_id

    # -- identity ----------------------------------------------------------
    @property
    def device_typeid(self) -> int:
        return self.devstr2type[self.device_type]

    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return f"{self.device_type}({self.device_id})"

    __str__ = __repr__

    # -- jax mapping -------------------------------------------------------
    def jax_device(self) -> Optional[jax.Device]:
        """Resolve to a concrete jax.Device.

        'tpu' resolves to local TPU chip ``device_id`` and 'gpu' is its
        documented alias (lets reference scripts using mx.gpu() run on
        TPU); both raise when this process has no TPU or the id is out
        of range, as the reference's mx.gpu() does without a GPU. 'cpu'
        resolves to a host device.
        """
        # LOCAL devices only: under jax.distributed, jax.devices() is the
        # global list and another rank's device is non-addressable here
        if self.device_type.startswith("cpu"):
            try:
                devs = jax.local_devices(backend="cpu")
            except RuntimeError:
                devs = [d for d in jax.local_devices()
                        if d.platform == "cpu"]
                if not devs:
                    return None
            return devs[min(self.device_id, len(devs) - 1)]
        devs = [d for d in jax.local_devices() if d.platform == "tpu"]
        if not 0 <= self.device_id < len(devs):
            raise MXNetError(
                f"{self!r}: this process has {len(devs)} local TPU "
                f"device(s) (jax backend {jax.default_backend()!r}); "
                "there is no fallback to another device")
        return devs[self.device_id]

    def __enter__(self):
        if not hasattr(Context._default_ctx, "stack"):
            Context._default_ctx.stack = []
        Context._default_ctx.stack.append(self)
        return self

    def __exit__(self, *exc):
        Context._default_ctx.stack.pop()

    def empty_cache(self):
        """ref: MXStorageEmptyCache — XLA owns pooling; no-op."""


def cpu(device_id: int = 0) -> Context:
    return Context("cpu", device_id)


def gpu(device_id: int = 0) -> Context:
    """Accelerator context. On TPU machines this is the TPU (alias kept so
    reference scripts using mx.gpu(i) run unchanged)."""
    return Context("gpu", device_id)


def tpu(device_id: int = 0) -> Context:
    return Context("tpu", device_id)


def current_context() -> Context:
    stack = getattr(Context._default_ctx, "stack", None)
    if stack:
        return stack[-1]
    return Context("cpu", 0)


def num_gpus() -> int:
    return len([d for d in jax.devices() if d.platform != "cpu"])


num_tpus = num_gpus
