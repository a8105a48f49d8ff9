"""Operator autotuning.

ref: src/operator/operator_tune.{h,cc} — the reference measures each
op's serial cost at startup to decide per-op OMP parallelization
(`UseOMP`, operator_tune.h:197; modes kAuto/kAlwaysOMP/kNeverOMP/...,
:165, selected by MXNET_USE_OPERATOR_TUNING). XLA already autotunes
*within* a compiled program (tiling, fusion, layout of intermediates),
so the TPU reinterpretation tunes the one thing XLA cannot: the choice
BETWEEN semantically-equal implementations the framework itself offers —
e.g. direct-layout vs transpose-to-NHWC convolution, scan vs unrolled
RNN cells. (Attention is not tuned here: kernel or dense is a rule on
the call's shape, ops/pallas_kernels.flash_attention_available; a
forward-only timing of batch 1 would flip between two close candidates
from run to run.) `autotune` times the candidates on the real device
once per (op, shape/dtype signature), caches the winner in-process and
on disk (MXNET_HOME/op_tune.json), and honors the reference's modes:
  auto   use cached winners, measure on first sight   (kAuto)
  always re-measure every process                     (kAlwaysOMP)
  never  always take the first (default) candidate    (kNeverOMP)
"""
from __future__ import annotations

import json
import os
import threading
import time
from typing import Callable, Dict, Sequence, Tuple

__all__ = ["set_tuning_mode", "tuning_mode", "measure_op_cost",
           "cost_table", "candidate_failures", "autotune", "choose",
           "clear_cache", "cache_path"]

_MODES = ("auto", "always", "never", "instrumented")
_mode = None  # resolved lazily from MXNET_USE_OPERATOR_TUNING
_costs: Dict[str, float] = {}
_failures: Dict[str, str] = {}  # "<name>[<label>]|<key>" -> error text
_choices: Dict[str, int] = {}
_measured_here: set = set()  # keys measured by THIS process
_lock = threading.Lock()
_disk_loaded = False


def _resolve_mode() -> str:
    global _mode
    if _mode is None:
        from .base import get_env
        # the reference flag is multi-valued (0/1/float32/...,
        # operator_tune.h:165): only explicit falsy forms disable
        raw = str(get_env("MXNET_USE_OPERATOR_TUNING", "1")).lower()
        _mode = "never" if raw in ("0", "false", "no", "off") else "auto"
    return _mode


def set_tuning_mode(mode: str):
    """ref: OperatorTuneBase tuning modes (operator_tune.h:165)."""
    m = mode.lower()
    if m not in _MODES:
        raise ValueError(f"unknown tuning mode {mode!r}; one of {_MODES}")
    global _mode
    _mode = m


def tuning_mode() -> str:
    return _resolve_mode()


def cache_path() -> str:
    from .base import data_dir
    return os.path.join(data_dir(), "op_tune.json")


def _load_disk_cache():
    global _disk_loaded
    if _disk_loaded:
        return
    _disk_loaded = True
    try:
        with open(cache_path()) as f:
            _choices.update({k: int(v) for k, v in json.load(f).items()})
    except (OSError, ValueError):
        pass


def _save_disk_cache():
    try:
        # merge-on-write under an inter-process flock: concurrent
        # processes (dist workers on one host) each tune different
        # keys; an unlocked read-merge-replace could still drop a
        # near-simultaneous writer's keys
        import fcntl
        os.makedirs(os.path.dirname(cache_path()), exist_ok=True)
        lockp = cache_path() + ".lock"
        with open(lockp, "w") as lockf:
            fcntl.flock(lockf, fcntl.LOCK_EX)
            merged = {}
            try:
                with open(cache_path()) as f:
                    merged.update({k: int(v)
                                   for k, v in json.load(f).items()})
            except (OSError, ValueError):
                pass
            merged.update(_choices)
            # drop pre-platform-scoping keys (no "|@plat" suffix): they
            # can never be looked up again and would accrete forever
            merged = {k: v for k, v in merged.items() if "|@" in k}
            tmp = cache_path() + f".tmp{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(merged, f, indent=0, sort_keys=True)
            os.replace(tmp, cache_path())
    except OSError:
        pass


def clear_cache():
    global _disk_loaded
    with _lock:
        _choices.clear()
        _disk_loaded = True  # don't resurrect the file we just ignored
        try:
            os.unlink(cache_path())
        except OSError:
            pass


def _time_candidate(fn: Callable, args, kwargs, iters: int) -> float:
    """Median-of-iters wall time, blocking on the result of each call —
    dispatch is asynchronous, so un-synced timing measures the enqueue."""
    import jax
    jax.block_until_ready(fn(*args, **kwargs))  # warmup / compile
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args, **kwargs))
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def _exec_platform(raw) -> str:
    """Platform the candidates would EXECUTE on: taken from the first
    concrete array argument (device-resident truth), else the active
    jax.default_device(...) context (host/numpy args execute there),
    else the process default backend (the jit-trace case)."""
    import jax
    for x in jax.tree.leaves(raw):
        devs = getattr(x, "devices", None)
        if callable(devs):
            try:
                return next(iter(devs())).platform
            except Exception:
                continue
    dd = getattr(jax.config, "jax_default_device", None)
    if dd is not None and hasattr(dd, "platform"):
        return dd.platform
    return jax.default_backend()


def _sig(name: str, args, kwargs) -> str:
    parts = [name]
    for a in args:
        shape = getattr(a, "shape", None)
        if shape is not None:
            parts.append(f"{tuple(shape)}:{getattr(a, 'dtype', '?')}")
        else:
            parts.append(repr(a)[:32])
    for k in sorted(kwargs):
        parts.append(f"{k}={repr(kwargs[k])[:32]}")
    return "|".join(map(str, parts))


def choose(name: str, candidates: Sequence[Tuple[str, Callable]],
           *args, key: str = None, iters: int = 3, **kwargs):
    """Pick the fastest of `candidates` for these arguments and return
    the winning (label, fn) WITHOUT running it for the caller.

    candidates: [(label, fn), ...] — all semantically equivalent; the
    first is the default. The winner index is cached per key (default:
    the arg shape/dtype signature; pass `key=` to coarsen, e.g. drop
    the batch dim so an eager warm-up forward tunes for the jitted
    batch too) in-process and in MXNET_HOME/op_tune.json (ref role:
    the measured-cost table of operator_tune.cc, reused across
    processes instead of re-measured at every startup).

    Under a jit trace the candidates cannot be timed (args are
    tracers); the cached winner is served, else the default. The eager
    warm-up pass frameworks run to resolve deferred shapes is what
    populates the cache."""
    # deterministic override: MXNET_OPTUNE_CHOICE_<NAME>=<label> pins a
    # candidate by its label (e.g. MXNET_OPTUNE_CHOICE_ATTENTION=dense),
    # trumping both the measurement and the cache; resolved through
    # get_env so config.set_flag() overrides work like any other flag
    from .base import get_env
    forced = get_env(f"MXNET_OPTUNE_CHOICE_{name.upper()}", "")
    if forced:
        for cand in candidates:
            if cand[0] == forced:
                return cand
        raise ValueError(
            f"MXNET_OPTUNE_CHOICE_{name.upper()}={forced!r} does not "
            f"match any candidate {[c[0] for c in candidates]}")
    mode = _resolve_mode()
    if mode == "never" or len(candidates) == 1:
        return candidates[0]
    raw = [getattr(a, "_data", a) for a in args]
    key = key or _sig(name, raw, kwargs)
    # scope the cache by EXECUTION platform: an eager warm-up pinned to
    # the host (jax.default_device(cpu)) must not cache a CPU-measured
    # winner that a TPU trace then serves (observed: the flash-vs-dense
    # choice measured on CPU picking dense for the chip). Concrete
    # arrays name their platform; tracers fall back to the process
    # default backend.
    key = f"{key}|@{_exec_platform(raw)}"
    with _lock:
        _load_disk_cache()
        idx = _choices.get(key)
    cached = candidates[idx] if idx is not None and \
        0 <= idx < len(candidates) else None
    import jax
    if any(isinstance(x, jax.core.Tracer) for x in jax.tree.leaves(raw)):
        if cached is None:
            from .base import get_logger
            get_logger("mxnet_tpu.operator_tune").debug(
                "autotune: no cached winner for %s under a trace; "
                "using the default '%s' (run one eager forward to "
                "measure)", key, candidates[0][0])
        return cached or candidates[0]
    if cached is not None and (mode != "always" or key in _measured_here):
        # 'always' = re-measure once per PROCESS (kAlwaysOMP re-tunes at
        # startup, not per invocation); in-process winners are reused
        return cached
    best_i, best_t = 0, float("inf")
    for i, (label, fn) in enumerate(candidates):
        try:
            t = _time_candidate(fn, raw, kwargs, iters)
        except Exception as e:
            # a candidate may not support this config — but a kernel
            # the compiler refuses must not silently become the other
            # path: say so, and keep the error where callers can see it
            err = f"{type(e).__name__}: {e}"
            _failures[f"{name}[{label}]|{key}"] = err
            from .base import get_logger
            get_logger("mxnet_tpu.operator_tune").warning(
                "autotune: candidate '%s' of %s failed and is skipped: "
                "%s", label, key, err[:2000])
            continue
        _costs[f"{name}[{label}]|{key}"] = t
        if t < best_t:
            best_i, best_t = i, t
    if best_t < float("inf"):
        # only cache a MEASURED winner — if every candidate failed
        # (transient device error), fall back to the default this time
        # and leave the key untuned so a healthy process re-measures
        with _lock:
            _choices[key] = best_i
            _measured_here.add(key)
            _save_disk_cache()
    return candidates[best_i]


def autotune(name: str, candidates: Sequence[Tuple[str, Callable]],
             *args, key: str = None, iters: int = 5, **kwargs):
    """choose() then run the winner — on the same unwrapped arrays the
    timing saw, so a candidate can't pass measurement yet fail
    execution on a framework wrapper type."""
    _, fn = choose(name, candidates, *args, key=key, iters=iters, **kwargs)
    raw = [getattr(a, "_data", a) for a in args]
    return fn(*raw, **kwargs)


def measure_op_cost(name: str, fn: Callable, *args, iters: int = 10,
                    **kwargs) -> float:
    """Measure an op's steady-state wall time (the analog of the startup
    micro-benchmarks in operator_tune.cc) and record it in the table."""
    cost = _time_candidate(fn, args, kwargs, iters)
    _costs[name] = cost
    return cost


def cost_table() -> Dict[str, float]:
    return dict(_costs)


def candidate_failures() -> Dict[str, str]:
    """Candidates that raised while being measured, keyed like
    :func:`cost_table` (``"<name>[<label>]|<key>"``), with the error."""
    return dict(_failures)
