"""Gluon DataLoader.

ref: python/mxnet/gluon/data/dataloader.py — multi-worker loading. The
reference forks worker processes that share NDArrays through
cpu_shared_storage + ForkingPickler (dataloader.py:27-71). Here workers
are SPAWNED (forking a JAX-initialized parent is unsafe — the runtime
is multithreaded) with the dataset shipped pre-pickled, and finished
batches travel back through POSIX shared memory
(multiprocessing.shared_memory — the cpu_shared storage role): the
worker batchifies into numpy, copies into a shm segment, and the parent
re-wraps without a queue-pickle of the bulk data. The device transfer
(jax.device_put) happens exactly once, in the parent.

Workers run numpy-only code (datasets/transforms should return numpy) —
each child forces the CPU jax backend before the dataset unpickles, so
a worker can never open (or hang on) the accelerator. `thread_pool=True`
selects the in-process thread pool instead (useful when __getitem__
already releases the GIL).
"""
from __future__ import annotations

import concurrent.futures
import multiprocessing as mp
from multiprocessing import shared_memory
from typing import Optional

import numpy as onp

from ...ndarray.ndarray import NDArray, array
from .sampler import BatchSampler, RandomSampler, SequentialSampler

__all__ = ["DataLoader", "default_batchify_fn", "default_mp_batchify_fn"]


def default_batchify_fn(data):
    """ref: dataloader.py default_batchify_fn."""
    if isinstance(data[0], NDArray):
        from ...ndarray.ndarray import stack
        return stack(data)
    if isinstance(data[0], tuple):
        data = zip(*data)
        return [default_batchify_fn(i) for i in data]
    data = onp.asarray(data)
    return array(data)


def default_mp_batchify_fn(data):
    """Worker-process batchify: numpy in, numpy out — no NDArray/XLA in
    the forked child (ref: dataloader.py default_mp_batchify_fn, which
    targets shared-memory ndarrays for the same reason)."""
    if isinstance(data[0], tuple):
        data = zip(*data)
        return [default_mp_batchify_fn(i) for i in data]
    return onp.asarray(data)


# ---------------------------------------------------------------------------
# shared-memory transport (the cpu_shared_storage + ForkingPickler role)
# ---------------------------------------------------------------------------

def _shm_encode(obj, segments):
    """Replace numpy leaves with shm descriptors; collect segments."""
    if isinstance(obj, onp.ndarray):
        seg = shared_memory.SharedMemory(create=True, size=max(1, obj.nbytes))
        flat = onp.ndarray(obj.shape, dtype=obj.dtype, buffer=seg.buf)
        flat[...] = obj
        segments.append(seg)
        return ("__shm__", seg.name, obj.shape, str(obj.dtype))
    if isinstance(obj, (list, tuple)):
        return type(obj)(_shm_encode(o, segments) for o in obj)
    return obj


def _shm_decode(obj, opened):
    if isinstance(obj, tuple) and len(obj) == 4 and obj[0] == "__shm__":
        _, name, shape, dtype = obj
        seg = shared_memory.SharedMemory(name=name)
        opened.append(seg)
        arr = onp.ndarray(shape, dtype=onp.dtype(dtype),
                          buffer=seg.buf).copy()
        return array(arr)
    if isinstance(obj, (list, tuple)):
        return [_shm_decode(o, opened) for o in obj] \
            if isinstance(obj, list) else \
            tuple(_shm_decode(o, opened) for o in obj)
    return obj


def _worker_entry(dataset_bytes, batchify_bytes, task_q, res_q):
    """Spawn-context child entry. The payloads arrive PICKLED so nothing
    jax-backed materializes before this body forces the CPU backend —
    a worker must never open the accelerator (a chip belongs to one
    process, and the parent holds it). Spawn replaces the
    previous fork context: forking a JAX-initialized parent is
    documented-unsafe (os.fork + multithreaded runtime). Like torch's
    spawn-mode DataLoader, user SCRIPTS must guard DataLoader
    construction with `if __name__ == "__main__":` (the child re-imports
    the main module at bootstrap)."""
    import jax
    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass
    import cloudpickle
    dataset = cloudpickle.loads(dataset_bytes)
    batchify_fn = cloudpickle.loads(batchify_bytes)
    # startup handshake: tells the parent this worker is fully
    # operational, so the (long) spawn+import boot window is not
    # charged against the per-batch timeout
    res_q.put(("__ready__", None, None, None))
    _worker_loop(dataset, batchify_fn, task_q, res_q)


def _worker_loop(dataset, batchify_fn, task_q, res_q):
    """Runs in the worker child: pull (seq, indices), batchify, ship via
    shared memory (ref: dataloader.py worker_loop)."""
    # MXNET_MP_WORKER_NTHREADS caps per-worker decode threads
    # (ref: env_var.md:60 / MXNET_MP_OPENCV_NUM_THREADS)
    try:
        from ...base import get_env
        import cv2
        cv2.setNumThreads(int(get_env("MXNET_MP_WORKER_NTHREADS", 4)))
    except Exception:
        pass
    warned_ndarray = [False]

    def _to_np(x):
        if isinstance(x, NDArray):
            if not warned_ndarray[0]:
                warned_ndarray[0] = True
                import warnings
                warnings.warn(
                    "DataLoader worker received NDArray items from the "
                    "dataset; worker-side XLA arrays live on the "
                    "worker's CPU backend — return numpy from "
                    "__getitem__ for zero-copy shm handoff")
            return x.asnumpy()
        return x

    while True:
        task = task_q.get()
        if task is None:
            return
        epoch, seq, indices = task
        try:
            items = [dataset[i] for i in indices]
            items = [_to_np(i) if not isinstance(i, tuple)
                     else tuple(_to_np(x) for x in i) for i in items]
            batch = batchify_fn(items)
            segments = []
            payload = _shm_encode(batch, segments)
            res_q.put((epoch, seq, payload, None))
            for seg in segments:  # parent owns them now
                seg.close()
                # ownership moved to the parent (which unlinks); without
                # this the child's resource tracker double-counts them
                try:
                    from multiprocessing import resource_tracker
                    resource_tracker.unregister(seg._name, "shared_memory")
                except Exception:
                    pass
        except Exception as e:  # surface the error at the parent
            res_q.put((epoch, seq, None, f"{type(e).__name__}: {e}"))


class DataLoader:
    def __init__(self, dataset, batch_size=None, shuffle=False, sampler=None,
                 last_batch=None, batch_sampler=None, batchify_fn=None,
                 num_workers=0, pin_memory=False, pin_device_id=0,
                 prefetch=None, thread_pool=False, timeout=120):
        self._dataset = dataset
        self._pin_memory = pin_memory
        self._timeout = timeout

        if batch_sampler is None:
            if batch_size is None:
                raise ValueError("batch_size must be specified unless "
                                 "batch_sampler is specified")
            if sampler is None:
                if shuffle:
                    sampler = RandomSampler(len(dataset))
                else:
                    sampler = SequentialSampler(len(dataset))
            elif shuffle:
                raise ValueError("shuffle must not be specified if sampler "
                                 "is specified")
            batch_sampler = BatchSampler(
                sampler, batch_size, last_batch or "keep")
        elif batch_size is not None or shuffle or sampler is not None or \
                last_batch is not None:
            raise ValueError("batch_size, shuffle, sampler and last_batch "
                             "must not be specified if batch_sampler is "
                             "specified.")
        self._batch_sampler = batch_sampler
        self._num_workers = max(0, num_workers)
        self._prefetch = max(0, int(prefetch) if prefetch is not None
                             else 2 * self._num_workers)
        self._thread_pool = thread_pool
        self._pool = None
        self._workers = []
        self._task_q = self._res_q = None
        self._epoch = 0
        if self._num_workers > 0 and thread_pool:
            self._batchify_fn = batchify_fn or default_batchify_fn
            self._pool = concurrent.futures.ThreadPoolExecutor(
                max_workers=self._num_workers)
        elif self._num_workers > 0:
            # real worker processes (ref: dataloader.py:27-71) — SPAWNED
            # (forking a JAX-initialized parent is unsafe: the runtime
            # is multithreaded), results via shared memory. Dataset and
            # batchify_fn ship pre-pickled so the child can force its
            # CPU backend before anything jax-backed unpickles.
            # cloudpickle, not pickle: datasets/batchify fns defined in
            # local scope (or as lambdas) must keep working under the
            # spawn context the way they did under fork
            import cloudpickle
            self._batchify_fn = batchify_fn or default_mp_batchify_fn
            # spawn, not fork: fork would clone the JAX-initialized
            # (multithreaded) parent — documented-unsafe. Spawn requires
            # the torch-style `if __name__ == "__main__"` guard in user
            # scripts; a missing guard is detected and reported below.
            ctx = mp.get_context("spawn")
            self._task_q = ctx.Queue()
            self._res_q = ctx.Queue()
            dataset_bytes = cloudpickle.dumps(dataset)
            batchify_bytes = cloudpickle.dumps(self._batchify_fn)
            # _worker_entry forces the CPU backend before anything
            # jax-backed unpickles; importing mxnet_tpu itself is
            # backend-free (lazy RNG key), so no env mutation is needed
            # — a global os.environ dance here would race concurrent
            # spawns in other threads
            for _ in range(self._num_workers):
                w = ctx.Process(target=_worker_entry,
                                args=(dataset_bytes, batchify_bytes,
                                      self._task_q, self._res_q),
                                daemon=True)
                w.start()
                self._workers.append(w)
            self._pending_ready = self._num_workers
        else:
            self._batchify_fn = batchify_fn or default_batchify_fn

    def _load_batch(self, indices):
        return self._batchify_fn([self._dataset[i] for i in indices])

    def __iter__(self):
        if self._workers:
            yield from self._mp_iter()
            return
        if self._pool is None:
            for batch_idx in self._batch_sampler:
                yield self._load_batch(batch_idx)
            return
        # thread pool: keep `prefetch` batches in flight
        sampler_iter = iter(self._batch_sampler)
        futures = []
        try:
            for _ in range(max(1, self._prefetch)):
                futures.append(self._pool.submit(self._load_batch,
                                                 next(sampler_iter)))
        except StopIteration:
            pass
        while futures:
            fut = futures.pop(0)
            try:
                futures.append(self._pool.submit(self._load_batch,
                                                 next(sampler_iter)))
            except StopIteration:
                pass
            yield fut.result(timeout=self._timeout)

    @staticmethod
    def _discard_payload(payload):
        """Free shm segments of a result that will never be consumed
        (stale epoch after an abandoned iteration)."""
        opened = []
        try:
            _shm_decode(payload, opened)
        except Exception:
            pass
        for seg in opened:
            seg.close()
            try:
                seg.unlink()
            except FileNotFoundError:
                pass

    def _mp_iter(self):
        # epoch tag: results of an abandoned/failed earlier iteration
        # still in res_q must not be served as this epoch's batches
        self._epoch += 1
        epoch = self._epoch
        sampler_iter = iter(self._batch_sampler)
        sent = 0
        received = 0
        buffered = {}
        for _ in range(max(1, self._prefetch)):
            try:
                self._task_q.put((epoch, sent, next(sampler_iter)))
                sent += 1
            except StopIteration:
                break
        try:
            while received < sent:
                while received not in buffered:
                    import queue as _queue
                    import time as _time
                    # poll in short slices so dead workers surface
                    # immediately instead of after the full timeout;
                    # worker BOOT (spawn + fresh interpreter + imports)
                    # gets its own generous window, charged only while
                    # workers are alive-but-not-ready
                    booting = self._pending_ready > 0
                    deadline = _time.monotonic() + (
                        max(self._timeout, 600) if booting
                        else self._timeout)
                    while True:
                        try:
                            e, seq, payload, err = self._res_q.get(
                                timeout=min(
                                    5.0, max(0.1, deadline
                                             - _time.monotonic())))
                            break
                        except _queue.Empty:
                            dead = [w.pid for w in self._workers
                                    if not w.is_alive()]
                            if dead and self._pending_ready > 0:
                                raise RuntimeError(
                                    "DataLoader worker process(es) "
                                    f"{dead} died during startup — if "
                                    "this is a script, DataLoader with "
                                    "num_workers>0 must be created "
                                    "under the `if __name__ == "
                                    "'__main__':` guard (spawn start "
                                    "method re-imports the main module)")
                            if dead:
                                # mid-epoch death: the task it held can
                                # never complete — fail NOW, not after
                                # the full timeout
                                raise RuntimeError(
                                    f"DataLoader worker process(es) "
                                    f"{dead} died mid-epoch (killed/"
                                    "OOM?); in-flight batches are lost")
                            if _time.monotonic() >= deadline:
                                raise RuntimeError(
                                    "DataLoader timed out after "
                                    f"{self._timeout}s")
                            continue
                    if e == "__ready__":
                        self._pending_ready -= 1
                        continue
                    if e != epoch:  # stale result, abandoned epoch
                        if payload is not None:
                            self._discard_payload(payload)
                        continue
                    buffered[seq] = (payload, err)
                payload, err = buffered.pop(received)
                received += 1
                try:
                    self._task_q.put((epoch, sent, next(sampler_iter)))
                    sent += 1
                except StopIteration:
                    pass
                if err is not None:
                    raise RuntimeError(f"DataLoader worker failed: {err}")
                opened = []
                try:
                    batch = _shm_decode(payload, opened)
                finally:
                    for seg in opened:
                        seg.close()
                        try:
                            seg.unlink()
                        except FileNotFoundError:
                            pass
                yield batch
        finally:
            # free shm of out-of-order results that will never be served
            # (worker error / abandoned generator)
            for payload, _ in buffered.values():
                if payload is not None:
                    self._discard_payload(payload)

    def __len__(self):
        return len(self._batch_sampler)

    def _shutdown(self):
        if self._pool is not None:
            self._pool.shutdown(wait=False)
            self._pool = None
        if self._workers:
            for _ in self._workers:
                try:
                    self._task_q.put(None)
                except Exception:
                    pass
            for w in self._workers:
                w.join(timeout=2)
                if w.is_alive():
                    w.terminate()
            self._workers = []
            # free any undelivered results' shm segments
            try:
                while True:
                    _, _, payload, _ = self._res_q.get_nowait()
                    if payload is not None:
                        self._discard_payload(payload)
            except Exception:
                pass

    def __del__(self):
        try:
            self._shutdown()
        except Exception:
            pass
