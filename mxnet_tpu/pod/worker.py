"""The mxpod drill training worker (one HOST PROCESS).

``python -m mxnet_tpu.pod.worker`` — spawned N times by the subprocess
drill harness (pod/drill.py), ``tools/mxresil.py pod`` and the tier-1
smoke test. Each process:

- bootstraps a :class:`PodContext` from the ``MXPOD_*`` env,
- trains the same seeded regression MLP as the in-process elastic
  drill (identical task -> comparable loss trajectories) through a
  real gluon ``Trainer`` + split-phase ElasticStepFunction over the
  socket-transport exchange,
- evaluates the ``pod.host.<rank>`` fault site at every step boundary
  (``kill9``/``preempt``/``stall`` per MXRESIL_FAULT_PLAN — each
  process carries its OWN plan env, so exactly the scripted host
  dies),
- emits one ``POD {json}`` line per event on stdout (step records,
  final program census, typed-death markers) for the harness to
  parse.

Exit codes: 0 clean / preempted; 43 quarantined by the cross-host
fingerprint vote; 44 coordinator lost beyond the grace budget; 45
evicted or group failed; anything else = unexpected crash.
"""
from __future__ import annotations

import json
import os
import signal
import sys
import time


def _emit(evt: str, **kw):
    kw["evt"] = evt
    print("POD " + json.dumps(kw), flush=True)


def main(argv=None) -> int:
    # CPU backend for local drills unless the harness says otherwise
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.elastic.drill import _make_data
    from mxnet_tpu.elastic.membership import GroupFailed, WorkerEvicted
    from mxnet_tpu.guard.voting import GuardQuarantined
    from mxnet_tpu.pod.context import PodContext
    from mxnet_tpu.pod.group import CoordinatorLost
    from mxnet_tpu.resil import faultplan

    steps = int(os.environ.get("POD_STEPS", "20"))
    step_sleep = float(os.environ.get("POD_STEP_SLEEP", "0"))
    batch = int(os.environ.get("POD_BATCH", "8"))
    lr = float(os.environ.get("POD_LR", "0.05"))
    seed = int(os.environ.get("POD_SEED", "0"))
    in_dim = int(os.environ.get("POD_IN_DIM", "16"))
    hidden = int(os.environ.get("POD_HIDDEN", "32"))
    out_dim = int(os.environ.get("POD_OUT_DIM", "4"))
    join = os.environ.get("MXPOD_JOIN") == "1"

    preempted = {"flag": False}

    def _on_sigterm(signum, frame):
        preempted["flag"] = True

    signal.signal(signal.SIGTERM, _on_sigterm)

    # identical initial weights on every ORIGINAL worker (a joiner's
    # init is irrelevant — it installs the group's live state)
    mx.random.seed(seed)
    onp.random.seed(seed)
    net = gluon.nn.HybridSequential()
    with net.name_scope():
        net.add(gluon.nn.Dense(hidden, activation="relu",
                               flatten=False))
        net.add(gluon.nn.Dense(out_dim, flatten=False))
    net.initialize()
    loss_fn = gluon.loss.L2Loss()
    data = _make_data(seed, in_dim, out_dim)

    # POD_GO_FILE = the warm-standby gate of the drill harness: this
    # process imports and builds EVERYTHING (the slow part of a host
    # bring-up), then holds BEFORE touching the control plane until
    # the harness touches the file — a rejoining host enters the group
    # at the moment the drill scripts, not import-time later. A
    # restarted rank-0 binds the coordinator port (and replays the
    # journal) only here, i.e. only once its predecessor is dead.
    go_file = os.environ.get("POD_GO_FILE")
    if go_file:
        _emit("warmed")
        deadline = time.monotonic() + float(
            os.environ.get("POD_GO_TIMEOUT_S", "120"))
        while not os.path.exists(go_file):
            if time.monotonic() > deadline:
                _emit("go_timeout")
                return 46
            time.sleep(0.02)

    ctx = PodContext(join=join)
    _emit("context", rank=ctx.rank, nprocs=ctx.nprocs, join=join,
          restored=ctx.restored, worker_id=ctx.worker_id)

    fused = None
    session = None
    try:
        kv = ctx.kvstore()
        ctx.form_group(kv)
        trainer = gluon.Trainer(
            net.collect_params(), "sgd", {"learning_rate": lr},
            kvstore=kv, update_on_kvstore=False)
        fused = trainer.fuse_step(net, loss_fn)
        session = kv.session
        start_step = int(session.start_meta.get("step") or 0) \
            if join else 0
        _emit("formed", generation=session.generation,
              world=session.world, start_step=start_step,
              synced_from_group=bool(join and start_step > 0))

        from mxnet_tpu.ndarray.ndarray import array as nd_array
        for step in range(start_step, steps):
            if preempted["flag"]:
                session.leave()
                _emit("preempted", step=step)
                return 0
            t0 = time.perf_counter()
            faultplan.inject(f"pod.host.{ctx.rank}", step=step)
            x, y = data(ctx.rank, step, batch)
            loss = fused.step(nd_array(x), nd_array(y))
            lval = float(onp.mean(loss.asnumpy()))
            _emit("step", step=step, t=time.perf_counter() - t0,
                  loss=lval, world=session.world,
                  gen=session.generation)
            if step_sleep > 0:
                time.sleep(step_sleep)
        # POD_LANDED_FILE: the drill scripted a late entrant — keep
        # the membership boundary ALIVE after the last step (beat,
        # publish join state when leader, absorb bumps) until the
        # harness confirms the entrant landed (it touches the file on
        # the entrant's "formed" event), so a worker racing past the
        # finish line cannot orphan an announced joiner. Bounded by
        # POD_LINGER_S either way.
        landed = os.environ.get("POD_LANDED_FILE")
        if landed:
            deadline = time.monotonic() + float(
                os.environ.get("POD_LINGER_S", "20"))
            while not os.path.exists(landed) and \
                    time.monotonic() < deadline:
                if session.heartbeat(steps):
                    session.rebuild()
                time.sleep(0.02)
        _emit("done", steps=steps, programs=fused.program_counts(),
              generation=session.generation, world=session.world,
              guard_events=list(fused.guard_events),
              final_view=session.view.describe())
        # teardown: the job is over — a coordinator that dies now is
        # uninteresting, so the goodbye gets a SHORT grace instead of
        # the full rejoin budget
        group = session.group
        group.grace_s = min(group.grace_s, 2.0)
        try:
            session.leave()
        except Exception:
            pass
        if ctx.is_coordinator_host:
            # hold the control plane up until the peers said goodbye
            # (their leaves/teardown must not burn a CoordinatorLost
            # grace on a job that ENDED) — bounded, not a barrier
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                try:
                    if ctx._server._ensure_elastic().view(
                            ).world_size == 0:
                        break
                except Exception:
                    break
                time.sleep(0.05)
        return 0
    except GuardQuarantined as e:
        _emit("quarantined", error=str(e)[:200],
              guard_events=list(fused.guard_events) if fused is not None
              else [])
        return 43
    except CoordinatorLost as e:
        _emit("coordinator_lost", error=str(e)[:200])
        return 44
    except (GroupFailed, WorkerEvicted) as e:
        if session is not None:
            # coordinated capture: GroupFailed means the whole pod is
            # coming down — grab every rank's recorder while the
            # control plane still answers
            session.request_pod_dump(f"group-failed-{type(e).__name__}")
        _emit("group_failed", kind=type(e).__name__,
              error=str(e)[:200])
        return 45
    finally:
        try:
            ctx.close()
        except Exception:
            pass


if __name__ == "__main__":
    sys.exit(main())
