"""The mxpipe drill worker (one HOST PROCESS = one-or-more
pipeline STAGES).

``python -m mxnet_tpu.pipe.worker`` — spawned N times by the
lost-stage drill harness (pipe/drill.py). Each process:

- bootstraps a :class:`~mxnet_tpu.pod.context.PodContext` from the
  ``MXPOD_*`` env (the pipe drill IS a pod: same coordinator, same
  fenced socket transport, same journal),
- builds the seeded pipeline LM and a
  :class:`~mxnet_tpu.pipe.stepfn.PipeStepFunction` over the pod's
  elastic session — stage ownership derives from the membership view,
- trains deterministic seeded batches (every host constructs the SAME
  global batch per step, so a post-kill redo is bit-identical),
- evaluates the ``pod.host.<rank>`` fault site at every step boundary
  (``kill9`` per MXRESIL_FAULT_PLAN — the same site the pod drills
  script, because a lost stage IS a lost host),
- emits one ``PIPE {json}`` line per event: ``context``, ``formed``
  (with the initial stage map), ``step``, ``restage`` (survivors
  re-mapped stages after a bump), ``done`` (program census by kind +
  stage-map history, the drill's re-key-budget evidence).

Exit codes mirror pod/worker.py: 0 clean, 44 coordinator lost, 45
evicted/group failed.
"""
from __future__ import annotations

import json
import os
import sys
import time


def _emit(evt: str, **kw):
    kw["evt"] = evt
    print("PIPE " + json.dumps(kw), flush=True)


def main(argv=None) -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import numpy as onp
    import jax.numpy as jnp

    import mxnet_tpu  # noqa: F401  (jax compat shims)
    from mxnet_tpu.elastic.membership import GroupFailed, WorkerEvicted
    from mxnet_tpu.parallel.pipeline_lm import init_pipeline_lm
    from mxnet_tpu.pipe.stepfn import PipeStepFunction
    from mxnet_tpu.pod.context import PodContext
    from mxnet_tpu.pod.group import CoordinatorLost
    from mxnet_tpu.resil import faultplan

    steps = int(os.environ.get("PIPE_STEPS", "12"))
    step_sleep = float(os.environ.get("PIPE_STEP_SLEEP", "0.02"))
    batch = int(os.environ.get("PIPE_BATCH", "8"))
    seq = int(os.environ.get("PIPE_SEQ", "8"))
    vocab = int(os.environ.get("PIPE_VOCAB", "64"))
    d_model = int(os.environ.get("PIPE_DMODEL", "16"))
    n_layers = int(os.environ.get("PIPE_LAYERS", "6"))
    lr = float(os.environ.get("PIPE_LR", "1e-3"))
    seed = int(os.environ.get("PIPE_SEED", "0"))
    n_stage = int(os.environ.get("PIPE_STAGES", "0"))
    n_micro = int(os.environ.get("PIPE_MICROBATCH", "0"))
    schedule = os.environ.get("PIPE_SCHEDULE") or None

    # identical params on every host (replicated-state model)
    params = init_pipeline_lm(seed, vocab=vocab, d_model=d_model,
                              n_layers=n_layers, n_heads=2,
                              d_head=max(4, d_model // 2), d_ff=32,
                              n_experts=2)

    def make_batch(step: int):
        # seeded per STEP, not per rank: the pipeline consumes ONE
        # global batch at stage 0, and any host must be able to
        # reconstruct it for a post-bump redo
        r = onp.random.RandomState(seed * 100003 + step)
        tok = r.randint(0, vocab, size=(batch, seq)).astype("int32")
        lab = r.randint(0, vocab, size=(batch, seq)).astype("int32")
        return jnp.asarray(tok), jnp.asarray(lab)

    ctx = PodContext()
    _emit("context", rank=ctx.rank, nprocs=ctx.nprocs,
          worker_id=ctx.worker_id)
    sf = None
    session = None
    maps_seen = []

    def on_restage(stage_map, token):
        maps_seen.append({"stage_map": stage_map,
                          "world": list(token)})
        _emit("restage", stage_map={str(k): v for k, v
                                    in stage_map.items()},
              world=list(token), n=len(maps_seen))

    try:
        kv = ctx.kvstore()
        ctx.form_group(kv)
        session = kv.session
        sf = PipeStepFunction(
            params, n_stage=n_stage or None, schedule=schedule,
            n_microbatch=n_micro or None, lr=lr, session=session,
            name=f"pipe-w{ctx.rank}", on_restage=on_restage)
        maps_seen.append({"stage_map": dict(sf.stage_map),
                          "world": list(sf._world_token)})
        _emit("formed", generation=session.generation,
              world=session.world, n_stage=sf.n_stage,
              n_micro=sf.n_micro, schedule=sf.schedule.kind,
              stage_map={str(k): v for k, v in sf.stage_map.items()})

        for step in range(steps):
            t0 = time.perf_counter()
            faultplan.inject(f"pod.host.{ctx.rank}", step=step)
            tok, lab = make_batch(step)
            loss = sf.step(tok, lab)
            _emit("step", step=step, t=time.perf_counter() - t0,
                  loss=loss, world=session.world,
                  gen=session.generation,
                  stages=[s for s, w in sf.stage_map.items()
                          if w == session.worker_id])
            if step_sleep > 0:
                # paced like the pod drill: membership events must be
                # able to land between sub-millisecond CPU steps
                time.sleep(step_sleep)
        _emit("done", steps=steps, programs=sf.program_counts(),
              census=sf.program_census(),
              worlds_seen=sf.worlds_seen(),
              maps_seen=[{"stage_map": {str(k): v for k, v in
                                        m["stage_map"].items()},
                          "world": m["world"]} for m in maps_seen],
              generation=session.generation, world=session.world,
              lint=sf.lint_report())
        group = session.group
        group.grace_s = min(group.grace_s, 2.0)
        try:
            session.leave()
        except Exception:
            pass
        return 0
    except CoordinatorLost as e:
        _emit("coordinator_lost", error=str(e)[:200])
        return 44
    except (GroupFailed, WorkerEvicted) as e:
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
