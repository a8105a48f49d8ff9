"""Deterministic in-process elastic drills.

``run_elastic_drill`` stands up N worker threads sharing one
:class:`ElasticCoordinator` — each with its own model replica, gluon
``Trainer`` over an :class:`ElasticKVStore`, and split-phase
:class:`ElasticStepFunction` — trains a small regression MLP in
lockstep, kills (or preempts) one worker at a scripted step via the
``MXRESIL_FAULT_PLAN`` thread-mode actions, optionally rejoins a fresh
worker through the group state-sync, and reports:

- per-phase (full group / shrunk / rejoined) median step rates and the
  aggregate-throughput ratios;
- recovery time (kill → first completed post-rebuild step) and the
  number of steps the survivors had in flight when fenced;
- the re-key budget: per surviving worker, exactly ONE new update
  program per NEW world size, grad programs untouched, and zero
  further compiles in the steady state after a rebuild;
- final mean loss (for the loss-trajectory contract against an
  uninterrupted baseline, ``MXELASTIC_LOSS_TOL``).

Faults are scripted, never timed: ``elastic.worker.<rank>:K=kill``
fires at step K of that worker exactly. Shared by
``tools/mxresil.py elastic`` and the tier-1 integration test.
"""
from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional

import numpy as onp

from ..base import get_logger
from .coordinator import ElasticCoordinator
from .membership import GroupFailed, MembershipTracker, WorkerEvicted

__all__ = ["run_elastic_drill", "run_pod_drill"]


def run_pod_drill(*args, **kwargs):
    """The subprocess N-HOST harness: same drill contract, but every
    worker is a real host process over the socket-transport exchange
    (SIGKILL'able, coordinator-restartable). Implementation lives in
    :mod:`mxnet_tpu.pod.drill`; re-exported here because the two
    harnesses are the two rungs of one ladder — threads prove the
    protocol, processes prove the pod."""
    from ..pod.drill import run_pod_drill as _impl
    return _impl(*args, **kwargs)

_log = get_logger("mxnet_tpu.elastic")


def _make_data(seed: int, in_dim: int, out_dim: int):
    """The fixed regression task: y = tanh(x W) with a seeded W —
    every worker/batch draws from it deterministically."""
    rng = onp.random.RandomState(seed)
    w = rng.uniform(-1, 1, size=(in_dim, out_dim)).astype("float32")

    def batch(worker_seed: int, step: int, batch_size: int):
        r = onp.random.RandomState(
            (seed * 1000003 + worker_seed * 9973 + step) % (2 ** 31))
        x = r.uniform(-1, 1, size=(batch_size, in_dim)).astype("float32")
        y = onp.tanh(x @ w).astype("float32")
        return x, y

    return batch


class _DrillWorker:
    def __init__(self, rank: int, group, cfg: dict, join: bool = False):
        import mxnet_tpu as mx
        from mxnet_tpu import gluon
        from .kvstore import ElasticKVStore

        self.rank = rank
        self.wid = f"w{rank}"
        self.cfg = cfg
        self.join = join
        self.steps: List[Dict] = []  # {step, t, loss, world, gen}
        self.death: Optional[str] = None
        self.error: Optional[BaseException] = None
        self.recovered_at: Optional[float] = None
        self.rekeys: List[Dict] = []
        self.thread: Optional[threading.Thread] = None

        # identical initial weights on every ORIGINAL worker: re-seed
        # the global stream before each net's initialize (a rejoiner's
        # init is irrelevant — it installs the group's live state)
        mx.random.seed(cfg["seed"])
        onp.random.seed(cfg["seed"])
        net = gluon.nn.HybridSequential()
        with net.name_scope():
            net.add(gluon.nn.Dense(cfg["hidden"], activation="relu",
                                   flatten=False))
            net.add(gluon.nn.Dense(cfg["out_dim"], flatten=False))
        net.initialize()
        self.net = net
        self.loss_fn = gluon.loss.L2Loss()
        if join:
            # announce → admitted with the group's live state →
            # rebuild barrier; blocks until a leader's step boundary
            # (the join path starts its heartbeat pump itself)
            self.kv = ElasticKVStore(group=group, worker_id=self.wid,
                                     join=True)
        else:
            self.kv = ElasticKVStore(group=group, worker_id=self.wid)
            # beat from the moment of registration: trainer/step
            # construction and the first compile must not read as death
            self.kv.session.start_heartbeat_pump(
                cfg["hb_interval"] / 2.0)
        self.trainer = gluon.Trainer(
            net.collect_params(), "sgd",
            {"learning_rate": cfg["lr"]}, kvstore=self.kv,
            update_on_kvstore=False)
        self.fused = self.trainer.fuse_step(net, self.loss_fn)
        self.session = self.kv.session
        self.start_step = int(self.session.start_meta.get("step") or 0) \
            if join else 0

    def programs(self):
        return self.fused.program_counts()

    def worlds(self):
        """Distinct world sizes this worker completed steps at — the
        re-key budget is exactly one UPDATE program per entry (and one
        grad program total)."""
        return sorted({r["world"] for r in self.steps})

    def run(self):
        from ..guard.voting import GuardQuarantined
        from ..resil import faultplan
        from ..resil.faultplan import WorkerKilled, WorkerPreempted
        from mxnet_tpu.ndarray.ndarray import array as nd_array
        cfg = self.cfg
        data = cfg["data"]
        self.session.start_heartbeat_pump(cfg["hb_interval"] / 2.0)
        try:
            for step in range(self.start_step, cfg["steps"]):
                t0 = time.perf_counter()
                try:
                    faultplan.inject(f"elastic.worker.{self.rank}",
                                     step=step, thread_mode=True)
                    x, y = data(self.rank, step, cfg["batch"])
                    loss = self.fused.step(nd_array(x), nd_array(y))
                    lval = float(onp.mean(loss.asnumpy()))
                except WorkerKilled:
                    # hard death: no leave, no pump — survivors must
                    # detect this through missed heartbeats alone
                    self.death = "killed"
                    self.session.stop_heartbeat_pump()
                    return
                except WorkerPreempted:
                    self.death = "preempted"
                    self.session.leave()
                    self.session.stop_heartbeat_pump()
                    return
                except GuardQuarantined:
                    # the fingerprint vote named this worker and the
                    # corruption reproduced under re-execution: the
                    # step already left the group (the membership bump
                    # survivors fence on) — just stop driving it
                    self.death = "quarantined"
                    self.session.stop_heartbeat_pump()
                    return
                self.steps.append({
                    "step": step, "t": time.perf_counter() - t0,
                    "loss": lval, "world": self.session.world,
                    "gen": self.session.generation,
                    "wall": time.perf_counter()})
            self.session.leave()  # clean exit: don't burn the budget
        except (GroupFailed, WorkerEvicted) as e:
            self.death = type(e).__name__
            self.error = e
        except BaseException as e:  # pragma: no cover - surfaced up
            self.error = e
        finally:
            self.session.stop_heartbeat_pump()

    def start(self):
        self.thread = threading.Thread(
            target=self.run, name=f"mxelastic-drill-{self.wid}",
            daemon=True)
        self.thread.start()
        return self


def _median(vals):
    vals = sorted(vals)
    return vals[len(vals) // 2] if vals else None


def _phase_rate(workers, lo_gen, hi_gen, batch):
    """Aggregate samples/sec for steps whose generation g satisfies
    lo_gen <= g < hi_gen (None = unbounded), from the median per-step
    time x contributing world size."""
    times, worlds = [], []
    for w in workers:
        for rec in w.steps:
            if (lo_gen is None or rec["gen"] >= lo_gen) and \
                    (hi_gen is None or rec["gen"] < hi_gen):
                times.append(rec["t"])
                worlds.append(rec["world"])
    med = _median(times)
    if med is None or med <= 0:
        return None, 0
    world = max(worlds) if worlds else 0
    return world * batch / med, len(times)


def run_elastic_drill(n_workers: int = 3, steps: int = 40,
                      kill_step: Optional[int] = None,
                      kill_rank: int = 1, action: str = "kill",
                      rejoin: bool = False,
                      rejoin_after_steps: int = 6, batch: int = 8,
                      in_dim: int = 16, hidden: int = 32,
                      out_dim: int = 4, lr: float = 0.05,
                      seed: int = 0, hb_interval: float = 0.1,
                      miss_limit: int = 3, min_world: int = 1,
                      timeout_s: float = 120.0,
                      fault_plan: Optional[str] = None,
                      guard: bool = False) -> Dict[str, object]:
    """One scripted drill (see module docstring); returns the report
    dict. ``kill_step=None`` runs the uninterrupted baseline.

    ``action="sdc"`` (or ``"sdc:scale"``) is the mxguard
    silent-corruption drill: instead of dying, the selected worker's
    gradients are corrupted by one element from ``kill_step`` onward
    (the ``guard.sdc.<worker_id>`` site, persistent ``:K+`` selector), the
    fingerprint vote catches it pre-averaging, and the worker is
    QUARANTINED through the same membership-bump machinery a kill
    exercises — the report gains a ``guard`` section (detection step,
    attribution, per-worker verdicts). MXGUARD taps are forced on for
    every worker of an sdc drill (or via ``guard=True`` with any
    action); ``fault_plan`` overrides the drill-owned plan entirely
    (custom-selector drills, e.g. a transient ``@K`` sdc clause)."""
    from mxnet_tpu import config
    from ..resil import faultplan

    sdc = action.startswith("sdc")
    saved_plan = config.get("MXRESIL_FAULT_PLAN")
    config.set_flag("MXELASTIC_HEARTBEAT_S", hb_interval)
    config.set_flag("MXELASTIC_MISS_LIMIT", miss_limit)
    config.set_flag("MXELASTIC_MIN_WORLD", min_world)
    if sdc or guard:
        mode = action.split(":", 1)[1] if ":" in action else "bitflip"
        config.set_flag("MXGUARD", True)
    if fault_plan is not None:
        config.set_flag("MXRESIL_FAULT_PLAN", fault_plan)
    elif kill_step is not None:
        config.set_flag(
            "MXRESIL_FAULT_PLAN",
            f"guard.sdc.w{kill_rank}:{kill_step}+=sdc:{mode}" if sdc
            else f"elastic.worker.{kill_rank}:{kill_step}={action}")
    else:
        config.set_flag("MXRESIL_FAULT_PLAN", "")
    faultplan.reset()
    try:
        return _run(n_workers, steps, kill_step, kill_rank, action,
                    rejoin, rejoin_after_steps, batch, in_dim, hidden,
                    out_dim, lr, seed, hb_interval, miss_limit,
                    min_world, timeout_s)
    finally:
        # restore a caller's programmatic plan override; with none,
        # drop ours so the env/default value resolves again (the
        # restore-then-unset form would discard the caller's override
        # — same bug class fixed in guard/replay.py)
        if saved_plan:
            config.set_flag("MXRESIL_FAULT_PLAN", saved_plan)
        else:
            config.unset_flag("MXRESIL_FAULT_PLAN")
        faultplan.reset()
        for f in ("MXELASTIC_HEARTBEAT_S", "MXELASTIC_MISS_LIMIT",
                  "MXELASTIC_MIN_WORLD"):
            config.unset_flag(f)
        if sdc or guard:
            config.unset_flag("MXGUARD")


def _run(n_workers, steps, kill_step, kill_rank, action, rejoin,
         rejoin_after_steps, batch, in_dim, hidden, out_dim, lr, seed,
         hb_interval, miss_limit, min_world, timeout_s):
    tracker = MembershipTracker(heartbeat_interval_s=hb_interval,
                                miss_limit=miss_limit,
                                min_world=min_world)
    co = ElasticCoordinator(tracker=tracker, timeout_s=timeout_s,
                            tick_s=min(0.02, hb_interval / 4.0))
    cfg = dict(steps=steps, batch=batch, lr=lr, seed=seed,
               hidden=hidden, out_dim=out_dim, hb_interval=hb_interval,
               data=_make_data(seed, in_dim, out_dim))

    t_start = time.perf_counter()
    workers = [_DrillWorker(r, co, cfg) for r in range(n_workers)]
    # one agreed starting view before anyone steps (registration churn
    # is not what this drill measures)
    for w in workers:
        w.session.refresh()
    gen0 = co.view().generation

    for w in workers:
        w.start()

    report: Dict[str, object] = {
        "workers": n_workers, "steps": steps, "kill_step": kill_step,
        "action": action if kill_step is not None else None,
        "rejoin": bool(rejoin and kill_step is not None),
        "batch": batch, "gen0": gen0}
    joiner = None
    t_kill = None
    gen_after_kill = None

    if kill_step is not None:
        # wait for the membership verdict (scripted step, measured
        # recovery — the only timing here is the detection itself)
        deadline = time.time() + timeout_s

        def _check_errors(ws):
            for w in ws:
                if w.error is not None:
                    raise w.error

        while co.view().generation == gen0:
            if time.time() > deadline:
                raise RuntimeError("drill: kill was never detected")
            _check_errors(workers)
            time.sleep(hb_interval / 4.0)
        t_kill = time.perf_counter()
        gen_after_kill = co.view().generation
        survivors = [w for w in workers if w.rank != kill_rank]
        # first completed step at the post-kill generation = recovered
        while not any(any(r["gen"] >= gen_after_kill for r in w.steps)
                      for w in survivors):
            if time.time() > deadline:
                raise RuntimeError("drill: survivors never recovered")
            _check_errors(survivors)
            time.sleep(hb_interval / 4.0)
        t_rec = time.perf_counter()
        report["recovery_s"] = round(t_rec - t_kill, 4)
        report["world_after_kill"] = co.view().world_size

        if rejoin:
            # let the shrunk group reach steady state first (the
            # post-shrink throughput phase needs real steps, not the
            # one that paid the update-program re-key)
            def shrunk_steps():
                return max((sum(1 for r in w.steps
                                if r["gen"] >= gen_after_kill)
                            for w in survivors), default=0)
            while shrunk_steps() < rejoin_after_steps:
                if time.time() > deadline:
                    raise RuntimeError(
                        "drill: shrunk phase never reached "
                        f"{rejoin_after_steps} steps")
                _check_errors(survivors)
                time.sleep(hb_interval / 4.0)
            joiner = _DrillWorker(n_workers, co, cfg, join=True)
            joiner.start()

    for w in workers:
        w.thread.join(timeout=timeout_s)
    if joiner is not None:
        joiner.thread.join(timeout=timeout_s)
    wall = time.perf_counter() - t_start

    live = [w for w in workers + ([joiner] if joiner else [])
            if w.thread is not None]
    for w in live:
        if w.thread.is_alive():
            raise RuntimeError(f"drill: worker {w.wid} wedged "
                               f"(report so far: {report})")
        if w.error is not None:
            raise w.error

    # ---- phases by generation: [gen0, kill) / [kill, rejoin) / rest
    all_workers = workers + ([joiner] if joiner else [])
    if kill_step is not None:
        rate_full, n_full = _phase_rate(workers, None, gen_after_kill,
                                        batch)
        gen_rejoin = None
        if joiner is not None and joiner.steps:
            gen_rejoin = min(r["gen"] for r in joiner.steps)
        rate_shrunk, n_shrunk = _phase_rate(
            all_workers, gen_after_kill, gen_rejoin, batch)
        report["rate_full_samples_per_s"] = \
            round(rate_full, 2) if rate_full else None
        report["rate_shrunk_samples_per_s"] = \
            round(rate_shrunk, 2) if rate_shrunk else None
        report["shrink_throughput_ratio"] = (
            round(rate_shrunk / rate_full, 4)
            if rate_full and rate_shrunk else None)
        if gen_rejoin is not None:
            rate_re, n_re = _phase_rate(all_workers, gen_rejoin, None,
                                        batch)
            report["rate_rejoined_samples_per_s"] = \
                round(rate_re, 2) if rate_re else None
            report["rejoin_gen"] = gen_rejoin
        # the re-key budget, deterministic absolute counts: ONE grad
        # program per worker, ONE update program per distinct world
        # size it trained at, nothing else — any excess is a
        # steady-state recompile after a rebuild
        report["rekeys"] = {
            w.wid: {"grad": w.programs()["grad"],
                    "update": w.programs()["update"],
                    "worlds": w.worlds()}
            for w in all_workers if w.rank != kill_rank}
        report["recompiles_after_rebuild"] = sum(
            max(0, w.programs()["grad"] - 1)
            + max(0, w.programs()["update"] - len(w.worlds()))
            for w in all_workers if w.rank != kill_rank)
    else:
        rate, n = _phase_rate(workers, None, None, batch)
        report["rate_full_samples_per_s"] = round(rate, 2) if rate \
            else None

    # final loss: mean of each final member's last recorded loss
    finals = [w.steps[-1]["loss"] for w in all_workers
              if w.steps and w.death is None]
    report["final_loss"] = round(float(onp.mean(finals)), 6) if finals \
        else None
    report["final_view"] = co.view().describe()
    report["wall_s"] = round(wall, 3)
    report["per_worker"] = {
        w.wid: {"steps": len(w.steps), "death": w.death,
                "programs": w.programs(),
                "start_step": w.start_step}
        for w in all_workers}

    # mxguard verdict summary (sdc drills): who was suspected, when,
    # and whether the quarantine landed through a membership bump
    events = {w.wid: list(w.fused.guard_events) for w in all_workers
              if w.fused.guard_events}
    if events:
        suspect_steps = [e["step"] for evs in events.values()
                         for e in evs if e["kind"] == "suspect"]
        suspects = [s for evs in events.values() for e in evs
                    if e["kind"] in ("suspect", "persistent")
                    for s in (e["suspect"] if isinstance(
                        e["suspect"], list) else [e["suspect"]])]
        quarantined = [w.wid for w in all_workers
                       if w.death == "quarantined"]
        report["guard"] = {
            "detected_step": min(suspect_steps) if suspect_steps
            else None,
            "suspects": sorted(set(suspects)),
            "quarantined": quarantined,
            "events": events}
    return report
