"""Subprocess lost-stage drills: the proof layer of mxpipe's elastic
claim (a lost host IS a lost stage).

``run_pipe_drill`` spawns N REAL host processes (``python -m
mxnet_tpu.pipe.worker``), each a pod rank owning one-or-more pipeline
stages of the SAME replicated model, trains the seeded pipeline LM in
lockstep over the fenced socket transport, SIGKILLs one mid-pipeline
host at its scripted step (``pod.host.<rank>:K=kill9``), and asserts
the mxpipe recovery contract:

- **survivors recover**: every surviving host detects the dead stage
  through missed control-socket beats, absorbs the membership bump,
  re-maps stages onto the survivor set (``restage`` events), REDOES
  the interrupted step from committed state and keeps training —
  zero user code;
- **no trajectory damage**: because stage state is replicated through
  the end-of-step sync rounds and the interrupted step is redone from
  committed state, the survivors' final loss must match an
  UNINTERRUPTED baseline of the same seed within
  ``MXELASTIC_LOSS_TOL`` (it is bit-identical in practice — the
  tolerance guards numerical noise, not divergence);
- **audited re-key budget**: recompiles are counted against the
  stage-kind model — grad programs are world-independent (first=2,
  mid=2, last=1 per owned stage KIND; S==1 degenerate=1) and update
  programs re-key once per stage-kind per topology — any extra
  compile fails the drill.

Faults are scripted by step, never timed. Run by tests/test_pipe.py
(@slow).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import threading
import time
from typing import Dict, List, Optional

from ..base import get_logger

__all__ = ["run_pipe_drill", "expected_programs"]

_log = get_logger("mxnet_tpu.pipe")

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


class _Host:
    """One spawned host process + its parsed PIPE event stream."""

    def __init__(self, rank: int, env: Dict[str, str]):
        self.rank = rank
        self.wid = f"w{rank}"
        self.events: List[Dict] = []
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "mxnet_tpu.pipe.worker"],
            env=env, cwd=_REPO_ROOT, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True)
        self.raw: List[str] = []
        self._reader = threading.Thread(target=self._drain, daemon=True)
        self._reader.start()
        self.t_exit: Optional[float] = None

    def _drain(self):
        for ln in self.proc.stdout:
            self.raw.append(ln)
            if ln.startswith("PIPE "):
                try:
                    evt = json.loads(ln[5:])
                except ValueError:
                    continue
                evt["_t"] = time.perf_counter()
                self.events.append(evt)

    def poll(self) -> Optional[int]:
        rc = self.proc.poll()
        if rc is not None and self.t_exit is None:
            self.t_exit = time.perf_counter()
        return rc

    def of(self, kind: str) -> List[Dict]:
        return [e for e in self.events if e.get("evt") == kind]

    def steps(self) -> List[Dict]:
        return self.of("step")

    def kill_now(self):
        try:
            self.proc.kill()
        except OSError:
            pass


def _stage_kinds(stage_map: Dict, n_stage: int, wid: str) -> set:
    """The stage KINDS a worker owns under one map: 'first' | 'mid' |
    'last' | 'only' (S==1 degenerate). Program signatures are shared
    within a kind, so the compile budget counts kinds, not stages."""
    kinds = set()
    for s_str, w in stage_map.items():
        if w != wid:
            continue
        s = int(s_str)
        if n_stage == 1:
            kinds.add("only")
        elif s == 0:
            kinds.add("first")
        elif s == n_stage - 1:
            kinds.add("last")
        else:
            kinds.add("mid")
    return kinds


# world-independent grad programs per stage kind: first = fwd_first +
# bwd_first; mid = fwd_mid + bwd_mid; last = loss_grad (fused);
# only = loss_grad_first (S==1)
_GRAD_PER_KIND = {"first": 2, "mid": 2, "last": 1, "only": 1}


def expected_programs(maps_seen: List[Dict], n_stage: int,
                      wid: str) -> Dict[str, int]:
    """The audited compile budget for one worker, from its observed
    per-generation stage maps: grad programs = union of owned kinds
    across ALL generations (world-independent — a kind compiled once
    is never recompiled); update programs = one per owned kind per
    TOPOLOGY (the update program keys on the world token)."""
    all_kinds = set()
    update = 0
    for m in maps_seen:
        kinds = _stage_kinds(m["stage_map"], n_stage, wid)
        all_kinds |= kinds
        update += len(kinds)
    grad = sum(_GRAD_PER_KIND[k] for k in all_kinds)
    return {"grad": grad, "update": update}


def _tails(hosts, limit=1500):
    return {h.wid: "".join(h.raw)[-limit:] for h in hosts}


def run_pipe_drill(n_hosts: int = 3, steps: int = 10,
                   kill_step: Optional[int] = None, kill_rank: int = 1,
                   n_stage: Optional[int] = None,
                   schedule: str = "1f1b", n_micro: int = 4,
                   batch: int = 8, seq: int = 8, vocab: int = 64,
                   d_model: int = 16, n_layers: int = 6,
                   lr: float = 1e-3, seed: int = 0,
                   hb_interval: float = 0.3, miss_limit: int = 3,
                   grace_s: float = 60.0, step_sleep: float = 0.02,
                   baseline_loss: Optional[float] = None,
                   keep_dirs: bool = False,
                   timeout_s: float = 300.0) -> Dict[str, object]:
    """One scripted lost-stage drill (module docstring); returns the
    report dict. ``kill_step=None`` runs the uninterrupted baseline;
    pass its ``final_loss`` back as ``baseline_loss`` to get the
    ``loss_delta`` verdict in the kill run's report."""
    import socket as _socket
    n_stage = int(n_stage or n_hosts)
    with _socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    jdir = tempfile.mkdtemp(prefix="mxpipe_journal_")

    base_env = dict(os.environ)
    for k in ("MX_COORDINATOR", "MX_KV_SERVER", "MX_WORKER_ID",
              "MX_NUM_WORKERS", "XLA_FLAGS", "MXRESIL_FAULT_PLAN",
              "MXPOD_JOIN", "MXPIPE_STAGES", "MXPIPE_SCHEDULE",
              "MXPIPE_MICROBATCH"):
        base_env.pop(k, None)
    base_env.update({
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": _REPO_ROOT + os.pathsep
        + base_env.get("PYTHONPATH", ""),
        "MXPOD_COORDINATOR": f"127.0.0.1:{port}",
        "MXPOD_NPROCS": str(n_hosts),
        "MXPOD_HEARTBEAT_S": str(hb_interval),
        "MXPOD_JOURNAL_DIR": jdir,
        "MXPOD_COORDINATOR_GRACE_S": str(grace_s),
        "MXELASTIC_MISS_LIMIT": str(miss_limit),
        "MXELASTIC_MIN_WORLD": "1",
        "PIPE_STEPS": str(steps), "PIPE_BATCH": str(batch),
        "PIPE_SEQ": str(seq), "PIPE_VOCAB": str(vocab),
        "PIPE_DMODEL": str(d_model), "PIPE_LAYERS": str(n_layers),
        "PIPE_LR": str(lr), "PIPE_SEED": str(seed),
        "PIPE_STAGES": str(n_stage), "PIPE_MICROBATCH": str(n_micro),
        "PIPE_SCHEDULE": schedule,
        "PIPE_STEP_SLEEP": str(step_sleep),
    })

    target_plan = None
    if kill_step is not None:
        target_plan = f"pod.host.{kill_rank}:{kill_step}=kill9"

    def spawn(rank: int) -> _Host:
        env = dict(base_env)
        env["MXPOD_RANK"] = str(rank)
        if rank == kill_rank and target_plan:
            env["MXRESIL_FAULT_PLAN"] = target_plan
        return _Host(rank, env)

    t_start = time.perf_counter()
    hosts = [spawn(r) for r in range(n_hosts)]
    deadline = time.monotonic() + timeout_s
    report: Dict[str, object] = {
        "hosts": n_hosts, "steps": steps, "kill_step": kill_step,
        "kill_rank": kill_rank if kill_step is not None else None,
        "n_stage": n_stage, "schedule": schedule, "n_micro": n_micro,
        "batch": batch, "journal_dir": jdir}

    def check_deadline(what: str):
        if time.monotonic() > deadline:
            for h in hosts:
                h.kill_now()
            raise RuntimeError(
                f"pipe drill: {what} (tails: {_tails(hosts)})")

    target_rank = kill_rank if kill_step is not None else None

    def unexpected_death(hs):
        for h in hs:
            rc = h.poll()
            if rc not in (None, 0) and h.rank != target_rank:
                raise RuntimeError(
                    f"pipe drill: {h.wid} died unexpectedly rc={rc}: "
                    f"{''.join(h.raw)[-1500:]}")

    try:
        # formation: every host reports the agreed stage map
        while not all(h.of("formed") for h in hosts):
            check_deadline("formation never completed")
            unexpected_death(hosts)
            time.sleep(0.05)
        gen0 = max(h.of("formed")[0]["generation"] for h in hosts)
        map0 = hosts[0].of("formed")[0]["stage_map"]
        report["gen0"] = gen0
        report["stage_map0"] = map0
        for h in hosts[1:]:
            if h.of("formed")[0]["stage_map"] != map0:
                raise RuntimeError(
                    f"pipe drill: {h.wid} formed a DIFFERENT stage "
                    f"map: {h.of('formed')[0]['stage_map']} != {map0}")

        gen_after_kill = None
        if kill_step is not None:
            target = hosts[kill_rank]
            survivors = [h for h in hosts if h.rank != kill_rank]
            while target.poll() is None and target.t_exit is None:
                check_deadline("scripted fault never fired")
                unexpected_death(survivors)
                time.sleep(0.05)
            t_death = target.t_exit

            def recovered_gen():
                gens = [r["gen"] for h in survivors
                        for r in h.steps() if r["gen"] > gen0]
                return min(gens) if gens else None

            while recovered_gen() is None:
                check_deadline("survivors never recovered")
                unexpected_death(survivors)
                time.sleep(0.05)
            gen_after_kill = recovered_gen()
            t_rec = min(r["_t"] for h in survivors for r in h.steps()
                        if r["gen"] >= gen_after_kill)
            report["recovery_s"] = round(max(0.0, t_rec - t_death), 4)
            report["world_after_kill"] = min(
                int(r["world"]) for h in survivors for r in h.steps()
                if r["gen"] >= gen_after_kill)

        # drain: every live process runs to completion
        while any(h.poll() is None for h in hosts):
            check_deadline("drill never drained")
            time.sleep(0.1)
        for h in hosts:
            h._reader.join(timeout=5.0)
        wall = time.perf_counter() - t_start

        for h in hosts:
            rc = h.proc.returncode
            ok = {0} | ({-9} if h.rank == target_rank else set())
            if rc not in ok:
                raise RuntimeError(
                    f"pipe drill: {h.wid} exited rc={rc}: "
                    f"{''.join(h.raw)[-1500:]}")

        finishers = [h for h in hosts if h.rank != target_rank]

        # ---- restage + stage-coverage verdicts ----------------------
        if kill_step is not None:
            restages = {h.wid: h.of("restage") for h in finishers}
            missing = [w for w, evs in restages.items() if not evs]
            if missing:
                raise RuntimeError(
                    f"pipe drill: survivors {missing} never emitted a "
                    f"restage event (tails: {_tails(finishers)})")
            # the re-mapped stage map must agree across survivors and
            # cover ALL stages with only survivors
            final_maps = [evs[-1]["stage_map"]
                          for evs in restages.values()]
            if any(m != final_maps[0] for m in final_maps[1:]):
                raise RuntimeError(
                    f"pipe drill: survivors disagree on the re-mapped "
                    f"stage map: {final_maps}")
            dead_wid = f"w{kill_rank}"
            fmap = final_maps[0]
            if sorted(int(s) for s in fmap) != list(range(n_stage)):
                raise RuntimeError(
                    f"pipe drill: re-mapped stage map does not cover "
                    f"all {n_stage} stages: {fmap}")
            if dead_wid in fmap.values():
                raise RuntimeError(
                    f"pipe drill: dead host {dead_wid} still owns "
                    f"stages after the bump: {fmap}")
            report["stage_map_after_kill"] = fmap
            report["restages"] = {w: len(evs)
                                  for w, evs in restages.items()}

        # ---- audited re-key budget ----------------------------------
        rekeys = {}
        excess_total = 0
        for h in finishers:
            done = h.of("done")
            if not done:
                raise RuntimeError(
                    f"pipe drill: {h.wid} finished without a done "
                    f"event: {''.join(h.raw)[-1500:]}")
            d = done[0]
            expect = expected_programs(d["maps_seen"], n_stage, h.wid)
            got = {"grad": d["programs"]["grad"],
                   "update": d["programs"]["update"]}
            excess = max(0, got["grad"] - expect["grad"]) + \
                max(0, got["update"] - expect["update"])
            excess_total += excess
            rekeys[h.wid] = {"got": got, "expected": expect,
                             "excess": excess,
                             "worlds": d["worlds_seen"],
                             "census": d["census"]}
        report["rekeys"] = rekeys
        report["recompiles_beyond_budget"] = excess_total

        # ---- loss verdict -------------------------------------------
        finals = [h.steps()[-1]["loss"] for h in finishers
                  if h.steps()]
        report["final_loss"] = (round(sum(finals) / len(finals), 6)
                                if finals else None)
        if len(set(round(f, 6) for f in finals)) > 1:
            raise RuntimeError(
                f"pipe drill: finishers disagree on the final loss "
                f"(replicated state broken): {finals}")
        if baseline_loss is not None and finals:
            delta = abs(finals[0] - baseline_loss)
            report["baseline_loss"] = round(baseline_loss, 6)
            report["loss_delta"] = round(delta, 6)
        report["wall_s"] = round(wall, 3)
        report["per_host"] = {
            h.wid: {"steps": len(h.steps()), "rc": h.proc.returncode,
                    "killed": h.rank == target_rank}
            for h in hosts}
        return report
    finally:
        for h in hosts:
            if h.poll() is None:
                h.kill_now()
        if not keep_dirs:
            import shutil
            shutil.rmtree(jdir, ignore_errors=True)
            report["journal_dir"] = None
