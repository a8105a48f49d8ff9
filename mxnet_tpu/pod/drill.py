"""Subprocess N-host pod drills: the proof layer of mxpod.

``run_pod_drill`` spawns N REAL host processes (``python -m
mxnet_tpu.pod.worker``), each a full pod rank — own jax runtime, own
gluon Trainer over the socket-transport ElasticKVStore, own
split-phase step — trains the seeded drill task in lockstep, applies
one scripted host-scope fault via each process's OWN fault-plan env,
and reports the same phase/recovery/re-key schema as the in-process
elastic drill (elastic/drill.py), plus the pod-only verdicts:

- ``action="kill9"`` — SIGKILL one host at its step K
  (``pod.host.<rank>:K=kill9``); survivors must detect the dead HOST
  through missed control-socket beats alone, absorb the bump with
  zero user code, and a fresh host rejoins from group state-sync;
- ``action="sdc"`` — one host's gradients are silently corrupted
  (``guard.sdc.w<rank>:K+``); the CROSS-HOST fingerprint vote must
  attribute it by rank, quarantine it through a membership bump, and
  the survivors' loss trajectory stays in tolerance;
- ``kill_rank=0`` + ``restart_coordinator=True`` — the coordinator
  host itself dies; the harness restarts it, the new coordinator
  replays its generation journal, survivors ride their bounded-backoff
  reconnect into the ordinary rebuild, and the restarted host rejoins
  — no orphaned workers, no silent wedge.

Faults are scripted by step, never timed. Shared by
``tools/mxresil.py pod``, tests/test_pod.py (the subprocess drills
are @slow) and the tier-1 smoke.
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

__all__ = ["run_pod_drill"]

_log = get_logger("mxnet_tpu.pod")

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


class _Host:
    """One spawned host process + its parsed POD event stream."""

    def __init__(self, rank: int, env: Dict[str, str], join: bool):
        self.rank = rank
        self.wid = f"w{rank}"
        self.join = join
        self.events: List[Dict] = []  # each carries _t (arrival time)
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "mxnet_tpu.pod.worker"],
            env=env, cwd=_REPO_ROOT, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True)
        self.raw: List[str] = []
        self._reader = threading.Thread(target=self._drain, daemon=True)
        self._reader.start()
        self.t_exit: Optional[float] = None

    def _drain(self):
        for ln in self.proc.stdout:
            self.raw.append(ln)
            if ln.startswith("POD "):
                try:
                    evt = json.loads(ln[4:])
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

    def worlds(self) -> List[int]:
        return sorted({int(r["world"]) for r in self.steps()})

    def death(self) -> Optional[str]:
        rc = self.proc.returncode
        if rc is None:
            return None
        if rc == -9:
            return "killed"
        if rc == 43:
            return "quarantined"
        if rc == 44:
            return "coordinator_lost"
        if rc == 45:
            return "group_failed"
        if self.of("preempted"):
            return "preempted"
        return None if rc == 0 else f"rc{rc}"

    def kill_now(self):
        try:
            self.proc.kill()
        except OSError:
            pass


def _phase_rate(hosts, lo_gen, hi_gen, batch):
    """Aggregate samples/sec for steps with lo_gen <= gen < hi_gen
    (None = unbounded) — same median-step-time x world fold as
    elastic/drill.py, over the subprocess step streams."""
    times, worlds = [], []
    for h in hosts:
        for r in h.steps():
            if (lo_gen is None or r["gen"] >= lo_gen) and \
                    (hi_gen is None or r["gen"] < hi_gen):
                times.append(float(r["t"]))
                worlds.append(int(r["world"]))
    times.sort()
    if not times:
        return None, 0
    med = times[len(times) // 2]
    if med <= 0:
        return None, 0
    return max(worlds) * batch / med, len(times)


def _tails(hosts, limit=1200):
    return {h.wid: "".join(h.raw)[-limit:] for h in hosts}


def run_pod_drill(n_hosts: int = 3, steps: int = 20,
                  kill_step: Optional[int] = None, kill_rank: int = 1,
                  action: str = "kill9", rejoin: bool = True,
                  restart_coordinator: Optional[bool] = None,
                  rejoin_after_steps: int = 4, batch: int = 8,
                  in_dim: int = 16, hidden: int = 32, out_dim: int = 4,
                  lr: float = 0.05, seed: int = 0,
                  hb_interval: float = 0.3, miss_limit: int = 3,
                  min_world: int = 1, grace_s: float = 60.0,
                  journal: bool = True, step_sleep: float = 0.02,
                  keep_dirs: bool = False,
                  timeout_s: float = 300.0) -> Dict[str, object]:
    """One scripted drill (module docstring); returns the report dict.
    ``kill_step=None`` runs the uninterrupted baseline. The temp
    journal/gate dirs are removed on exit unless ``keep_dirs=True``
    (post-mortem inspection)."""
    import socket as _socket
    sdc = action.startswith("sdc")
    if restart_coordinator is None:
        restart_coordinator = (kill_rank == 0 and not sdc
                               and kill_step is not None)
    with _socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    jdir = tempfile.mkdtemp(prefix="mxpod_journal_") if journal else ""

    base_env = dict(os.environ)
    for k in ("MX_COORDINATOR", "MX_KV_SERVER", "MX_WORKER_ID",
              "MX_NUM_WORKERS", "XLA_FLAGS", "MXRESIL_FAULT_PLAN",
              "MXPOD_JOIN"):
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
        "MXELASTIC_MIN_WORLD": str(min_world),
        # paced steps: sub-millisecond CPU steps would let the whole
        # run outpace membership events (a released joiner's announce,
        # a heartbeat verdict) — the drill measures protocol behavior,
        # not peak step rate
        "POD_STEP_SLEEP": str(step_sleep),
        "POD_STEPS": str(steps), "POD_BATCH": str(batch),
        "POD_LR": str(lr), "POD_SEED": str(seed),
        "POD_IN_DIM": str(in_dim), "POD_HIDDEN": str(hidden),
        "POD_OUT_DIM": str(out_dim),
    })
    if sdc:
        base_env["MXGUARD"] = "1"

    def spawn(rank: int, join: bool = False,
              plan: Optional[str] = None,
              go_file: Optional[str] = None) -> _Host:
        env = dict(base_env)
        env["MXPOD_RANK"] = str(rank)
        if join:
            env["MXPOD_JOIN"] = "1"
            # the entrant itself never waits on its own landing
            env.pop("POD_LANDED_FILE", None)
        if plan:
            env["MXRESIL_FAULT_PLAN"] = plan
        if go_file:
            env["POD_GO_FILE"] = go_file
        return _Host(rank, env, join)

    target_plan = None
    if kill_step is not None:
        if sdc:
            mode = action.split(":", 1)[1] if ":" in action \
                else "bitflip"
            target_plan = f"guard.sdc.w{kill_rank}:{kill_step}+=" \
                          f"sdc:{mode}"
        else:
            target_plan = f"pod.host.{kill_rank}:{kill_step}={action}"

    t_start = time.perf_counter()
    # warm standby: the drill's rejoining host imports jax/the
    # framework UP FRONT (the slow part of a host bring-up) and holds
    # at a go-file gate before touching the control plane — so the
    # join lands while the survivors are still training, and a
    # restarted rank-0 binds the coordinator port only once its
    # predecessor is dead. Real deployments get the same effect from
    # the cluster manager's standby pool.
    entrant: Optional[_Host] = None
    go_file = None
    if kill_step is not None and (rejoin or restart_coordinator):
        go_file = os.path.join(jdir or tempfile.mkdtemp(
            prefix="mxpod_go_"), "go")
        # original hosts hold the membership boundary open at the end
        # of their run until the harness confirms the entrant landed
        # (worker.py linger on this file) — a fast run must not
        # orphan an announced joiner
        base_env["POD_LANDED_FILE"] = go_file + ".landed"
        base_env["POD_LINGER_S"] = "20"
        entrant = spawn(kill_rank if restart_coordinator else n_hosts,
                        join=True, go_file=go_file)
    hosts = [spawn(r, plan=target_plan if r == kill_rank else None)
             for r in range(n_hosts)]
    deadline = time.monotonic() + timeout_s
    report: Dict[str, object] = {
        "workers": n_hosts, "steps": steps, "kill_step": kill_step,
        "action": action if kill_step is not None else None,
        "rejoin": bool(rejoin and kill_step is not None),
        "restart_coordinator": bool(restart_coordinator),
        "batch": batch, "journal_dir": jdir or None}

    def everyone():
        return hosts + ([entrant] if entrant else [])

    def check_deadline(what: str):
        if time.monotonic() > deadline:
            for h in everyone():
                h.kill_now()
            raise RuntimeError(
                f"pod drill: {what} (tails: {_tails(everyone())})")

    # only a scripted drill tolerates the target's death — a baseline
    # worker dying (OOM, crash) must fail LOUDLY, never silently
    # corrupt the reference numbers every gate compares against
    target_rank = kill_rank if kill_step is not None else None

    def unexpected_death(hs):
        for h in hs:
            rc = h.poll()
            if rc not in (None, 0) and h.rank != target_rank:
                raise RuntimeError(
                    f"pod drill: {h.wid} died unexpectedly rc={rc}: "
                    f"{''.join(h.raw)[-1500:]}")

    def release_entrant():
        with open(go_file, "w") as f:
            f.write("go\n")

    try:
        # formation: every original host reports its agreed generation
        while not all(h.of("formed") for h in hosts):
            check_deadline("formation never completed")
            unexpected_death(hosts)
            time.sleep(0.05)
        gen0 = max(h.of("formed")[0]["generation"] for h in hosts)
        report["gen0"] = gen0

        t_death = None
        gen_after_kill = None
        if kill_step is not None:
            target = hosts[kill_rank]
            survivors = [h for h in hosts if h.rank != kill_rank]
            # the scripted fault fires in-process; wait for the death
            while target.poll() is None and target.t_exit is None:
                check_deadline("scripted fault never fired")
                unexpected_death(survivors)
                time.sleep(0.05)
            # sdc: the membership bump lands at the quarantine verdict
            # (in-step), before the corrupt process finishes tearing
            # down — measure recovery from the verdict, not the exit
            quar = target.of("quarantined")
            t_death = quar[0]["_t"] if quar else target.t_exit
            if restart_coordinator and entrant is not None:
                # predecessor dead -> the standby may bind the port,
                # replay the journal and re-form the group
                release_entrant()

            def recovered_gen():
                gens = [r["gen"] for h in survivors
                        for r in h.steps() if r["gen"] > gen0]
                return min(gens) if gens else None

            while recovered_gen() is None:
                check_deadline("survivors never recovered")
                unexpected_death(survivors)
                time.sleep(0.05)
            gen_after_kill = recovered_gen()
            t_rec = min(
                r["_t"] for h in survivors for r in h.steps()
                if r["gen"] >= gen_after_kill)
            report["recovery_s"] = round(max(0.0, t_rec - t_death), 4)
            report["world_after_kill"] = min(
                int(r["world"]) for h in survivors for r in h.steps()
                if r["gen"] >= gen_after_kill)

            if entrant is not None and not restart_coordinator:
                def shrunk_steps():
                    return max((sum(1 for r in h.steps()
                                    if r["gen"] >= gen_after_kill)
                                for h in survivors), default=0)
                while shrunk_steps() < rejoin_after_steps:
                    check_deadline("shrunk phase never reached "
                                   f"{rejoin_after_steps} steps")
                    unexpected_death(survivors)
                    time.sleep(0.05)
                release_entrant()

        # drain: every live process runs to completion. The moment the
        # entrant reports itself formed (admitted + state synced) —
        # or dies — the landed-file releases the lingering originals.
        landed_path = (go_file + ".landed") if go_file else None
        live = everyone()
        while any(h.poll() is None for h in live):
            check_deadline("drill never drained")
            if landed_path and not os.path.exists(landed_path) and \
                    entrant is not None and \
                    (entrant.of("formed") or
                     entrant.poll() is not None):
                with open(landed_path, "w") as f:
                    f.write("landed\n")
            time.sleep(0.1)
        for h in live:
            h._reader.join(timeout=5.0)
        wall = time.perf_counter() - t_start

        for h in live:
            rc = h.proc.returncode
            ok = {0}
            if h.rank == target_rank and not h.join:
                # the scripted death: SIGKILL for kill9, quarantine
                # exit for sdc, clean exit for preempt
                ok |= {-9, 43}
            if rc not in ok:
                raise RuntimeError(
                    f"pod drill: {h.wid} exited rc={rc}: "
                    f"{''.join(h.raw)[-1500:]}")

        # ---- phases / budget / loss ---------------------------------
        if kill_step is not None:
            survivors = [h for h in hosts if h.rank != kill_rank]
            finishers = survivors + ([entrant] if entrant else [])
            rate_full, _ = _phase_rate(hosts, None, gen_after_kill,
                                       batch)
            gen_rejoin = None
            if entrant is not None and entrant.steps():
                gen_rejoin = min(r["gen"] for r in entrant.steps())
            rate_shrunk, _ = _phase_rate(
                finishers, gen_after_kill, gen_rejoin, batch)
            report["rate_full_samples_per_s"] = \
                round(rate_full, 2) if rate_full else None
            report["rate_shrunk_samples_per_s"] = \
                round(rate_shrunk, 2) if rate_shrunk else None
            report["shrink_throughput_ratio"] = (
                round(rate_shrunk / rate_full, 4)
                if rate_full and rate_shrunk else None)
            if gen_rejoin is not None:
                rate_re, _ = _phase_rate(finishers, gen_rejoin, None,
                                         batch)
                report["rate_rejoined_samples_per_s"] = \
                    round(rate_re, 2) if rate_re else None
                report["rejoin_gen"] = gen_rejoin
            rekeys = {}
            recompiles = 0
            for h in finishers:
                done = h.of("done")
                if not done:
                    continue
                if h.join and not h.steps():
                    # an entrant admitted after the others finished
                    # trained zero steps and compiled nothing — no
                    # budget to account
                    continue
                progs = done[0]["programs"]
                worlds = h.worlds()
                rekeys[h.wid] = {"grad": progs["grad"],
                                 "update": progs["update"],
                                 "worlds": worlds}
                recompiles += max(0, progs["grad"] - 1) + \
                    max(0, progs["update"] - len(worlds))
            report["rekeys"] = rekeys
            report["recompiles_after_rebuild"] = recompiles
            if entrant is not None:
                formed = entrant.of("formed")
                start = formed[0]["start_step"] if formed else 0
                report["rejoin_synced_from_group"] = bool(
                    formed and formed[0]["synced_from_group"])
                report["steps_lost"] = max(0, start - kill_step) \
                    if formed else None
        else:
            rate, _ = _phase_rate(hosts, None, None, batch)
            report["rate_full_samples_per_s"] = \
                round(rate, 2) if rate else None

        finals = [h.steps()[-1]["loss"] for h in everyone()
                  if h.steps() and h.death() is None]
        report["final_loss"] = (round(sum(finals) / len(finals), 6)
                                if finals else None)
        dones = [e for h in everyone() for e in h.of("done")]
        report["final_view"] = dones[-1]["final_view"] if dones \
            else None
        report["wall_s"] = round(wall, 3)
        report["per_worker"] = {
            h.wid + ("+join" if h.join else ""): {
                "steps": len(h.steps()), "death": h.death(),
                "rc": h.proc.returncode,
                "start_step": (h.of("formed")[0]["start_step"]
                               if h.of("formed") else 0)}
            for h in everyone()}

        if restart_coordinator and entrant is not None:
            ctx_evt = entrant.of("context")
            report["coordinator_restart"] = {
                "journal_replayed": bool(ctx_evt and
                                         ctx_evt[0]["restored"]),
                "rejoined": bool(entrant.of("done")),
                "survivor_coordinator_lost": any(
                    h.of("coordinator_lost") for h in hosts
                    if h.rank != kill_rank)}

        # mxguard verdicts (sdc drills): attribution by rank
        events = {}
        for h in everyone():
            evs = [e for kind in ("done", "quarantined")
                   for d in h.of(kind)
                   for e in (d.get("guard_events") or [])]
            if evs:
                events[h.wid] = evs
        if events:
            suspect_steps = [e["step"] for evs in events.values()
                             for e in evs if e["kind"] == "suspect"]
            suspects = [s for evs in events.values() for e in evs
                        if e["kind"] in ("suspect", "persistent")
                        for s in (e["suspect"] if isinstance(
                            e["suspect"], list) else [e["suspect"]])]
            report["guard"] = {
                "detected_step": (min(suspect_steps)
                                  if suspect_steps else None),
                "suspects": sorted(set(suspects)),
                "quarantined": [h.wid for h in hosts
                                if h.death() == "quarantined"],
                "events": events}
        if not keep_dirs:
            report["journal_dir"] = None  # removed below
        return report
    finally:
        for h in everyone():
            if h.poll() is None:
                h.kill_now()
        if not keep_dirs:
            import shutil
            for d in {jdir, os.path.dirname(go_file or "")} - {""}:
                shutil.rmtree(d, ignore_errors=True)
