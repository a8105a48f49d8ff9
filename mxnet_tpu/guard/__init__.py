"""mxguard: the silent-corruption integrity layer.

The resilience stack (mxnet_tpu/resil/, mxnet_tpu/elastic/) handles
*loud* failures — crashes, preemption, lost workers, wedged
collectives. mxguard handles the quiet ones: a flaky core that flips
one bit in one gradient, a run that silently diverges — faults that
today ride the allreduce into every replica and are noticed only when
the loss is already ruined. Three pillars (ISSUE 10; the production
elevation of the reference's TensorInspector/Monitor debugging
surfaces):

- :mod:`~mxnet_tpu.guard.fingerprint` — per-gradient **integrity
  fingerprints** (float checksum, absmax, non-finite count) emitted as
  extra outputs of the fused train step behind the ``MXGUARD`` flag
  (part of the signature-cache key: zero steady-state recompiles,
  bitwise-neutral to the weights — test-enforced), plus the sharded
  path's per-device replica digests;
- :mod:`~mxnet_tpu.guard.voting` — **cross-replica voting**: workers
  exchange fingerprints through a generation-fenced round *before*
  gradients enter the allreduce; the deterministic verdict names the
  corrupt replica pre-averaging, a same-input re-execution classifies
  the fault transient (retry) vs persistent (quarantine through the
  elastic membership-bump machinery, or hard-fail solo runs);
- :mod:`~mxnet_tpu.guard.replay` — **deterministic replay**: a bounded
  record ring (batch digests, RNG keys, step scalars, fingerprints)
  plus a known-good checkpoint ring lets ``tools/mxresil.py replay``
  re-execute a window bitwise and bisect the first corrupted step
  after an EWMA anomaly verdict (:mod:`~mxnet_tpu.guard.anomaly`,
  riding the resil Watchdog's probe registry).

tests/test_guard.py drives the whole arc: a one-element gradient
corruption on 1 of N workers is detected within one step, attributed,
and quarantined, with zero steady-state recompiles (the taps' cost on
a chip is not measured). ``passes/guardlint.py`` audits that gradient
exchanges carry taps and that detection is paired with a recovery
ring. Architecture: docs/resilience.md, integrity section.
"""
from __future__ import annotations

from . import anomaly, fingerprint, replay, voting  # noqa: F401
from .anomaly import GuardProbe, default_probe  # noqa: F401
from .fingerprint import (FP_FIELDS, GuardVerdict,  # noqa: F401
                          check_replica_digests, fingerprint_rows,
                          fingerprint_vec, fold_rows, host_fingerprint,
                          replica_digests, vote)
from .replay import (ReplayRecorder, load_ring,  # noqa: F401
                     replay_ring, replay_window, run_replay_drill)
from .voting import (GuardCorruption, GuardQuarantined,  # noqa: F401
                     apply_sdc, sdc_token)

__all__ = ["fingerprint", "voting", "anomaly", "replay",
           "FP_FIELDS", "GuardVerdict", "vote", "fingerprint_vec",
           "fingerprint_rows", "fold_rows", "host_fingerprint",
           "replica_digests", "check_replica_digests",
           "GuardQuarantined", "GuardCorruption", "apply_sdc",
           "sdc_token", "GuardProbe", "default_probe",
           "ReplayRecorder", "load_ring", "replay_window",
           "replay_ring", "run_replay_drill"]
