"""The persistent tuning DB: measured configs, keyed and provenanced.

A JSONL file (one measurement record per line) following the
crash-safety idiom of ``guard/replay.py``'s ring: every append is a
single ``write + flush`` of
one line (a kill mid-write leaves at most one torn tail line, which
:meth:`TuneDB.records` skips), and when the file outgrows
``2 * capacity`` lines it is compacted **in place** via a tmp-file
``os.replace`` — keeping, per (key, objective), the best legal record
plus the newest, then the newest remainder up to capacity (the model
warm-start corpus).

Keys
----
Every record carries the four-part key the auto-apply path matches on:

- ``model_sig``   — digest of the bound model's (name, shape, dtype)
  parameter census (:func:`mxnet_tpu.tune.apply.signature_of`);
- ``device_kind`` — the backend this number was measured on (a TPU
  config must never auto-apply to a CPU host, and vice versa);
- ``mesh_shape``  — device-mesh extent at measurement time;
- ``space_fp``    — the knob-space fingerprint; a drifted knob
  universe invalidates the entry (tunelint's stale-DB class).

``best_config(key, objective)`` ranks legal records by the objective's
declared direction (:data:`mxnet_tpu.tune.space.OBJECTIVES`). Records
rejected by the measurement runner's legality rails are *not stored* —
the DB only ever holds configs that compiled warm and passed their
tolerance class, so a lookup can be applied without re-running the
gates.
"""
from __future__ import annotations

import json
import os
import threading
import time
from typing import Dict, List, Optional

from ..base import MXNetError, get_logger
from .space import objective_direction

__all__ = ["TuneDB", "DB_FILE", "SCHEMA_VERSION", "key_str",
           "default_dir"]

_log = get_logger("mxnet_tpu.tune")

DB_FILE = "tune_db.jsonl"
#: bumped when the record shape changes; provenance pins which bench
#: schema produced a number so a reader can refuse to compare across.
SCHEMA_VERSION = 1

_REQUIRED = ("key", "config", "objective", "value")
_KEY_FIELDS = ("model_sig", "device_kind", "mesh_shape", "space_fp")


def default_dir() -> str:
    """DB directory: ``MXTUNE_DB_DIR`` or ``~/.mxnet_tpu/tune``."""
    from .. import config
    d = str(config.get("MXTUNE_DB_DIR") or "")
    return d or os.path.join(os.path.expanduser("~"), ".mxnet_tpu",
                             "tune")


def key_str(key: Dict) -> str:
    """Canonical string form of a DB key (sorted, list-normalized) —
    the equality the lookup matches on."""
    norm = {}
    for f in _KEY_FIELDS:
        v = key.get(f)
        if f == "mesh_shape" and v is not None:
            v = [int(x) for x in v]
        norm[f] = v
    return json.dumps(norm, sort_keys=True)


class TuneDB:
    """Crash-safe append-only JSONL store with keyed best-config
    lookup. Thread-safe; cheap to construct (the file is read lazily
    per call — cross-process appends are always visible)."""

    def __init__(self, directory: Optional[str] = None,
                 capacity: int = 512):
        self.directory = directory or default_dir()
        self.capacity = max(8, int(capacity))
        self.path = os.path.join(self.directory, DB_FILE)
        self._lock = threading.Lock()

    # -- write ---------------------------------------------------------

    def append(self, record: Dict) -> Dict:
        """Validate + append one measurement record. Fills ``ts``,
        ``schema`` and normalizes the key; returns the stored form."""
        for f in _REQUIRED:
            if f not in record:
                raise MXNetError(
                    f"tune DB record missing required field {f!r} "
                    f"(have {sorted(record)})")
        objective_direction(str(record["objective"]))  # known objective
        for f in _KEY_FIELDS:
            if f not in record["key"]:
                raise MXNetError(
                    f"tune DB key missing field {f!r} "
                    f"(have {sorted(record['key'])})")
        rec = dict(record)
        rec["schema"] = SCHEMA_VERSION
        rec.setdefault("ts", time.time())
        rec["key"] = json.loads(key_str(rec["key"]))
        line = json.dumps(rec, sort_keys=True)
        with self._lock:
            os.makedirs(self.directory, exist_ok=True)
            with open(self.path, "a") as f:
                f.write(line + "\n")
                # mxsan: ok — one bounded line per trial; the flush IS the crash-safe append commit point
                f.flush()
            if self._count_lines() >= 2 * self.capacity:
                self._compact_locked()
        return rec

    def _count_lines(self) -> int:
        try:
            with open(self.path) as f:
                return sum(1 for _ in f)
        except OSError:
            return 0

    def _compact_locked(self):
        recs = self._load()
        keep: List[Dict] = []
        seen = set()
        # per (key, objective): the best record and the newest
        groups: Dict[str, List[Dict]] = {}
        for r in recs:
            groups.setdefault(
                key_str(r["key"]) + "|" + str(r["objective"]),
                []).append(r)
        for grp in groups.values():
            newest = max(grp, key=lambda r: r.get("ts", 0))
            best = self._rank(grp)
            for r in ([best] if best is not None else []) + [newest]:
                rid = id(r)
                if rid not in seen:
                    seen.add(rid)
                    keep.append(r)
        # newest remainder up to capacity (model warm-start corpus)
        rest = [r for r in recs if id(r) not in seen]
        rest.sort(key=lambda r: r.get("ts", 0), reverse=True)
        keep.extend(rest[:max(0, self.capacity - len(keep))])
        keep.sort(key=lambda r: r.get("ts", 0))
        tmp = self.path + ".tmp"
        with open(tmp, "w") as f:
            for r in keep:
                f.write(json.dumps(r, sort_keys=True) + "\n")
        os.replace(tmp, self.path)

    @staticmethod
    def _rank(grp: List[Dict]) -> Optional[Dict]:
        legal = [r for r in grp if r.get("value") is not None]
        if not legal:
            return None
        direction = objective_direction(str(legal[0]["objective"]))
        pick = min if direction == "min" else max
        return pick(legal, key=lambda r: float(r["value"]))

    # -- read ----------------------------------------------------------

    def _load(self) -> List[Dict]:
        out: List[Dict] = []
        try:
            with open(self.path) as f:
                for ln in f:
                    ln = ln.strip()
                    if not ln:
                        continue
                    try:
                        rec = json.loads(ln)
                    except ValueError:
                        continue  # torn tail line (crash mid-append)
                    if isinstance(rec, dict) and \
                            all(f in rec for f in _REQUIRED):
                        out.append(rec)
        except OSError:
            pass
        return out

    def records(self) -> List[Dict]:
        with self._lock:
            return self._load()

    def best_config(self, key: Dict, objective: str
                    ) -> Optional[Dict]:
        """The best legal record for (key, objective), or None. The
        returned dict is the full record (config + provenance), so the
        caller can log WHAT it applied and WHY."""
        objective_direction(objective)
        want = key_str(key)
        grp = [r for r in self.records()
               if key_str(r["key"]) == want
               and str(r["objective"]) == objective]
        return self._rank(grp)

    def compact(self) -> int:
        """Force a compaction; returns the surviving record count."""
        with self._lock:
            if os.path.exists(self.path):
                self._compact_locked()
            return self._count_lines()

    def describe(self) -> Dict:
        recs = self.records()
        keys = sorted({key_str(r["key"]) for r in recs})
        objectives = sorted({str(r["objective"]) for r in recs})
        return {"path": self.path, "records": len(recs),
                "keys": len(keys), "objectives": objectives,
                "schema": SCHEMA_VERSION,
                "newest_ts": max((r.get("ts", 0) for r in recs),
                                 default=None)}
