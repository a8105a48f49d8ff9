"""mxsan tests: the racelint static pass and the MXSAN runtime
lock-order sanitizer (ISSUE 16).

Coverage contract (the acceptance criteria, test-enforced):
- every bad fixture FIRES its check and every paired good spelling
  stays quiet — the lint can never go vacuous;
- the live mxnet_tpu tree lints clean modulo the reviewed exemption
  registry (``mxlint --race`` exits 0) — the tier-1 gate;
- an injected two-lock cycle is detected at runtime with BOTH
  acquisition stacks named in the finding;
- MXSAN=0 construction returns the PLAIN threading primitives (zero
  cost when off, asserted structurally here);
- a waiter blocked past MXSAN_BLOCK_THRESHOLD_MS triggers the
  flight-recorder dump and the blocked-waiter finding.
"""
import json
import os
import subprocess
import sys
import threading
import time
import warnings

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from mxnet_tpu import config  # noqa: E402
from mxnet_tpu.passes import default_manager  # noqa: E402
from mxnet_tpu.passes.racelint import RaceLint  # noqa: E402
from mxnet_tpu.san import exemptions, racelint, runtime  # noqa: E402


@pytest.fixture
def mxsan_on():
    """MXSAN=1 with a clean sanitizer state; always restored."""
    config.set_flag("MXSAN", True)
    runtime.reset()
    try:
        yield
    finally:
        runtime.reset()
        config.unset_flag("MXSAN")
        config.unset_flag("MXSAN_BLOCK_THRESHOLD_MS")


# ---------------------------------------------------------------------------
# racelint: the four checks fire on bad fixtures, stay quiet on good
# ---------------------------------------------------------------------------

BAD_UNGUARDED = """
import threading
class Counter:
    def __init__(self):
        self._lock = threading.Lock()
        self._n = 0
    def inc(self):
        with self._lock:
            self._n += 1
    def reset(self):
        self._n = 0
"""

GOOD_GUARDED = """
import threading
class Counter:
    def __init__(self):
        self._lock = threading.Lock()
        self._n = 0
    def inc(self):
        with self._lock:
            self._n += 1
    def reset(self):
        with self._lock:
            self._n = 0
"""

BAD_WAIT = """
import threading
class Box:
    def __init__(self):
        self._cv = threading.Condition()
        self._item = None
    def get(self):
        with self._cv:
            self._cv.wait()
            return self._item
"""

GOOD_WAIT_LOOP = """
import threading
class Box:
    def __init__(self):
        self._cv = threading.Condition()
        self._item = None
    def get(self):
        with self._cv:
            while self._item is None:
                self._cv.wait()
            return self._item
    def get2(self):
        with self._cv:
            self._cv.wait_for(lambda: self._item is not None)
            return self._item
"""

BAD_BLOCKING = """
import threading, time, subprocess
_LOCK = threading.Lock()
class Pump:
    def __init__(self):
        self._lock = threading.Lock()
        self._sock = None
        self._thread = None
    def poll(self):
        with self._lock:
            time.sleep(0.5)
    def pull(self):
        with self._lock:
            return self._sock.recv(4096)
    def stop(self):
        with self._lock:
            self._thread.join()
def run_tool():
    with _LOCK:
        subprocess.run(["true"])
"""

GOOD_BLOCKING = """
import threading, time
_LOCK = threading.Lock()
def outside():
    with _LOCK:
        n = 1
    time.sleep(0.01)          # after release: fine
    return ", ".join(["a"])   # string join is never blocking
"""

BAD_ENV = """
import os
def teardown(saved):
    os.environ["MXFOO"] = saved
    os.environ.pop("MXFOO", None)
"""

BAD_ENV_DEL = """
import os
def teardown(saved):
    try:
        os.environ["MXFOO"] = saved
        del os.environ["MXFOO"]
    finally:
        pass
"""

GOOD_ENV = """
import os
def teardown(saved):
    if saved is None:
        os.environ.pop("MXFOO", None)
    else:
        os.environ["MXFOO"] = saved
"""


def _checks(src, rel="fixture/mod.py"):
    return {f.check for f in racelint.lint_source(src, rel)
            if f.severity == "error"}


def test_unguarded_write_fires_and_good_spelling_clean():
    assert "unguarded-write" in _checks(BAD_UNGUARDED)
    assert not _checks(GOOD_GUARDED)


def test_wait_without_loop_fires_and_loop_or_wait_for_clean():
    assert "wait-without-predicate-loop" in _checks(BAD_WAIT)
    assert not _checks(GOOD_WAIT_LOOP)


def test_blocking_under_lock_fires_on_each_call_class():
    findings = [f for f in racelint.lint_source(BAD_BLOCKING, "f.py")
                if f.check == "blocking-under-lock"]
    msgs = " | ".join(f.message for f in findings)
    # sleep, socket recv, thread join, subprocess — all four shapes
    assert "time.sleep" in msgs
    assert "socket recv" in msgs
    assert "_thread.join" in msgs
    assert "subprocess.run" in msgs
    assert not _checks(GOOD_BLOCKING)


def test_restore_then_unset_fires_for_pop_and_del():
    assert "restore-then-unset" in _checks(BAD_ENV)
    assert "restore-then-unset" in _checks(BAD_ENV_DEL)
    assert not _checks(GOOD_ENV)


def test_init_writes_do_not_count_as_unguarded():
    # construction is single-threaded: __init__'s bare writes never
    # pair with guarded writes elsewhere into a finding
    assert not _checks("""
import threading
class C:
    def __init__(self):
        self._lock = threading.Lock()
        self._n = 0
    def inc(self):
        with self._lock:
            self._n += 1
""")


def test_caller_holds_lock_annotation_honored():
    # the repo's `# under self._lock` helper convention: the annotated
    # method is analyzed as guarded, so no unguarded-write — but a
    # blocking call inside it IS seen as under the lock
    src = """
import threading, time
class C:
    def __init__(self):
        self._lock = threading.Lock()
        self._n = 0
    def bump(self):
        with self._lock:
            self._bump()
            self._n += 1
    def _bump(self):
        # under self._lock
        self._n += 1
        time.sleep(0.1)
"""
    checks = _checks(src)
    assert "unguarded-write" not in checks
    assert "blocking-under-lock" in checks


def test_inline_mxsan_ok_suppresses():
    src = BAD_ENV.replace(
        'os.environ.pop("MXFOO", None)',
        'os.environ.pop("MXFOO", None)  # mxsan: ok')
    assert not _checks(src)


def test_exemption_registry_downgrades_to_info():
    fake = [f for f in racelint.lint_source(BAD_WAIT,
                                            "fixture/wait.py")]
    assert any(f.severity == "error" for f in fake)
    exemptions.EXEMPTIONS.append(
        ("fixture/wait.py", "wait-without-predicate-loop", "*",
         "test exemption"))
    try:
        out = exemptions.apply_exemptions(fake)
        waits = [f for f in out
                 if f.check == "wait-without-predicate-loop"]
        assert waits and all(f.severity == "info" for f in waits)
        assert all("[exempt: test exemption]" in f.message
                   for f in waits)
    finally:
        exemptions.EXEMPTIONS.pop()


def test_racelint_registered_in_default_manager():
    pm = default_manager()
    assert "racelint" in pm.names()
    # fixture duck-typing through the Pass protocol
    fired = {f.check for f in pm.get("racelint").run(
        {"sources": {"fixture/env.py": BAD_ENV}})}
    assert "restore-then-unset" in fired


def test_live_tree_lints_clean_modulo_exemptions():
    """The tier-1 gate: mxnet_tpu's own source has zero racelint
    errors; every suppressed site is a reviewed exemption (info)."""
    findings = racelint.lint_tree()
    errors = [f for f in findings if f.severity == "error"]
    assert not errors, "\n".join(repr(f) for f in errors)
    # the registry is in use, not dead weight: at least one reviewed
    # exemption actually matches a live site
    assert any("[exempt:" in f.message for f in findings)


# ---------------------------------------------------------------------------
# runtime sanitizer
# ---------------------------------------------------------------------------

def test_mxsan_off_returns_plain_primitives():
    """The zero-cost contract: with MXSAN=0 (default) the factories
    return the plain threading primitives — no wrapper, no overhead,
    bitwise-identical behavior."""
    assert type(runtime.make_lock("t.off")) is type(threading.Lock())
    assert type(runtime.make_rlock("t.off")) is type(threading.RLock())
    assert isinstance(runtime.make_condition("t.off"),
                      threading.Condition)
    assert not isinstance(runtime.make_condition("t.off"),
                          runtime.SanCondition)


def test_mxsan_on_returns_wrappers(mxsan_on):
    assert isinstance(runtime.make_lock("t.a"), runtime.SanLock)
    assert isinstance(runtime.make_rlock("t.b"), runtime.SanRLock)
    assert isinstance(runtime.make_condition("t.c"),
                      runtime.SanCondition)


def test_injected_cycle_detected_with_both_stacks(mxsan_on):
    a = runtime.make_lock("cyc.A")
    b = runtime.make_lock("cyc.B")

    def ab():
        with a:
            with b:
                pass

    def ba():
        with b:
            with a:
                pass

    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        for fn in (ab, ba):
            t = threading.Thread(target=fn)
            t.start()
            t.join()
    cycles = runtime.cycle_findings()
    assert len(cycles) == 1
    c = cycles[0]
    assert set(c["locks"]) == {"cyc.A", "cyc.B"}
    # BOTH nested-acquisition stacks, each pointing at its source line
    assert "in ab" in c["forward_stack"] or "in ba" in c["forward_stack"]
    assert c["reverse_stack"] is not None
    fwd, rev = {c["forward_stack"], c["reverse_stack"]}
    assert fwd != rev
    assert any("lock-order cycle" in str(x.message) for x in w)
    # ...and the finding surfaces through report() at error severity
    reps = [f for f in runtime.report()
            if f.check == "lock-order-cycle"]
    assert reps and reps[0].severity == "error"
    assert "cyc.A" in reps[0].message and "cyc.B" in reps[0].message


def test_consistent_order_produces_no_cycle(mxsan_on):
    a = runtime.make_lock("ord.A")
    b = runtime.make_lock("ord.B")
    for _ in range(3):
        with a:
            with b:
                pass
    assert runtime.cycle_findings() == []
    edges = {(e["src"], e["dst"]) for e in runtime.order_graph()}
    assert ("ord.A", "ord.B") in edges
    assert ("ord.B", "ord.A") not in edges


def test_rlock_reentry_records_no_self_edge(mxsan_on):
    r = runtime.make_rlock("re.R")
    with r:
        with r:  # reentrant: no edge, no second acquisition row
            assert runtime.held_locks() == ["re.R"]
    stats = runtime.lock_stats()["re.R"]
    assert stats["acquisitions"] == 1
    assert all(e["src"] != e["dst"] for e in runtime.order_graph())


def test_condition_wait_notify_roundtrip(mxsan_on):
    cv = runtime.make_condition("cv.box")
    items = []

    def consumer():
        with cv:
            while not items:
                cv.wait(1.0)

    t = threading.Thread(target=consumer)
    t.start()
    time.sleep(0.05)
    with cv:
        items.append(1)
        cv.notify_all()
    t.join(timeout=5.0)
    assert not t.is_alive()
    assert runtime.held_locks() == []
    assert runtime.lock_stats()["cv.box"]["acquisitions"] >= 2


def test_hold_and_contention_stats(mxsan_on):
    lk = runtime.make_lock("st.L")
    with lk:
        time.sleep(0.02)
    st = runtime.lock_stats()["st.L"]
    assert st["acquisitions"] == 1
    assert st["hold_ms_max"] >= 15.0

    def holder():
        with lk:
            time.sleep(0.05)

    t = threading.Thread(target=holder)
    t.start()
    time.sleep(0.01)
    with lk:   # contended acquire
        pass
    t.join()
    st = runtime.lock_stats()["st.L"]
    assert st["contentions"] >= 1
    assert st["wait_ms_max"] > 0.0


def test_export_to_registry_publishes_instruments(mxsan_on):
    from mxnet_tpu.telemetry import metrics as _m
    lk = runtime.make_lock("exp.L")
    with lk:
        pass
    n = runtime.export_to_registry()
    assert n >= 1
    live = _m.all_metrics()
    assert "mxsan_lock_hold_ms_exp_L" in live
    assert "mxsan_lock_acquisitions_exp_L" in live
    assert live["mxsan_lock_hold_ms_exp_L"].value()["count"] >= 1


def test_blocked_waiter_triggers_flight_dump(mxsan_on, tmp_path):
    config.set_flag("MXSAN_BLOCK_THRESHOLD_MS", 50.0)
    config.set_flag("MXTRACE_DUMP_DIR", str(tmp_path))
    try:
        lk = runtime.make_lock("blk.L")
        release = threading.Event()

        def holder():
            with lk:
                release.wait(5.0)

        t = threading.Thread(target=holder)
        t.start()
        time.sleep(0.02)
        t0 = time.monotonic()
        acquired = threading.Event()

        def waiter():
            with lk:
                acquired.set()

        w = threading.Thread(target=waiter)
        w.start()
        time.sleep(0.2)          # past the 50ms threshold
        release.set()
        w.join(timeout=5.0)
        t.join(timeout=5.0)
        assert acquired.is_set()  # the waiter DID get the lock
        assert time.monotonic() - t0 < 5.0
        ev = runtime.blocked_events()
        assert ev and ev[0]["lock"] == "blk.L"
        assert ev[0]["waited_ms"] >= 50.0
        assert ev[0]["holder_site"]          # the holder's acquire site
        assert "waiter" in ev[0]["waiter_stack"] \
            or "acquire" in ev[0]["waiter_stack"]
        dumps = [p for p in os.listdir(str(tmp_path))
                 if "mxsan-blocked-waiter" in p]
        assert dumps, "no flight-recorder dump was written"
        payload = json.loads(
            (tmp_path / dumps[0]).read_text())
        assert payload["extra"]["lock"] == "blk.L"
        # the warn-severity finding rides report()
        assert any(f.check == "blocked-waiter"
                   for f in runtime.report())
    finally:
        config.unset_flag("MXTRACE_DUMP_DIR")


def test_mxsan_off_serve_engine_uses_plain_locks():
    """MXSAN=0 neutrality, structurally: an engine constructed with
    the flag off carries plain primitives end to end (what makes the
    serving/step suites bitwise/no-recompile neutral — there is no
    wrapper anywhere to change behavior)."""
    assert not config.get("MXSAN")
    from mxnet_tpu.parallel.pipeline_lm import init_pipeline_lm
    from mxnet_tpu.serve2 import DecodeEngine
    params = init_pipeline_lm(0, vocab=32, d_model=16, n_layers=2,
                              n_heads=2, d_head=8, d_ff=32,
                              n_experts=2)
    e = DecodeEngine(params, page_size=4, num_pages=16,
                     max_inflight=2, prefill_buckets=[8],
                     max_new_default=2, max_seq_len=16,
                     name="<mxsan-off>")
    try:
        assert not isinstance(e._cv, runtime.SanLock)
        assert isinstance(e._cv, threading.Condition)
        assert type(e.alloc._lock) is type(threading.Lock())
        assert type(e.lm._lock) is type(threading.Lock())
    finally:
        e.close()


# ---------------------------------------------------------------------------
# the CLI gate
# ---------------------------------------------------------------------------

MXLINT = os.path.join(ROOT, "tools", "mxlint.py")


def test_cli_race_exits_zero_on_clean_tree():
    """`python tools/mxlint.py --race` — the tier-1 concurrency gate:
    live tree clean modulo exemptions, every fixture fires, the
    injected runtime cycle is detected."""
    proc = subprocess.run([sys.executable, MXLINT, "--race", "--json"],
                          cwd=ROOT, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, \
        proc.stdout[-2000:] + proc.stderr[-2000:]
    report = json.loads(proc.stdout)
    assert report["summary"]["error"] == 0
    assert report["summary"]["warn"] == 0
    # the reviewed exemptions surface as info — auditable, not hidden
    assert any("[exempt:" in f["message"] for f in report["findings"])
    assert any(f["check"] == "selfcheck-summary"
               for f in report["findings"])
