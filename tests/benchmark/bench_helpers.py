"""Shared by the benchmark's tests: where the checkout is, and how to
run the harness in a process of its own (it sets environment variables
and jax's cache configuration, which a test worker must not inherit)."""
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def manifest(root=ROOT):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load(*parts, root=ROOT):
    with open(os.path.join(root, *parts)) as f:
        return json.load(f)


def run_harness(args, root=ROOT, script=None, timeout=600):
    """Run ``benchmark/run.py`` (or ``script``) with ``args`` from
    ``root`` on the CPU; returns (return code, last stdout line parsed
    or None, stderr)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, script or os.path.join(root, "benchmark",
                                                  "run.py")] + list(args)
    p = subprocess.run(cmd, cwd=root, env=env, capture_output=True,
                       text=True, timeout=timeout)
    lines = [l for l in p.stdout.splitlines() if l.strip()]
    try:
        last = json.loads(lines[-1]) if lines else None
    except ValueError:
        last = None
    return p.returncode, last, p.stderr
