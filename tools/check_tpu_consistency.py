#!/usr/bin/env python
"""CPU-jax vs TPU-jax operator consistency sweep.

The reference's main cross-backend oracle is check_consistency run by
tests/python/gpu/test_operator_gpu.py (same op on cpu+gpu, outputs
compared). This is the TPU analog as a standalone tool — it must run
OUTSIDE the test suite because tests/conftest.py forces the CPU
platform. One process opens the chip (it belongs to one at a time);
without an accelerator it reports that and exits 1. Emits one JSON line.

Usage: python tools/check_tpu_consistency.py [--ops a,b,c] [--json]

--json swaps the one-line metric for the machine-readable findings
report shared with mxlint and flakiness_checker --json (one finding per
mismatching op).
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import numpy as onp  # noqa: E402


def _cases(rs):
    """name -> (fn_name, inputs, kwargs). Inputs sized to hit the MXU
    tiles (multiples of 8/128 where it matters)."""
    B = {
        "relu": (["T(64, 128)"], {}),
        "sigmoid": (["T(64, 128)"], {}),
        "tanh": (["T(64, 128)"], {}),
        "exp": (["T(64, 128)"], {}),
        "softmax": (["T(32, 128)"], {"axis": -1}),
        "log_softmax": (["T(32, 128)"], {"axis": -1}),
        "sum": (["T(16, 64, 32)"], {"axis": (1,)}),
        "mean": (["T(16, 64, 32)"], {"axis": (0, 2)}),
        "max": (["T(16, 64)"], {"axis": 1}),
        "argmax": (["T(16, 64)"], {"axis": 1}),
        "dot": (["T(64, 128)", "T(128, 96)"], {}),
        "batch_dot": (["T(8, 32, 64)", "T(8, 64, 48)"], {}),
        "elemwise_add": (["T(64, 128)", "T(64, 128)"], {}),
        "broadcast_mul": (["T(64, 128)", "T(1, 128)"], {}),
        "transpose": (["T(32, 64, 16)"], {"axes": (2, 0, 1)}),
        "take": (["T(128, 32)", "I(64, hi=128)"], {}),
        "one_hot": (["I(64, hi=32)"], {"depth": 32}),
        "topk": (["T(16, 128)"], {"k": 8, "ret_typ": "value"}),
        "sort": (["T(16, 128)"], {"axis": -1}),
        "LayerNorm": (["T(32, 128)", "T(128)", "T(128)"], {}),
        "FullyConnected": (["T(32, 64)", "T(48, 64)", "T(48)"],
                           {"num_hidden": 48}),
        "Convolution": (["T(4, 8, 28, 28)", "T(16, 8, 3, 3)", "T(16)"],
                        {"kernel": (3, 3), "num_filter": 16}),
        "Pooling": (["T(4, 8, 28, 28)"],
                    {"kernel": (2, 2), "pool_type": "max",
                     "stride": (2, 2)}),
        "BatchNorm": (["T(8, 16, 14, 14)", "T(16)", "T(16)", "T(16)",
                       "T(16, lo=0.5, hi=1.5)"], {"fix_gamma": False}),
    }

    def T(*shape, lo=-1.0, hi=1.0):
        return rs.uniform(lo, hi, shape).astype("float32")

    def I(*shape, hi=8):
        return rs.randint(0, hi, shape).astype("float32")

    env = {"T": T, "I": I}
    out = {}
    for name, (specs, kwargs) in B.items():
        out[name] = ([eval(s, env) for s in specs], kwargs)  # noqa: S307
    return out


# ops whose outputs are legitimately device-dependent get a structural
# comparison (shape/dtype/finiteness) instead of a numerical one: the
# registry's needs_rng flag marks every sampler/dropout-style op (each
# draws from the backend threefry stream), plus one non-RNG special case
_DEVICE_DEPENDENT_EXTRA = {
    "_contrib_boolean_mask",  # size-dependent host sync ordering
}


def _is_device_dependent(name, info):
    return getattr(info, "needs_rng", False) \
        or name in _DEVICE_DEPENDENT_EXTRA


def _registry_sweep(args, jax, cpu_dev, accel):
    """CPU-vs-accel sweep over EVERY unique registered op (VERDICT r3
    item 5 — the reference's test_operator_gpu.py check_consistency
    role). Reuses the curated per-op input corpus from
    tests/test_op_sweep.py; inputs are snapshotted to numpy once so both
    devices compute on identical data. Writes one report line per op
    (op, max_abs_err, tolerance, status) to --report."""
    sys.path.insert(0, os.path.join(ROOT, "tests"))
    import test_op_sweep as sweep  # noqa: E402
    from mxnet_tpu import nd
    from mxnet_tpu.ndarray.ndarray import array

    report = []
    ops = sorted(sweep._unique_ops(), key=lambda kv: kv[0])
    for name, info in ops:
        if name in sweep.SKIP:
            report.append({"op": name, "status": "skip",
                           "reason": sweep.SKIP[name]})
            continue
        case = sweep.CASES.get(name)
        try:
            if case is not None:
                args0, params = case()
            else:
                args0, params = ([sweep.T(2, 3, 4) for _ in
                                  range(sweep._n_required(info))], {})
            snap = [(a.asnumpy() if hasattr(a, "asnumpy") else a)
                    for a in args0]
        except Exception as e:  # noqa: BLE001
            report.append({"op": name, "status": "input_error",
                           "error": f"{type(e).__name__}: {str(e)[:120]}"})
            continue
        fn = getattr(nd, name)
        entry = {"op": name, "rtol": args.rtol, "atol": args.atol}
        try:
            outs = {}
            for label, dev in (("cpu", cpu_dev), ("accel", accel)):
                with jax.default_device(dev):
                    vals = fn(*[array(a) if isinstance(a, onp.ndarray)
                                else a for a in snap], **params)
                    vals = vals if isinstance(vals, (list, tuple)) \
                        else [vals]
                    outs[label] = [onp.asarray(v.asnumpy()) for v in vals]
            max_err = 0.0
            for c, t in zip(outs["cpu"], outs["accel"]):
                if _is_device_dependent(name, info):
                    assert c.shape == t.shape and c.dtype == t.dtype
                    if onp.issubdtype(t.dtype, onp.floating):
                        assert onp.isfinite(t).all()
                    continue
                if onp.issubdtype(c.dtype, onp.floating):
                    max_err = max(max_err,
                                  float(onp.max(onp.abs(
                                      c.astype("float64")
                                      - t.astype("float64")))
                                      if c.size else 0.0))
                    onp.testing.assert_allclose(c, t, rtol=args.rtol,
                                                atol=args.atol)
                else:
                    onp.testing.assert_array_equal(c, t)
            entry.update(status="pass", max_abs_err=round(max_err, 8),
                         device_dependent=_is_device_dependent(name, info))
        except Exception as e:  # noqa: BLE001 — report, don't abort
            entry.update(status="fail",
                         error=f"{type(e).__name__}: {str(e)[:160]}")
        report.append(entry)

    n_pass = sum(1 for r in report if r["status"] == "pass")
    # input_error counts as a FAILURE: an op whose inputs cannot be
    # built was never compared, and a green sweep must not hide that
    n_fail = [r["op"] for r in report
              if r["status"] in ("fail", "input_error")]
    n_skip = sum(1 for r in report if r["status"] == "skip")
    with open(args.report, "w") as f:
        json.dump({"metric": "tpu_registry_consistency",
                   "passed": n_pass, "failed": n_fail, "skipped": n_skip,
                   "total": len(report), "self_test": args.self_test,
                   "report": report}, f, indent=1)
    if args.as_json:
        print(_findings_json(
            [(r["op"], r.get("error", r["status"])) for r in report
             if r["status"] in ("fail", "input_error")],
            extra={"metric": "tpu_registry_consistency", "passed": n_pass,
                   "total": len(report), "skipped": n_skip,
                   "report_path": args.report}))
    else:
        print(json.dumps({"metric": "tpu_registry_consistency",
                          "value": n_pass, "total": len(report),
                          "failed": n_fail[:20], "n_failed": len(n_fail),
                          "report_path": args.report}))
    return 0 if not n_fail else 2


def _findings_json(failed_pairs, extra):
    """The shared machine-readable findings schema (mxnet_tpu.passes
    findings_report): one error finding per mismatching op."""
    from mxnet_tpu.passes import Finding, findings_report
    findings = [
        Finding("consistency", "cpu-accel-mismatch", op, "error",
                f"op '{op}' disagrees between cpu and accelerator: {msg}")
        for op, msg in failed_pairs]
    return findings_report("check_tpu_consistency", findings, extra=extra,
                           as_json=True)


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--ops", default=None)
    p.add_argument("--rtol", type=float, default=2e-2)  # bf16-tolerant
    p.add_argument("--atol", type=float, default=2e-2)
    p.add_argument("--self-test", action="store_true",
                   help="compare cpu against cpu (validates the harness "
                        "without an accelerator)")
    p.add_argument("--registry", action="store_true",
                   help="sweep EVERY unique registered op (the full "
                        "cross-backend oracle) instead of the curated "
                        "MXU-sized case list")
    p.add_argument("--report", default=os.path.join(
        ROOT, "CONSISTENCY_SWEEP.json"),
        help="where --registry writes the per-op report artifact")
    p.add_argument("--json", action="store_true", dest="as_json",
                   help="emit the shared machine-readable findings report")
    args = p.parse_args(argv)

    if args.self_test:
        os.environ["JAX_PLATFORMS"] = "cpu"
        import jax
        jax.config.update("jax_platforms", "cpu")
    else:
        import jax
        if all(d.platform == "cpu" for d in jax.devices()):
            print(json.dumps({"metric": "tpu_consistency", "value": None,
                              "total": 0, "failed": [],
                              "error": "accelerator unavailable"}))
            return 1

    from mxnet_tpu import nd
    from mxnet_tpu.ndarray.ndarray import array

    cpu_dev = jax.local_devices(backend="cpu")[0]
    accel = cpu_dev if args.self_test else \
        [d for d in jax.devices() if d.platform != "cpu"][0]

    if args.registry:
        return _registry_sweep(args, jax, cpu_dev, accel)

    rs = onp.random.RandomState(0)
    cases = _cases(rs)
    selected = args.ops.split(",") if args.ops else sorted(cases)
    unknown = [s for s in selected if s not in cases]
    if unknown:
        print(json.dumps({"metric": "tpu_consistency", "value": None,
                          "total": 0, "failed": [],
                          "error": f"unknown ops {unknown}; "
                                   f"choices: {sorted(cases)}"}))
        return 1
    passed, failed = [], []
    for name in selected:
        inputs, kwargs = cases[name]
        fn = getattr(nd, name)
        try:
            outs = {}
            for label, dev in (("cpu", cpu_dev), ("tpu", accel)):
                with jax.default_device(dev):
                    vals = fn(*[array(a) for a in inputs], **kwargs)
                    vals = vals if isinstance(vals, (list, tuple)) \
                        else [vals]
                    outs[label] = [onp.asarray(v.asnumpy()) for v in vals]
            for c, t in zip(outs["cpu"], outs["tpu"]):
                onp.testing.assert_allclose(c, t, rtol=args.rtol,
                                            atol=args.atol)
            passed.append(name)
        except Exception as e:  # noqa: BLE001 — report, don't abort
            failed.append(f"{name}: {type(e).__name__}: {str(e)[:120]}")
    if args.as_json:
        print(_findings_json(
            [(f.split(":")[0], f.split(":", 1)[1].strip()) for f in failed],
            extra={"metric": "tpu_consistency", "passed": len(passed),
                   "total": len(selected)}))
    else:
        print(json.dumps({"metric": "tpu_consistency",
                          "value": len(passed), "total": len(selected),
                          "failed": failed}))
    return 0 if not failed else 2


if __name__ == "__main__":
    sys.exit(main())
