"""Smoke tier for the example families no sibling file holds (ref: the
reference's example/ breadth: multi-task, recommenders, sparse,
bayesian-methods, model-parallel, svm_mnist, numpy-ops, profiler,
svrg_module, reinforcement-learning, dsd, amp, lib_api; the CTC, vision,
generative and text families are test_examples_{ctc,vision,generative,
text}.py, so that no file's cases sum to more than the suite can spare
one worker: ROADMAP.md D13). Each runs end to end with tiny settings and
asserts its learning signal."""
import os


def test_multi_task_example(load_example):
    acc_c, acc_p = load_example("multi_task/multitask.py").main(
        ["--steps", "150"])
    assert acc_c > 0.7 and acc_p > 0.7


def test_recommender_matrix_fact_example(load_example):
    first, last = load_example("recommenders/matrix_fact.py").main(
        ["--steps", "200"])
    assert last < first * 0.8


def test_sparse_linear_classification_example(load_example):
    first, last, untouched = load_example(
        "sparse/linear_classification.py").main(["--epochs", "6"])
    assert last < first * 0.5 and untouched


def test_sgld_posterior_example(load_example):
    est, post_mean, err = load_example("bayesian_methods/sgld.py").main(
        ["--steps", "800", "--burn-in", "200"])
    assert err < 0.2


def test_model_parallel_pjit_example(load_example):
    first, last = load_example("model_parallel/pjit_mlp.py").main(
        ["--steps", "40", "--mp", "4"])
    assert last < first * 0.1


def test_svm_output_example_trains(load_example):
    score = load_example("svm_mnist/svm_mnist.py").main(["--epochs", "4"])
    assert score[0][1] > 0.9


def test_svm_l1_variant_trains(load_example):
    score = load_example("svm_mnist/svm_mnist.py").main(
        ["--epochs", "4", "--l1"])
    assert score[0][1] > 0.9


def test_custom_op_example_trains(load_example):
    score = load_example("numpy_ops/custom_softmax.py").main(["--epochs", "4"])
    assert score[0][1] > 0.9


def test_profiler_example_emits_trace(load_example):
    trace, n_events, stats = load_example(
        "profiler_demo/profile_model.py").main(["--steps", "3"])
    assert os.path.exists(trace) and n_events > 0
    assert "Time" in stats or "time" in stats


def test_svrg_example(load_example):
    mse = load_example("svrg/svrg_train.py").main(["--epochs", "6"])
    assert mse < 0.05


def test_reinforce_example_improves(load_example):
    first, final = load_example("reinforcement_learning/reinforce.py").main(
        ["--episodes", "200"])
    assert final > first + 0.2


def test_dsd_example_mask_holds(load_example):
    acc_d, acc_s, acc_r = load_example("dsd/dsd_train.py").main(
        ["--phase-steps", "80"])
    assert acc_s > 0.8 and acc_r > 0.8  # survives 70% pruning


def test_amp_example_trains(load_example):
    acc = load_example("amp/amp_train.py").main(["--steps", "150"])
    assert acc > 0.8


def test_extension_lib_example(load_example):
    """Runtime operator-extension loading (ref: example/lib_api):
    loaded ops behave like built-ins under nd and autograd. The
    registry is restored afterwards — a leaked extension op would be
    picked up by the registry-wide sweep with generic inputs."""
    import mxnet_tpu.ndarray as nd_mod
    import mxnet_tpu.symbol as sym_mod
    from mxnet_tpu import library
    from mxnet_tpu.ops.registry import _OPS
    before = set(_OPS)
    loaded_before = dict(library._LOADED)
    try:
        assert load_example("extension_lib/consume.py").main([]) is True
    finally:
        for name in set(_OPS) - before:
            _OPS.pop(name, None)
            # the nd/sym namespaces memoize generated wrappers on first
            # attribute access; drop those too or the op stays callable
            for mod in (nd_mod, sym_mod):
                if hasattr(mod, name):
                    delattr(mod, name)
        library._LOADED.clear()
        library._LOADED.update(loaded_before)
