"""Kernels: device time a step of every event that holds neither a
convolution nor a matrix product: normalisation, softmax, activation,
relayout copies, the optimizer's update."""


def read(run):
    s = run.summary
    if not s or not s["steps"]:
        return None
    other = sum(sec for c, sec in s["class_seconds"].items() if c != "mxu")
    return 1e3 * other / s["steps"]
