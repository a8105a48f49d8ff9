"""Kernels: the least time the chip could take over the model's
convolutions and matrix products, divided by the device time a step of
the trace's events that hold a convolution or a dot (by the step's own
HLO), in percent. The least time is summed layer by layer: for each, the
larger of its needed FLOPs over the bf16 peak and its needed bytes over
the HBM rate (``matrix_layers`` of the family, ``peaks.json``); so a 1x1
convolution that memory binds and a 3x3 one that compute binds each
count by their own roof. ``roofs`` says how much of that time each roof
gave.

The share reads high exactly when an event that does matrix work is
classed with the others. So the reader first adds up, by the step's own
HLO, the FLOPs of the events it took as matrix work
(``trace_reduce.hlo_flops``) and fails where they do not reach the FLOPs
the model needs: the classification, not the kernels, is then at fault.
A Mosaic kernel's FLOPs the HLO does not tell; a step that holds one is
not checked this way."""
from benchmark import peaks

SHORT = 0.995  # of the needed FLOPs: below it an event is misclassed


def roofs(run):
    """``(seconds a step under the compute roof, under the memory
    roof)``, over the layers each of them binds."""
    peak = peaks.lookup(run.peaks, run.device.device_kind)
    compute = memory = 0.0
    for _, flops, nbytes in run.family.matrix_layers(run.sizes,
                                                     run.traffic):
        c = flops / peak["bf16_flops"]
        m = nbytes / peak["hbm_bytes_per_s"]
        if c >= m:
            compute += c
        else:
            memory += m
    return compute, memory


def read(run):
    s = run.summary
    if not s or not s["steps"]:
        return None
    mxu_s = s["class_seconds"].get("mxu")
    if not mxu_s:
        return None
    need = run.family.needed_flops(run.sizes, run.traffic)
    if not s["mxu_unknown"] and s["mxu_hlo_flops"] < SHORT * need:
        raise RuntimeError(
            f"the traced events classed as matrix work hold "
            f"{s['mxu_hlo_flops']:.4g} FLOPs a step by the HLO, the model "
            f"needs {need:.4g}: an event that does matrix work is classed "
            "with the others")
    return 100.0 * sum(roofs(run)) / (mxu_s / s["steps"])
