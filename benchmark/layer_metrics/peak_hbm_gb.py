"""Device: ``memory_stats()["peak_bytes_in_use"]`` after the window, in
GB (1e9 bytes). Recorded, never judged. Nothing on a backend that does
not report it."""


def read(run):
    peak = run.result["memory_peak_bytes"]
    if not peak or run.device.platform != "tpu":
        return None
    return peak / 1e9
