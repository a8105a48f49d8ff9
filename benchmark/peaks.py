"""The table of peaks, keyed by ``device_kind``. A device that is not in
``peaks.json`` is an error, never a default."""


def lookup(table, device_kind):
    for entry in table["devices"]:
        if entry["device_kind"] == device_kind:
            return entry
    raise KeyError(f"device_kind {device_kind!r} is not in "
                   "benchmark/peaks.json; add it with its source, do not "
                   "guess")
