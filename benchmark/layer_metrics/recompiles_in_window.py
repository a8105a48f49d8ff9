"""Step program: ``telemetry.recompile_count()`` after the window less
before it. 0 is the only healthy reading."""


def read(run):
    return run.result["counters"]["window"].get("recompiles")
