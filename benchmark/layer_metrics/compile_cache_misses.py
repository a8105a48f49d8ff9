"""Compile cache: ``jax_compile_cache_misses_total`` at the end of
set-up: the programs this run compiled anew. 0 in every run of a cell
after its first in a checkout."""


def read(run):
    return run.result["counters"]["setup"].get("compile_cache_misses")
