"""Persistent-compile-cache misses of the run (``training/warmup.cache_stats``)."""


def read(run, spec):  # noqa: ARG001
    return run.get("cache_misses")
