"""Seconds of set-up spent making the executables: compiling, or reading and
deserialising from the persistent cache (``pipeline_s["load"]``; the cache's
own share, ``cache_retrieval``, stays in the scrape)."""
from benchmarks.setup_pipeline import pipeline_seconds


def read(run):
    return pipeline_seconds("load")
