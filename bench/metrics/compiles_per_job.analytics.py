"""Compiles or persistent-cache loads per job: the number of
``jax.compile`` spans under ``engine.dispatch`` over the jobs."""
from metrics._program_spans import named, window_spans


def read(run):
    spans = window_spans(run)
    if spans is None:
        return None
    return len(named(spans, "jax.compile", "engine.dispatch")) / len(
        run.driver.jobs)
