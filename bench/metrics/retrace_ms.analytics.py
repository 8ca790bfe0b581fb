"""Host milliseconds per job in which JAX traces and lowers the job's
programs: the union of the ``jax.trace`` and ``jax.lower`` spans under
``engine.dispatch`` (a trace nested in another counts once)."""
from metrics._program_spans import union_ms


def read(run):
    return union_ms(run, ("jax.trace", "jax.lower"), "engine.dispatch")
