"""Host milliseconds per job in XLA compiles or persistent-cache loads
(the ``jax.compile`` spans under ``engine.dispatch``)."""
from metrics._program_spans import total_ms


def read(run):
    return total_ms(run, "jax.compile", "engine.dispatch")
