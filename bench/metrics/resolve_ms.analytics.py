"""Host milliseconds per job in ``Engine.run``'s resolve of the design
point (the program's ``engine.resolve`` spans)."""
from metrics._program_spans import total_ms


def read(run):
    return total_ms(run, "engine.resolve")
