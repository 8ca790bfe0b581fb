"""Host milliseconds per job building the fused delivery layout (the
program's ``engine.layout_build`` spans; 0 when every job finds it
cached)."""
from metrics._program_spans import total_ms


def read(run):
    return total_ms(run, "engine.layout_build")
