"""Set-up: process start to the window's first job or request
(generation, upload, engine, executables loaded or compiled, the cell's
shapes warmed)."""


def read(run):
    return run.setup_s
