"""Programs XLA compiled, the persistent cache having had no answer, in the
process that holds the chip up to the window's open.  0 in a warm run; a
run where it is not is a compiling run."""

from benchmark import startup


def read(run: dict):
    return startup.programs_compiled(run)
