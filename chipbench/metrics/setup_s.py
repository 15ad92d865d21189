"""Set-up: process start to the first timed operation (host clock).

Generating the table and the forest, loading the table into the store,
compiling (or loading from the compile cache) and warming every shape
the cell's traffic uses.
"""


def read(run):
    return run.setup_s
