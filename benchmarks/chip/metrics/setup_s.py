"""setup_s: seconds from the start of the process to the opening of the
measured window: imports, building the system, weights, warming every shape
the cell's traffic uses (compiling, or loading from the persistent cache)."""


def read(rec):
    return rec.setup_s
