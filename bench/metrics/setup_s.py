"""setup_s: seconds from the process's start to the end of warm-up:
imports, the catalog made on the device, every compile or cache load, and
the warm-up passes."""


def read(run):
    return run.setup_s
