"""setup_s: seconds from the start of the run to the start of the window:
making the inputs, the program's set-up, loading or compiling its
programs, handing over the right-hand sides and the warm-up solve."""


def read(run):
    return run.setup["total_s"]
