"""amg_setup_s: wall seconds of the program's set-up call (upload and
hierarchy), less the seconds JAX spent tracing and compiling inside it."""


def read(run):
    return run.setup["program_s"] - run.setup["program_compile_s"]
