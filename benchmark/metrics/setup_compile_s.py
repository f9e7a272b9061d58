"""setup_compile_s: seconds JAX spent tracing, lowering and compiling
during the whole set-up (its monitoring events), the warm-up solve's
compile included; a warm persistent cache leaves the tracing."""


def read(run):
    return run.setup["compile_s"]
