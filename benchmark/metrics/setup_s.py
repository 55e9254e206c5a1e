"""Set-up: from process start to the window's open (JAX and native
start, data, deployment, sealing, warm-up, compiles). Host clock."""


def read(run):
    return run["setup_s"]
