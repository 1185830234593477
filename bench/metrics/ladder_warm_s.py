"""Set-up: seconds to compile every ladder tier's plan
(``exec.compile_plan``), compile it with XLA and run it twice."""


def read(run):
    return run.setup["ladder_warm_s"]
