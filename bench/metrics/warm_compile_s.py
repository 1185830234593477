"""Set-up: seconds of XLA compiling the served plans' whole-forward
programs, or loading them from the persistent cache on a hit, from the
program's compile observer (``exec.run.plan_compile_stats``).  No plan
compiles inside the window (``compiles_in_window``), so this is the
ladder warm's share of ``ladder_warm_s``.  None from a program without
the observer."""


def read(run):
    try:
        from repro.exec.run import plan_compile_stats
    except ImportError:
        return None
    return plan_compile_stats()["compile_s"]
