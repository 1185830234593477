"""Set-up: seconds JAX spent tracing the served plans' whole-forward
programs and lowering them to MLIR, from the program's compile observer
(``exec.run.plan_compile_stats``).  No plan compiles inside the window
(``compiles_in_window``), so this is the ladder warm's share of
``ladder_warm_s``.  None from a program without the observer."""


def read(run):
    try:
        from repro.exec.run import plan_compile_stats
    except ImportError:
        return None
    s = plan_compile_stats()
    return s["trace_s"] + s["lower_s"]
