"""Host dispatch: the mean ``bench.dispatch`` span of the traced window,
in ms.  The span holds exactly the window's call of
``exec.execute_plan`` (the program's ``repro.execute_plan`` span lies
inside it): its checks, hashing the static plan and the jit dispatch,
up to the call's return, before ``bench.wait`` blocks on the result."""

SPAN = "bench.dispatch"


def read(run):
    t = run.trace
    if t is None or not t.span_count(SPAN):
        return None
    return t.span_s(SPAN) / t.span_count(SPAN) * 1e3
