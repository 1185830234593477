"""Host dispatch: host time per batch outside ``bench.wait`` — the
``bench.pop``, ``bench.prepare``, ``bench.dispatch`` and ``bench.fetch``
spans of the traced window over its batches, in ms."""

SPANS = ("bench.pop", "bench.prepare", "bench.dispatch", "bench.fetch")


def read(run):
    t = run.trace
    if t is None or not t.span_count("bench.dispatch"):
        return None
    return sum(t.span_s(s) for s in SPANS) / t.span_count(
        "bench.dispatch") * 1e3
