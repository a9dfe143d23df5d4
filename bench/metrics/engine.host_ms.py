"""engine.host_ms (ms/call): host time of ``LiraEngine.search`` around the
device call, from the program's ``engine.prepare`` and ``engine.post`` spans."""


def read(run):
    if run.tracer is None:
        return None
    calls = run.tracer.finished("engine.search")
    if not calls:
        return None
    host = sum(s.duration_ms for n in ("engine.prepare", "engine.post")
               for s in run.tracer.finished(n))
    return host / len(calls)
