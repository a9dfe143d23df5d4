"""engine.idle_ms.online (ms/step): device-idle time inside the window under
the program's ``engine.*`` spans (``LiraEngine.search``: prepare, dispatch,
wait, post), per serve step."""
from lirabench.span_gaps import idle_ms_per_step


def read(run):
    return idle_ms_per_step(run, "engine", "engine.search")
