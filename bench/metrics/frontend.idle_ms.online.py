"""frontend.idle_ms.online (ms/step): device-idle time inside the window
under the front-end's ``frontend.*`` spans and outside every ``engine.*``
span (assembling a batch, scattering its answers), per serve step."""
from lirabench.span_gaps import idle_ms_per_step


def read(run):
    return idle_ms_per_step(run, "frontend", "frontend.batch")
