"""Entry (``core/runtime.py``): mean host time per ``coexec.submit``
span, the program's span around ``launch_async``: the scheduler, the
output container, and ``engine.submit``'s plan, pre-warm and offer."""
import statistics


def read(obs):
    if obs.trace is None:
        return None
    spans = [e - s for name, s, e in obs.trace.host
             if name == "coexec.submit"]
    return 1e-6 * statistics.fmean(spans) if spans else None
