"""Units (``core/units.py``): mean host time per ``coexec.issue`` span,
the program's own span around ``JaxUnit.dispatch``: the package's offset
scalar and the executable call."""
import statistics


def read(obs):
    if obs.trace is None:
        return None
    spans = [e - s for name, s, e in obs.trace.host if name == "coexec.issue"]
    return 1e-6 * statistics.fmean(spans) if spans else None
