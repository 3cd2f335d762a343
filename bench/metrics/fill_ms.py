"""Data plane (``core/dataplane.py``): mean host time per package in the
program's ``coexec.fill`` spans, BUFFERS' fill of the host scratch of
each split argument of each piece (interior rows, halo rows, zero edges,
bucket pad) before its ``device_put``, over the ``coexec.issue`` spans,
one a package."""


def read(obs):
    if obs.trace is None:
        return None
    host = obs.trace.host
    issued = sum(1 for name, _, _ in host if name == "coexec.issue")
    fills = [e - s for name, s, e in host if name == "coexec.fill"]
    if not issued or not fills:
        return None
    return 1e-6 * sum(fills) / issued
