"""Control plane (``core/engine.py``): host time the workers spent
waiting to take the engine lock, the program's ``coexec.lock`` spans,
over the ``coexec.issue`` spans, one a package."""


def read(obs):
    if obs.trace is None:
        return None
    host = obs.trace.host
    issued = sum(1 for name, _, _ in host if name == "coexec.issue")
    if not issued:
        return None
    return 1e-6 * sum(e - s for name, s, e in host
                      if name == "coexec.lock") / issued
