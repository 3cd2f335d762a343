"""Control plane (``core/exec.py`` under the engine lock): host time in
the program's ``coexec.pull`` and ``coexec.retire`` spans, the loop's
pull and completion bookkeeping, over the ``coexec.issue`` spans, one a
package."""

HELD = ("coexec.pull", "coexec.retire")


def read(obs):
    if obs.trace is None:
        return None
    host = obs.trace.host
    issued = sum(1 for name, _, _ in host if name == "coexec.issue")
    if not issued:
        return None
    return 1e-6 * sum(e - s for name, s, e in host if name in HELD) / issued
