"""Admission (``core/admission.py``): mean time from a launch's offer to
its first package's issue (``LaunchStats.t_first_issue - t_submit``),
over the window's served launches."""
import statistics


def read(obs):
    if obs.trace is None:
        return None
    waits = [x.stats.t_first_issue - x.stats.t_submit
             for x in obs.window.served
             if getattr(x.stats, "t_first_issue", None) is not None]
    return 1e3 * statistics.fmean(waits) if waits else None
