"""Data plane (``core/dataplane.py``): bytes the data plane moved per
served launch (``LaunchStats.data.h2d_bytes + d2h_bytes``: pieces
staged with their bucket pad and halo, broadcast operands, copy-backs),
over the bytes the launch must move by its reference (``nbytes`` of
``bench/refs/<kernel>.py``); the mean over the window's served launches.
A plane that counts no copies (USM) gives nothing."""
import statistics


def read(obs):
    cfg, ref = obs.cell.config, obs.cell.ref
    ratios = []
    for x in obs.window.served:
        moved = x.stats.data.h2d_bytes + x.stats.data.d2h_bytes
        need = ref.nbytes(cfg, obs.input_sets[x.input_set],
                          obs.answers[x.input_set])
        if moved > 0 and need > 0:
            ratios.append(moved / need)
    return statistics.fmean(ratios) if ratios else None
