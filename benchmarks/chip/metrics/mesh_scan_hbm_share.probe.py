"""Share of the chips' memory bandwidth that the mesh launch needs, in
percent: the bytes the launch has to read, summed over the window's
calls (:func:`launch_bytes`), at ``chips`` times the chip's peak
bandwidth over the mean per-chip device time of the launch program
(``jit_mesh_scan``).  Each chip reads at least the bytes of its own
rows while that program runs, so the share cannot pass 100."""

import re

PROGRAM = "jit_mesh_scan"


def launch_bytes(rows_pinned: int, rows_verified: int, *, segments: int,
                 series_len: int) -> int:
    """Bytes one launch has to read from device memory at the least:
    the one-byte SAX code of every segment of every real row pinned,
    and the float32 series of every row verified."""
    return rows_pinned * segments + rows_verified * series_len * 4


def read(run):
    if run.kind != "probe" or run.peaks is None \
            or run.device_programs is None:
        return None
    lo, hi = run.window
    planes = [p for p in run.device_programs.values()
              if any(re.sub(r"\(\d+\)$", "", n) == PROGRAM
                     and s >= lo and e <= hi
                     for n, s, e in p)]
    total = sum(run.program_times(PROGRAM))
    if not planes or not total:
        return None
    c = run.config
    nbytes = sum(launch_bytes(k["counts"]["rows_pinned"],
                              k["counts"]["rows_verified"],
                              segments=c["segments"],
                              series_len=c["series_len"])
                 for k in run.calls if not k.get("error"))
    mean_s = total / len(planes)
    return 100.0 * nbytes / (len(planes) * run.peaks["hbm_bytes_per_s"]
                             * mean_s)
