"""Share of the chip's memory bandwidth that the probes' scan needs, in
percent: the bytes a scan has to read (codes of the leaves scanned, raw
rows verified; ``roofline.scan_bytes`` from each call's counts) at the
chip's peak bandwidth, over the device's busy time in the window.  It
cannot pass 100: the device reads at least those bytes while busy."""
import roofline


def read(run):
    if run.kind != "probe" or run.peaks is None:
        return None
    busy = run.busy_s()
    if not busy:
        return None
    c = run.config
    nbytes = sum(roofline.scan_bytes(
        k["counts"]["leaves_scanned"], k["counts"]["candidates"],
        leaf_size=c["leaf_size"], segments=c["segments"],
        series_len=c["series_len"], rows=c["series"])
        for k in run.calls if not k.get("error"))
    return 100.0 * nbytes / run.peaks["hbm_bytes_per_s"] / busy
