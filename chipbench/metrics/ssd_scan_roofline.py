"""Share of the roofline of the Pallas `ssd_scan` forward (the backward is
XLA's recomputed oracle VJP and is not counted), from the traced tail."""
import roofline


def read(run):
    return roofline.share(run, "ssd_scan")
