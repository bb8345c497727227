"""Seconds from process start to the first operation of the timed window:
import, data, store, device, compile and warm-up (host clock)."""


def read(run):
    return run.setup_s
