"""Percent of the traced window in which no operation ran on the chip:
1 - busy / window, busy being the union of the device's op intervals."""


def read(run):
    return None if run.trace is None else run.trace.idle_share()
