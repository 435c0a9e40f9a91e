"""Share of the traced window in which no operation ran on the device, in
percent: one minus the union of the device op intervals over the window."""


def read(run):
    return run.idle_pct()
