"""device_idle_pct.take: share of the traced window in which no
operation ran on the chip (1 minus the union of the device operations of the
trace's XLA Ops line over the window), in percent."""


def read(view):
    return 100.0 * (1.0 - view.busy_s / view.window_s)
