"""take_rows_s: rows returned per second, over all requests and the whole
window (host clock)."""


def read(win):
    return win.units / win.window_s
