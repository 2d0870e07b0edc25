"""search_qps: queries answered per second, over all requests and the whole
window (host clock).  With one closed-loop client it is also the queries of
a request over its mean latency."""


def read(win):
    return win.units / win.window_s
