"""The device's idle share of the traced window: 1 - the union of its
operations' intervals over the window, on the device that idled most."""


def read(params: dict, ctx: dict):
    trace = ctx["trace"]
    if trace is None or not trace.planes or not trace.window_s:
        return None
    return 100.0 * trace.idle_share()
