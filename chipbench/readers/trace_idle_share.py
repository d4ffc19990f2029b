"""Share of the traced window in which no op ran on the device, in
percent: 1 - busy_s / window_s, as ``device`` reports them."""


def read(ctx: dict, spec: dict):
    tr = ctx["trace"]
    return 100.0 * (1.0 - tr.busy_s() / tr.window_s)
