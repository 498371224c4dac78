"""``setup_s``: seconds from the start of the process to the start of the
window: imports, inputs, the warm-up call and, on a cell's first run in
a checkout, compilation."""


def read(window):
    return window.setup_s
