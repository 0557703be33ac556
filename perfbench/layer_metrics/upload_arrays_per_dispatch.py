"""Host arrays a decode dispatch hands to the device: growth of `/info`
`decode_upload_arrays_total` over growth of `decode_dispatches_total`
from the window's open to its close.  Each such array is one transfer
the host waits on inside `ptpu/upload`, and a transfer is paid per
array, not per byte; 1 is a launch whose host operands travel packed
in one buffer.  A program without the counter reports nothing."""


def read(ctx):
    a, b = ctx.collected["info_open"], ctx.collected["info_close"]
    names = ("decode_upload_arrays_total", "decode_dispatches_total")
    if any(n not in rec for n in names for rec in (a, b)):
        return None
    arrays, launched = (b[n] - a[n] for n in names)
    if launched <= 0:
        return None
    return arrays / launched
