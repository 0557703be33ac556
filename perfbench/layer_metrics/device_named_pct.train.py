"""Share of the device's busy time in a training trace whose name
stack says what the program was doing: 100 - ``unnamed``
(perfbench/device_scopes.py)."""

import device_scopes


def read(ctx):
    return device_scopes.named_pct(ctx)
