"""Share of the device's busy time in a serving trace whose name
stack says what the program was doing: 100 - ``unnamed`` (no scope,
no module's path: compiler-made copies, arguments re-laid, a custom
call that dropped its stack).  The reader's own coverage
(perfbench/device_scopes.py)."""

import device_scopes


def read(ctx):
    return device_scopes.named_pct(ctx)
