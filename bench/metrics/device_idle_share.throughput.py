"""Share of the traced window in which no operation ran on the device:
one minus the union of device-op intervals over the window, in percent."""
import layer


def read(ctx):
    return layer.idle_share(ctx)
