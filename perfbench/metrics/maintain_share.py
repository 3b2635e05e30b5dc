"""Share of the traced window spent in the program's eviction sweeps:
the union of its ``cg:maintain`` spans over the window, in %."""
import programspans


def read(r):
    secs, window = programspans.covered("maintain"), programspans.window_s()
    return 100.0 * secs / window if secs is not None and window else None
