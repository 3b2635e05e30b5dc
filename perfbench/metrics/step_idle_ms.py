"""Device-idle time inside the program's ``cg:slot.step`` spans (the slot
buffer's upload and download, the step launch and each retiring slot's
decode) per step launch of the window, in ms."""
import programspans


def read(r):
    idle = programspans.idle_by_span(within="slot.step")
    launches = len(r.window.slot_occupancy)
    if idle is None or not launches:
        return None    # no device plane, or no program span to read
    return 1e3 * sum(idle.values()) / launches
