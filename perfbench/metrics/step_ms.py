"""Device time per denoising step launch (``step_slots``: one DiT forward
over the slot buffer plus the DDIM update), from the trace, in ms."""
import tracereduce


def read(r):
    if r.red is None:
        return None
    secs, _ = tracereduce.program_time(r.red, "step_slots")
    n = r.red.spans.get("step_slots", 0)
    return 1e3 * secs / n if n and secs > 0 else None
