"""Device time per VAE decode of one finished latent (``slot_decode``),
from the trace, in ms."""
import tracereduce


def read(r):
    if r.red is None:
        return None
    secs, _ = tracereduce.program_time(r.red, "slot_decode")
    n = r.red.spans.get("slot_decode", 0)
    return 1e3 * secs / n if n and secs > 0 else None
