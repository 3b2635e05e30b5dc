"""The whole serve step's share of the chip's peak: the operations the
window's completed work requires (a DiT forward per active slot-step, a
VAE decode per generated image, a VAE encode per img2img start, and the
scans), from shapes, over the traced window times peak FLOP/s, in %.
Inactive slots and padding are not counted."""
import work


def read(r):
    if r.red is None or r.red.busy_s <= 0:
        return None    # no program ran on a device
    flops = work.window_flops(r.cfg, r.rec, r.window, r.rows0)
    return 100.0 * flops / (r.red.window_s * r.peak["peak_flops_per_s"])
