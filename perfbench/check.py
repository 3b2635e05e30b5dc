"""The comparison that decides ``correct``.

Once the window has closed, the peak memory has been read and the
program's state is freed, the sampled calls of the window are held
against the plain reference (:mod:`reference`), on the same inputs the
program was given there:

* ``step_gap``: per sampled step launch and active slot, the largest
  difference between the program's next latent and the reference's
  (DiT eps at float32 HIGHEST, then DDIM), relative to the slot's
  largest reference value;
* ``decode_gap``: per sampled decode, the same for the image the VAE
  decoded from the program's final latent;
* ``scan_gap``: per sampled scan call, query and node, the largest
  difference between the program's top-k scores and the float64 top-k
  over the rows valid at that call, position by position (a missed or
  extra row shows as a score out of place), and between each returned
  score and the float64 score of the row it names;
* ``decision_faults``: requests whose route disagrees with the
  policy's thresholds on their own score, whose step count disagrees
  with the route, or whose slot ran a different number of steps;
* ``failed_requests``: requests that never came back, or came back
  without a finite image of the configured size.

With ``control`` the control takes the program's place: the reference
computed one precision step below the one the configuration states (fp8
operands for the DiT and VAE, three bfloat16 passes for the scans), on
the same sampled inputs.  Its outputs are compared with the reference by
the same measures and held to the same limits, so a control run has to
come out not correct.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

import reference as ref

NUMBERS = ("step_gap", "decode_gap", "scan_gap", "decision_faults",
           "failed_requests")


def _rel_gap(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want))
                 / max(float(np.max(np.abs(want))), 1e-12))


def step_gap(samples, weights, cfg, control: bool = False) -> float:
    """Largest relative gap, over the sampled launches' active slots,
    between the next latent and the float32 reference's: the program's,
    or with ``control`` the fp8 reference's on the same inputs."""
    net, gap = weights[0], 0.0
    for (lat, ctx, t, tp, active), out in samples:
        act = np.flatnonzero(np.asarray(active))
        if not act.size:
            continue
        eps = ref.dit_eps(net, cfg["dit"], lat, t, ctx)
        want = np.asarray(ref.ddim_step(cfg["sampler"], lat, eps, t, tp))
        if control:
            eps8 = ref.dit_eps(net, cfg["dit"], lat, t, ctx, prec="fp8")
            out = ref.ddim_step(cfg["sampler"], lat, eps8, t, tp)
        got = np.asarray(out)
        gap = max([gap] + [_rel_gap(got[i], want[i]) for i in act])
    return gap


def decode_gap(samples, weights, cfg, control: bool = False) -> float:
    """The same for the sampled decodes of final latents to images."""
    vae, gap = weights[1], 0.0
    scale = cfg["sampler"]["latent_scale"]
    for z, out in samples:
        want = ref.vae_decode(vae, cfg["vae"], z / scale)
        if control:
            out = ref.vae_decode(vae, cfg["vae"], z / scale, prec="fp8")
        gap = max(gap, _rel_gap(out, want))
    return gap


def scan_gap(samples, mirror, control: bool = False) -> float:
    """Largest score gap over the sampled scans' queries and nodes: the
    program's top-k against the float64 top-k position by position, and
    each returned score against the float64 score of the row it names.
    With ``control`` the three-pass reference's top-k stands in for the
    program's."""
    gap = 0.0
    for queries, k, valids, out in samples:
        want, low = ref.topk_nodes(queries, mirror["img"], mirror["txt"],
                                   valids, k)
        if control:
            out = low
        q64 = np.asarray(queries, np.float64)
        q64 = q64 / np.linalg.norm(q64, axis=-1, keepdims=True)
        for qi, per_node in enumerate(out):
            for node, (s_got, i_got) in enumerate(per_node):
                s_want, i_want = want[qi][node]
                if len(s_got) != len(s_want):
                    return float("inf")
                if not len(s_got):
                    continue
                gap = max(gap, float(np.max(np.abs(
                    np.asarray(s_got, np.float64) - s_want))))
                # each returned score must be its row's score in one plane
                rows = np.asarray(i_got, np.int64)
                s = np.asarray(s_got, np.float64)
                named = np.minimum(
                    np.abs(s - mirror["img"][node][rows] @ q64[qi]),
                    np.abs(s - mirror["txt"][node][rows] @ q64[qi]))
                gap = max(gap, float(np.max(named)))
    return gap


def decisions(done, rec, progress, cfg) -> Dict[str, int]:
    """Exact checks of every window request's route, steps and image.
    Each completion is matched to its own admission through ``rec``, so
    the order in which results come back does not matter."""
    pol, smp = cfg["policy"], cfg["sampler"]
    res_px = cfg["image_res"]
    steps_of = {"hit_return": 0, "img2img": smp["steps_ref"],
                "txt2img": smp["steps_full"]}
    faults, failed = 0, 0
    for c in done:
        r = c.result
        img = None if r is None else r.image
        if (img is None or np.shape(img) != (res_px, res_px, 3)
                or not np.all(np.isfinite(img))):
            failed += 1
            continue
        route = r.route.value
        if r.fast_path is None:
            want = ("hit_return" if r.score > pol["hi"] else
                    "img2img" if r.score >= pol["lo"] else "txt2img")
            faults += route != want
        if r.steps != steps_of[route]:
            faults += 1
        seen = rec.request(r)
        if seen is None:            # a result the window never admitted
            faults += 1
        elif seen[0] == "gen":
            ran = len(progress.get(seen[1], [0])) - 1
            faults += ran != r.steps
    return {"decision_faults": faults, "failed_requests": failed}


def run(*, attempted: int, done, rec, progress, weights, cfg, mirror,
        control: bool = False) -> Dict[str, float]:
    """Every compared number of one run by name.  With ``control`` the
    three gaps are the control's, in the program's place."""
    gen = "gen" in rec.plan_kinds
    out = {"step_gap": step_gap(rec.steps.items, weights, cfg, control),
           "decode_gap": decode_gap(rec.decodes.items, weights, cfg,
                                    control),
           "scan_gap": scan_gap(rec.scans.items, mirror, control)}
    d = decisions(done, rec, progress, cfg)
    out["decision_faults"] = d["decision_faults"]
    out["failed_requests"] = d["failed_requests"] + (attempted - len(done))
    if not rec.steps.items and gen:
        out["step_gap"] = float("inf")      # generations went unseen
    if not rec.decodes.items and gen:
        out["decode_gap"] = float("inf")
    if not rec.scans.items:
        out["scan_gap"] = float("inf")
    return out
