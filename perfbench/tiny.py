"""A cell at a size the CPU can run in seconds, for the benchmark's tests.

It keeps the shape of the real configuration and traffic files and only
shrinks the numbers, so a rehearsal drives every function of a chip run
without the device gate.
"""
from __future__ import annotations

import copy
import json
import os
import types

HERE = os.path.dirname(os.path.abspath(__file__))


def config(name: str = "dit_b2") -> dict:
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        cfg = json.load(f)
    cfg = copy.deepcopy(cfg)
    cfg["dit"].update(input_size=8, depth=min(cfg["dit"]["depth"], 3),
                      hidden_size=8 * cfg["dit"]["num_heads"])
    cfg["vae"].update(base_ch=16, ch_mult=[1, 2], n_res=1)
    cfg["image_res"] = 32
    cfg["fleet"].update(rows_per_node=512, archive_room_per_node=128)
    return cfg


def traffic(name: str = "reuse") -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        spec = json.load(f)
    spec.update(corpus_images=48, warmup_requests=8, rate_per_s=20.0)
    return spec


def limits() -> dict:
    """The tiny cell's limits on the CPU, which runs the program at
    float32: its gaps read under 5e-7 (step, decode) and 1e-7 (scan) on
    three seeds, the control's over 0.04 and 2.8e-6."""
    return {"step_gap": 1e-3, "decode_gap": 1e-3, "scan_gap": 5e-7,
            "decision_faults": 0, "failed_requests": 0}


def device():
    """What the harness reads of a device, for a CPU rehearsal."""
    return types.SimpleNamespace(platform="cpu", device_kind="TPU v5 lite",
                                 memory_stats=lambda: {})


def args(seed: int = 2 ** 33 + 5, seconds: float = 1.5, trace: int = 0,
         control: int = 0):
    return types.SimpleNamespace(workload="tiny", seed=seed, seconds=seconds,
                                 trace=trace, control=control)
