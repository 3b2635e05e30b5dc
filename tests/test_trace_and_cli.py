"""Request-trace generator properties, the train CLI's restart path, and
the entry points' refusal to fall back off the chip path."""
from __future__ import annotations

import os
import shutil
import subprocess
import sys

import numpy as np
import pytest
try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:  # offline container: seeded-random shim
    from _hypothesis_shim import given, settings, strategies as st

from repro.core.trace import RequestTrace


def test_trace_deterministic():
    a = [r.prompt for r in RequestTrace(seed=5).generate(50)]
    b = [r.prompt for r in RequestTrace(seed=5).generate(50)]
    assert a == b


def test_trace_zipf_concentration():
    """Zipf law: the head of the popularity distribution dominates."""
    reqs = [r.prompt for r in RequestTrace(seed=2, zipf_a=1.4,
                                           repeat_rate=0.0).generate(400)]
    from collections import Counter
    counts = Counter(reqs).most_common()
    top10 = sum(c for _, c in counts[:10])
    assert top10 > 0.35 * len(reqs)


def test_trace_repeats_marked():
    reqs = list(RequestTrace(seed=3, repeat_rate=0.5).generate(200))
    repeats = [r for r in reqs if r.is_repeat]
    assert len(repeats) > 40
    # a repeat echoes the previous prompt verbatim
    for i, r in enumerate(reqs):
        if r.is_repeat and i > 0:
            assert r.prompt == reqs[i - 1].prompt
            break


@settings(max_examples=10, deadline=None)
@given(n=st.integers(1, 60), seed=st.integers(0, 20))
def test_trace_total_function(n, seed):
    reqs = list(RequestTrace(seed=seed, n_specs=50).generate(n))
    assert len(reqs) == n
    assert all(r.prompt for r in reqs)


def test_trace_drift_changes_popularity():
    """Topic drift rotates which scenes are popular across windows."""
    trace = RequestTrace(seed=7, drift_every=100, repeat_rate=0.0)
    reqs = [r.prompt for r in trace.generate(400)]
    from collections import Counter
    first = set(p for p, _ in Counter(reqs[:100]).most_common(5))
    last = set(p for p, _ in Counter(reqs[300:]).most_common(5))
    assert first != last


def test_train_cli_failure_restart(tmp_path):
    """The launch/train driver: inject a failure, restart, finish —
    the operational fault-tolerance story end-to-end."""
    from repro.launch import train as train_cli

    ckpt = str(tmp_path / "ckpt")
    argv = sys.argv
    try:
        sys.argv = ["train", "--arch", "sd15-small", "--steps", "8",
                    "--ckpt-every", "4", "--ckpt-dir", ckpt,
                    "--fail-at", "6", "--fresh"]
        with pytest.raises(Exception):
            train_cli.main()
        # restart picks up from the step-4 checkpoint and completes
        sys.argv = ["train", "--arch", "sd15-small", "--steps", "8",
                    "--ckpt-every", "4", "--ckpt-dir", ckpt]
        assert train_cli.main() == 0
    finally:
        sys.argv = argv


# ---------------------------------------------------------------------------
# no silent fallback off the chip path
# ---------------------------------------------------------------------------

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code_or_args, env_extra, cwd=REPO):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run([sys.executable, *code_or_args], env=env, cwd=cwd,
                          capture_output=True, text=True, timeout=300)


def test_chip_smoke_refuses_without_tpu(tmp_path):
    """No TPU: non-zero exit, a message naming it, and no result line —
    also from a directory holding chip_smoke.py alone."""
    proc = _run([os.path.join(REPO, "chip_smoke.py")], {})
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert '"ok"' not in proc.stdout
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    proc = _run([str(tmp_path / "chip_smoke.py")], {}, cwd=tmp_path)
    assert proc.returncode != 0 and '"ok"' not in proc.stdout


def test_serve_mesh_nodes_beyond_devices_exits_nonzero(monkeypatch):
    """More mesh nodes than devices is an error, never an unsharded run
    (this process already holds its CPU devices, so none can be added)."""
    import jax

    from repro.launch import serve
    n = len(jax.devices()) + 1
    monkeypatch.setattr(sys, "argv", ["serve", "--mesh-nodes", str(n)])
    with pytest.raises(SystemExit) as exc:
        serve.main()
    assert exc.value.code != 0


@pytest.mark.parametrize("env_dir", [True, False])
def test_compile_cache_location(tmp_path, env_dir):
    """With JAX_COMPILATION_CACHE_DIR set, entries land there and the
    helper sets no other directory; unset, the cache is the fixed path
    inside the checkout."""
    code = ("import sys, jax, jax.numpy as jnp;"
            f"sys.path.insert(0, {os.path.join(REPO, 'src')!r});"
            "from repro.launch.mesh import enable_compile_cache;"
            "print(enable_compile_cache());"
            "print(jax.config.jax_compilation_cache_dir);"
            + ("jax.jit(lambda x: x * 3 + 1)(jnp.ones(7)).block_until_ready()"
               if env_dir else ""))
    extra = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)} if env_dir else {}
    proc = _run(["-c", code], extra)
    assert proc.returncode == 0, proc.stderr
    path, configured = proc.stdout.split()
    if env_dir:
        assert path == configured == str(tmp_path)
        assert any(f.startswith("jit_") for f in os.listdir(tmp_path))
    else:
        assert path == configured == os.path.join(REPO, ".cache",
                                                  "jax_compile")
