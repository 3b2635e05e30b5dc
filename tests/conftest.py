"""Shared fixtures: tiny synthetic corpus, proxy embedder, node VDB fleet.

Multi-device harness: this conftest asks for 8 CPU devices
(``jax_num_cpu_devices``, via ``ensure_host_devices``) at import — i.e.
before any test can initialise the backend — so the mesh-sharded
cluster-retrieval parity suite runs on any CI box.  The whole tier-1
suite runs under the forced-8 world (single-device tests are
device-count agnostic).  When forcing fails (JAX backend already up in
the hosting process, e.g. an embedding pytest runner), the
``mesh_devices`` fixture SKIPS the sharded tests instead of erroring,
and ``forced_subprocess`` offers a clean-interpreter escape hatch.
"""
from __future__ import annotations

import os
import subprocess
import sys

import numpy as np
import pytest

# must run before the repro imports below can touch a jax device: the
# flag only takes effect if the XLA backend has not initialised yet
from repro.launch.mesh import ensure_host_devices

FORCED_DEVICES = 8
_FORCED_OK = ensure_host_devices(FORCED_DEVICES)

from repro.core.embeddings import ProxyClipEmbedder  # noqa: E402
from repro.core.storage_classifier import StorageClassifier  # noqa: E402
from repro.core.vdb import BlobStore  # noqa: E402
from repro.data.synthetic import make_corpus, render_caption  # noqa: E402


@pytest.fixture(scope="session")
def mesh_devices():
    """Number of XLA devices available for node-mesh sharding tests.
    Skips (never errors) when the backend came up with fewer than 2 —
    e.g. JAX was initialised before this conftest could force host
    devices."""
    import jax
    n = len(jax.devices())
    if not _FORCED_OK or n < 2:
        pytest.skip(
            f"sharding tests need >=2 XLA host devices, backend has {n} "
            "(JAX initialised before conftest could force them)")
    return min(n, FORCED_DEVICES)


def run_forced_subprocess(code: str, n_devices: int = FORCED_DEVICES,
                          timeout: float = 600.0):
    """Run ``code`` in a fresh interpreter with ``n_devices`` forced XLA
    host devices and ``src`` on PYTHONPATH — the escape hatch when the
    hosting process's backend is already up with too few devices (and
    the harness's own self-test)."""
    env = dict(os.environ)
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "--xla_force_host_platform_device_count" not in f]
    flags.append(f"--xla_force_host_platform_device_count={n_devices}")
    env["XLA_FLAGS"] = " ".join(flags)
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=timeout)


@pytest.fixture(scope="session")
def forced_subprocess():
    return run_forced_subprocess


@pytest.fixture(scope="session")
def corpus():
    images, captions, specs = make_corpus(240, res=32, seed=0)
    return images, captions, specs


@pytest.fixture(scope="session")
def embedder(corpus):
    images, _, _ = corpus
    e = ProxyClipEmbedder(render_caption)
    e.set_corpus_anchor(e.embed_image(images))
    return e


@pytest.fixture()
def fleet(corpus, embedder):
    """4-node VDB fleet built by the storage classifier + blob store."""
    images, captions, _ = corpus
    img_vecs = embedder.embed_image(images)
    txt_vecs = embedder.embed_text(captions)
    blob = BlobStore()
    payloads = np.array([blob.put(im) for im in images], np.int64)
    cls = StorageClassifier(4)
    # capacity ≥ corpus so cluster imbalance never truncates (the LCU
    # tests exercise capacity pressure explicitly)
    dbs = cls.build_node_dbs(img_vecs, txt_vecs, payloads,
                             capacity_per_node=240)
    return dbs, blob, cls, img_vecs, txt_vecs, payloads
