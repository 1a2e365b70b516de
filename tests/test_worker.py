"""Deployment-shaped worker round-trip: orchestrator writes the global
model, the worker process (the command the scheduler artifacts launch)
trains on its private shard and writes a usable update back."""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]


def test_worker_round_trip(tmp_path):
    from repro.checkpoint import load_pytree, save_pytree
    from repro.models.cnn import CIFAR_CNN, CNN

    model = CNN(CIFAR_CNN)
    params = model.init(jax.random.PRNGKey(0))
    save_pytree(tmp_path / "global_round_0000.bin",
                jax.tree.map(np.asarray, params))

    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"))
    out = subprocess.run(
        [sys.executable, "-m", "repro.worker", "--client-id", "3",
         "--workdir", str(tmp_path), "--once", "--local-steps", "2",
         "--batch-size", "8", "--timeout-s", "120"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]

    upd_path = tmp_path / "update_0000_client_003.bin"
    assert upd_path.exists()
    delta = load_pytree(upd_path, params)
    norms = [float(np.linalg.norm(np.asarray(l)))
             for l in jax.tree.leaves(delta)]
    assert sum(norms) > 0                      # actually trained
    meta = json.loads((tmp_path / "update_0000_client_003.json").read_text())
    assert np.isfinite(meta["loss"]) and meta["data_size"] > 0
    # orchestrator-side application
    new_params = jax.tree.map(lambda p, d: p + np.asarray(d), params, delta)
    jax.tree.map(lambda a: None, new_params)   # structure intact
