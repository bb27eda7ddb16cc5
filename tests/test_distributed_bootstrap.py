"""Multi-host bootstrap: `initialize_distributed` single-process no-op
semantics (tier-1) and the real 2-process `jax.distributed` CPU smoke
(slow; the CI sweep-sharded job runs it) — two coordinated subprocesses,
one CPU device each, gloo collectives, a process-spanning ("data",) mesh,
and a sharded sweep checked against the process-local engine."""
import os
import pathlib
import socket
import subprocess
import sys

import jax
import pytest

from repro import setup_compilation_cache
from repro.launch.distributed import initialize_distributed


def test_initialize_distributed_single_process_noop():
    """No coordinator, no env, or an explicit num_processes=1: nothing to
    bootstrap — must return False without touching the runtime."""
    assert initialize_distributed() is False
    assert initialize_distributed(num_processes=1) is False
    assert jax.process_count() == 1


def test_setup_compilation_cache_noop_without_dir(tmp_path, monkeypatch):
    """With $JAX_COMPILATION_CACHE_DIR set, JAX owns the cache directory:
    the helper reports it and sets nothing in code."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert setup_compilation_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_setup_compilation_cache_sets_dir(monkeypatch):
    """Unset, the cache goes to the one fixed path in the checkout."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    root = pathlib.Path(__file__).resolve().parents[1]
    before = jax.config.jax_compilation_cache_dir
    try:
        assert setup_compilation_cache() == str(root / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(root / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


@pytest.mark.slow
def test_two_process_distributed_smoke(tmp_path):
    root = pathlib.Path(__file__).resolve().parents[1]
    driver = str(root / "tests" / "distributed_smoke_driver.py")
    # Shared checkpoint dir: the driver also exercises the multi-process
    # checkpoint/resume edge (collective fetch on both ranks, rank-0
    # write, broadcast resume step).
    ckpt_dir = str(tmp_path / "ckpts")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(root / "src"), str(root / "tests")]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    # One CPU device per process (overriding any fake-device fan-out from
    # the CI job) so the 2-device mesh genuinely spans both processes.
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    procs = [subprocess.Popen([sys.executable, driver, str(port), str(rank),
                               ckpt_dir],
                              env=env, stdout=subprocess.PIPE,
                              stderr=subprocess.STDOUT, text=True)
             for rank in range(2)]
    outs = [p.communicate(timeout=600)[0] for p in procs]
    for rank, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {rank} failed:\n{out}"
        assert f"DISTRIBUTED_SMOKE_OK rank={rank}" in out, out
