"""chip_smoke.py at a tiny size on the CPU: every phase's checks pass,
the script refuses to run without a TPU, and the compile cache lands
where the entry points put it."""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

TINY = chip_smoke.Sizes(nodes=3000, edges=8000, oracle_nodes=300,
                        chunk_edges=1024, batch_edges=32, queries=12,
                        wave=4, kernel_chunk=1024, kernel_frontier=256)


def _phase_lines(out: str) -> dict:
    lines = [json.loads(x) for x in out.splitlines() if x.startswith("{")]
    return {x["phase"]: x for x in lines if "phase" in x}


def test_one_chip_phases_pass_at_tiny_size(capsys):
    chip_smoke.run_one_chip(TINY, seed=0)
    phases = _phase_lines(capsys.readouterr().out)
    assert list(phases) == ["kernels", "graph", "build", "oocore",
                            "maintain", "query"]
    for line in phases.values():
        assert {"seconds", "compile_s", "peak_bytes_in_use"} <= set(line)
    assert phases["oocore"]["device_folds"] >= 6 * phases["oocore"]["levels"]
    assert phases["maintain"]["maint_dispatches"] > 0
    assert phases["query"]["nonempty_path_answers"] > 0


def test_reference_pids_matches_the_exact_oracle():
    from repro.core import oracle_pids
    g = chip_smoke.linkedmdb_like(400, 1100, seed=5)
    chip_smoke.assert_levels(chip_smoke.reference_pids(g, 10),
                             oracle_pids(g, 10), "numpy vs oracle")


def test_assert_levels_catches_a_wrong_level():
    a = [np.array([0, 0, 1]), np.array([0, 1, 2])]
    b = [np.array([5, 5, 7]), np.array([0, 1, 1])]
    with pytest.raises(AssertionError, match="level 1"):
        chip_smoke.assert_levels(a, b, "x")


def _run(args, env_extra=None, cwd=ROOT):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run([sys.executable] + args, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=600)


def test_script_fails_without_a_tpu():
    """No accelerator: a non-zero exit before any phase, and no result
    line — never a CPU fallback."""
    p = _run([os.path.join(ROOT, "chip_smoke.py")])
    assert p.returncode != 0
    assert '"ok"' not in p.stdout and '"phase"' not in p.stdout
    assert "not a TPU" in p.stderr


def test_four_chip_path_on_four_virtual_devices():
    code = (
        "import jax, sys; sys.path.insert(0, %r); import chip_smoke as cs; "
        "cs.run_four_chips(cs.Sizes(nodes=2000, edges=5300), 0, "
        "jax.devices()[:4])" % ROOT)
    p = _run(["-c", code], {
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert p.returncode == 0, p.stderr[-3000:]
    phases = _phase_lines(p.stdout)
    assert set(phases) == {"compile", "sharded_build_allgather",
                           "sharded_build_bucketed", "sharded_placement",
                           "single_chip_build"}
    for where in phases["sharded_placement"]["inputs"].values():
        assert where["device_set"] == [0, 1, 2, 3]


@pytest.fixture
def cache_config():
    """Restore jax's cache directory (and drop the initialized cache)
    after a test that points it somewhere."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    compilation_cache.reset_cache()


def test_compile_cache_defaults_to_the_repo(monkeypatch, cache_config):
    from repro.compat import use_compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = use_compile_cache()
    assert path == os.path.join(ROOT, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path


def test_compile_cache_follows_the_environment(monkeypatch, tmp_path,
                                               cache_config):
    """With JAX_COMPILATION_CACHE_DIR set, compiled programs land there
    and the code sets no other directory."""
    from jax.experimental.compilation_cache import compilation_cache
    from repro.compat import use_compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    compilation_cache.reset_cache()
    prev = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        jax.jit(lambda x: x * 3 + 1)(np.arange(7)).block_until_ready()
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", prev)
    assert os.listdir(tmp_path), "nothing was cached in the directory"
