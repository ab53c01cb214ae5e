"""No chip, no number: the entry points that measure or prove something
about the TPU refuse to run where JAX finds none, and the helpers that
place the compile cache and the worker processes say where they put them.
"""

import json
import os
import subprocess
import sys

import jax
import pytest

from ddp_practice_tpu.utils import backend

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(*argv):
    return subprocess.run(
        [sys.executable, *argv], cwd=ROOT, capture_output=True, text=True,
        timeout=120, env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )


@pytest.mark.parametrize("args", [(), ("--chips", "4")], ids=["one", "four"])
def test_chip_smoke_refuses_to_run_without_a_chip(args):
    """Exit code non-zero, no result line, no phase (a phase prints a
    JSON line and takes far longer than this test's timeout allows)."""
    r = _run("chip_smoke.py", *args)
    assert r.returncode != 0
    assert r.stdout == ""
    assert "no accelerator" in r.stderr and "nothing was run" in r.stderr


@pytest.fixture
def cache_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_obeys_the_environment(monkeypatch, tmp_path,
                                             cache_config):
    """JAX_COMPILATION_CACHE_DIR set: that directory, and the code sets
    no other. (JAX reads the variable itself, at import.)"""
    was = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert backend.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == was


def test_compile_cache_default_is_fixed_inside_the_checkout(monkeypatch,
                                                            cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(ROOT, ".jax_compile_cache")
    assert backend.enable_compile_cache() == want
    assert backend.enable_compile_cache("auto") == want  # never a new name
    assert jax.config.jax_compilation_cache_dir == want
    ignored = subprocess.run(
        ["git", "check-ignore", "-q", want], cwd=ROOT
    ).returncode
    assert ignored in (0, 128)  # 128: not a git checkout (the chip copy)


def test_compile_cache_off_and_no_free_form_path(cache_config):
    was = jax.config.jax_compilation_cache_dir
    assert backend.enable_compile_cache("off") is None
    with pytest.raises(ValueError, match="JAX_COMPILATION_CACHE_DIR"):
        backend.enable_compile_cache("/some/where")
    assert jax.config.jax_compilation_cache_dir == was


def test_one_call_site_sets_the_cache_directory():
    hits = subprocess.run(
        ["grep", "-rn", "--include=*.py", "jax_compilation_cache_dir",
         "ddp_practice_tpu", "chip_smoke.py",
         "__graft_entry__.py", "tools", "experiments"],
        cwd=ROOT, capture_output=True, text=True,
    ).stdout.splitlines()
    assert len(hits) == 1 and "utils/backend.py" in hits[0], hits


# ------------------------------------------------- the smoke's own phases
TINY_TRAIN_ARGS = [
    "--model", "lm_tiny", "--seq_len", "128", "--attn_impl", "flash",
    "--pos_emb", "rope", "--precision", "bf16", "--optimizer", "adamw",
    "--lr", "1e-3", "--dataset", "synthetic_tokens", "--log_every", "1",
    "--synthetic_size", "40000", "--compile_cache", "off",
]


@pytest.fixture
def smoke(monkeypatch):
    """chip_smoke's phases at a tiny size, for the CPU: what they check
    about the device (compiled kernels) is switched off, what they check
    about the program (loss falls, checkpoint loads, tokens agree with
    generate() and the float32 forward, shards sit where they should)
    stays."""
    sys.path.insert(0, ROOT)
    import chip_smoke

    monkeypatch.setattr(chip_smoke, "TRAIN_ARGS", TINY_TRAIN_ARGS)
    return chip_smoke


def _json_lines(capsys) -> list:
    return [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("{")]


def test_paged_walk_check_rehearses_on_cpu(smoke):
    """The smoke's ragged, left-padded walk check at a toy size, the
    kernel interpreted: its lengths hold the edges it names and a sound
    kernel passes its one-ulp bound."""
    seen = smoke.paged_walk_check(
        seed=3, slots=10, columns=10, pool=128, heads=2, impl="kernel",
        require_kernels=False)
    assert seen["paged_walk_mosaic_kernels"] == 0       # interpreted
    assert seen["paged_walk_worst_bf16_ulps"] <= 1.0
    assert seen["paged_walk_live_tokens"] > 10


@pytest.mark.slow
def test_one_chip_phases_rehearse_on_cpu(smoke, tmp_path, capsys):
    clock = smoke.CompileClock()
    ckpt = smoke.train_phase(
        str(tmp_path), clock, seed=0, require_kernels=False,
        size_args=("-b", "8", "-e", "2", "--max_steps", "12"),
    )
    smoke.serve_phase(
        ckpt, clock, seed=0, prompt_lengths=(3, 5, 12, 9), max_new=48,
        buckets=(8, 16), slot_len=128, require_kernels=False,
    )
    train, serve = _json_lines(capsys)
    assert train["last3_mean"] < train["first3_mean"]
    assert serve["tokens"] == 4 * 48 and serve["buckets_used"] == [8, 16]
    with pytest.raises(smoke.SmokeFailed, match="Mosaic kernels"):
        smoke.train_phase(str(tmp_path / "again"), clock, seed=0,
                          size_args=("-b", "8", "-e", "1"))


@pytest.mark.slow
def test_mesh_phase_rehearses_on_virtual_devices(smoke, devices, tmp_path,
                                                 capsys):
    smoke.mesh_phase(str(tmp_path), smoke.CompileClock(), seed=0,
                     require_kernels=False)
    lines = _json_lines(capsys)
    assert [ln["mesh_devices"] for ln in lines[:4]] == [
        [0], list(range(8)), list(range(8)), list(range(8))]
    assert max(lines[-1]["max_abs_loss_diff"].values()) <= smoke.LOSS_TOL
