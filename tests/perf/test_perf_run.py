"""perf/run.py: refusing to measure without a chip, and the rest of a run
driven past that refusal at toy sizes to see `correct` decided — true for the
sound program, false with the timed path broken underneath."""

import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

import perf_toy
from perf import run as harness
from perf.lib import peaks


def run_py(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "perf", "run.py"), "--workload",
         "vitb16_train_224", "--seed", "1", "--seconds", "1", "--trace", "0",
         *extra], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=120)


def test_no_chip_no_number():
    out = run_py(perf_toy.ROOT)
    assert out.returncode == 2
    assert out.stdout.strip() == "" and "Nothing was measured" in out.stderr


def test_unknown_workload_and_unknown_device_kind():
    with pytest.raises(SystemExit):
        harness.load_cell("no_such_cell")
    with pytest.raises(peaks.UnknownDevice):
        peaks.lookup("TPU v9 imaginary")
    assert peaks.lookup("TPU v5 lite")["bf16_flops_s"] == 197e12


def test_only_the_benchmarks_files_is_not_enough(tmp_path):
    m = perf_toy.manifest()
    shutil.copy(os.path.join(perf_toy.ROOT, "BENCHMARK.json"), tmp_path)
    for p in m["paths"]:
        shutil.copytree(os.path.join(perf_toy.ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    out = run_py(str(tmp_path))
    assert out.returncode != 0 and out.stdout.strip() == ""


@pytest.fixture(autouse=True)
def cache_every_program():
    """The second run of a test (the broken one) and every later session
    find the toy programs in the persistent cache the Trainer turns on."""
    keys = {"jax_persistent_cache_min_compile_time_secs": 0.0,
            "jax_persistent_cache_min_entry_size_bytes": 0}
    old = {k: getattr(jax.config, k) for k in keys}
    for k, v in keys.items():
        jax.config.update(k, v)
    yield
    for k, v in old.items():
        jax.config.update(k, v)


def measure(cell, config, traffic, tmp_path, seed=3_000_000_019):
    return harness.measure(
        perf_toy.manifest_with_the_tail_cell(), cell, config, traffic,
        seed=seed, seconds=0.5,
        trace=False, devices=jax.devices()[:cell["chips"]],
        chip_peaks=perf_toy.PEAKS, outroot=str(tmp_path))


def keep_the_state(monkeypatch):
    """The Trainer's step computes and then hands back the state it was
    given: the loss is right and nothing is learned."""
    from ddp_practice_tpu.train import loop

    real = loop.Trainer.__init__

    def broken(self, config):
        real(self, config)
        step = self.resident_train_step

        def keeps_its_state(state, data, rows):
            new, metrics = step(jax.tree.map(lambda x: x.copy(), state),
                                data, rows)
            return state, metrics

        self.resident_train_step = keeps_its_state

    monkeypatch.setattr(loop.Trainer, "__init__", broken)


def alter_a_token(monkeypatch, vocab):
    """Every served token is shifted by one where it is produced."""
    from ddp_practice_tpu.serve import engine

    real = engine.PagedEngine.step_burst
    monkeypatch.setattr(engine.PagedEngine, "step_burst",
                        lambda self: (real(self) + 1) % vocab)


@pytest.mark.parametrize("broken", [False, True])
@pytest.mark.parametrize("family", ["vit", "lm"])
def test_train_run_is_correct_unless_a_step_keeps_its_state(
        family, broken, tmp_path, monkeypatch):
    perf_toy.shrink_registry(monkeypatch)
    cell, config, traffic = perf_toy.train_cell(family)
    if broken:
        keep_the_state(monkeypatch)
    line = measure(cell, config, traffic, tmp_path)
    assert line["correct"] is not broken and line["failed"] == 0
    assert set(line["metrics"]) == {"train_mfu_pct", "setup_s"}
    assert line["device"]["platform"] == "cpu"  # never a device number
    series = json.load(open(os.path.join(
        tmp_path, cell["name"], "seed3000000019_trace0", "series.json")))
    assert len(series["segment_seconds"]) == series["segments"] >= 2
    assert len(series["segment_loss"]) == series["segments"]
    # the cell's number is all the work over all the window's time
    work = series["segments"] * series["steps_per_segment"] \
        * series["items_per_step"] * series["flops_per_item"]
    assert series["window_seconds"] >= sum(series["segment_seconds"])
    assert line["metrics"]["train_mfu_pct"]["value"] == pytest.approx(
        100.0 * work / series["window_seconds"]
        / (cell["chips"] * perf_toy.PEAKS["bf16_flops_s"]))
    assert series["median_mfu_pct"] >= line["metrics"]["train_mfu_pct"][
        "value"] * 0.5


@pytest.mark.parametrize("broken", [False, True])
@pytest.mark.parametrize("mode", ["tail", "flood"])
def test_serve_run_is_correct_unless_a_token_is_altered(
        mode, broken, tmp_path, monkeypatch):
    perf_toy.shrink_registry(monkeypatch)
    cell, config, traffic = perf_toy.serve_cell(mode)
    if broken:
        alter_a_token(monkeypatch, config["vocab_size"])
    line = measure(cell, config, traffic, tmp_path)
    assert line["correct"] is not broken and line["failed"] == 0
    want = {"tail": {"ttft_p95_ms", "setup_s"},
            "flood": {"serve_tok_s", "setup_s"}}[mode]
    assert set(line["metrics"]) == want
    assert all(v["value"] > 0 for v in line["metrics"].values())


def test_the_sample_holds_the_longest_and_one_of_every_bucket():
    import types

    from perf.drivers import serve

    lens = [5, 100, 130, 200, 300, 600, 700, 90, 80, 70]
    by_rid = {i: {"prompt": [1] * n} for i, n in enumerate(lens)}
    ok = [types.SimpleNamespace(rid=i, tokens=[2] * (40 if i == 3 else 8))
          for i in by_rid]
    for seed in (1, 2, 3_000_000_019):
        got = serve.pick_sample(ok, by_rid, seed, 6, [128, 256, 512, 768])
        plens = [len(p) for p, _ in got]
        assert len(got) == 6 and len(set(plens)) == 6
        assert plens[0] == 700                      # the longest in all
        for lo, hi in ((0, 128), (128, 256), (256, 512), (512, 768)):
            assert any(lo < n <= hi for n in plens)
    few = serve.pick_sample(ok[:2], by_rid, 1, 6, [128, 256, 512, 768])
    assert len(few) == 2 and serve.pick_sample([], by_rid, 1, 6, [8]) == []
