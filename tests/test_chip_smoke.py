"""``chip_smoke.py`` on the CPU: its rehearsal passes, and it exits
non-zero when the chip is missing, when the resume does not restore the
emergency step, and when the emergency save fails."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from repro.checkpoint import CheckpointError, CheckpointManager  # noqa: E402


@pytest.fixture(autouse=True)
def no_repo_cache(monkeypatch):
    # the entry point's compile cache would land in the checkout
    monkeypatch.setattr(chip_smoke, "enable_compile_cache", lambda: "off")


def _script(args, tmp_path, **env):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"), **env)
    return subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py"),
                           *args], capture_output=True, text=True, env=env,
                          timeout=300)


def _result(out: str):
    lines = out.strip().splitlines()
    return json.loads(lines[-1]) if lines and lines[-1].startswith("{") else None


def test_script_without_chip_exits_nonzero(tmp_path):
    p = _script([], tmp_path)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert _result(p.stdout) is None


@pytest.mark.parametrize("platform", ["cpu", "gpu"])
def test_refuses_what_is_not_a_tpu(monkeypatch, capsys, platform):
    monkeypatch.setattr(chip_smoke, "device_info", lambda: {
        "platform": platform, "kind": "steered", "count": 1})
    with pytest.raises(chip_smoke.SmokeFailure, match="no TPU"):
        chip_smoke.main([])
    assert '"ok"' not in capsys.readouterr().out


def test_rehearsal_passes(capsys):
    assert chip_smoke.main(["--rehearse"]) == 0
    res = _result(capsys.readouterr().out)
    assert res == {"ok": True, "rehearsal": True,
                   "device": {"platform": "cpu", "kind": "cpu", "count": 1}}


def test_four_chip_rehearsal_spreads_the_state(tmp_path):
    p = _script(["--rehearse", "--chips", "4"], tmp_path,
                XLA_FLAGS="--xla_force_host_platform_device_count=4")
    assert p.returncode == 0, p.stderr[-2000:]
    assert _result(p.stdout)["device"]["count"] == 4
    assert "device 3:" in p.stdout


def _hide_committed(monkeypatch):
    """The resume sees no committed checkpoint: a fresh start."""
    monkeypatch.setattr(CheckpointManager, "restore_latest",
                        lambda self, like=None: None)


def _corrupt_restore(monkeypatch):
    """Every restore fails its CRC: restore_latest falls back past it."""
    def restore(self, step, check_crc=True):
        raise CheckpointError("crc mismatch (injected)")
    monkeypatch.setattr(CheckpointManager, "restore", restore)


@pytest.mark.parametrize("fault", [_hide_committed, _corrupt_restore],
                         ids=["fresh_start", "restore_fails"])
def test_resume_that_misses_the_emergency_step_fails(monkeypatch, capsys, fault):
    orig_run = chip_smoke.train.run
    calls = []

    def run(argv, **kw):
        calls.append(argv)
        if len(calls) == 2:  # the resume, after the killed run
            fault(monkeypatch)
        return orig_run(argv, **kw)

    monkeypatch.setattr(chip_smoke.train, "run", run)
    with pytest.raises(chip_smoke.SmokeFailure, match="fresh start"):
        chip_smoke.main(["--rehearse"])
    assert '"ok"' not in capsys.readouterr().out


def test_failed_emergency_save_fails(monkeypatch, capsys):
    orig = CheckpointManager.save

    def save(self, step, tree, extra=None, delta=False):
        if (extra or {}).get("emergency"):
            raise OSError("disk gone (injected)")
        return orig(self, step, tree, extra, delta=delta)

    monkeypatch.setattr(CheckpointManager, "save", save)
    with pytest.raises(CheckpointError, match="emergency save at step 6"):
        chip_smoke.main(["--rehearse"])
    assert '"ok"' not in capsys.readouterr().out


@pytest.mark.parametrize("from_env", [True, False], ids=["env", "checkout"])
def test_compile_cache_location(monkeypatch, tmp_path, from_env):
    import jax

    from repro.launch import cache

    before = jax.config.jax_compilation_cache_dir
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = cache.enable_compile_cache()
        after = jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    if from_env:  # JAX reads the variable itself; nothing else is set
        assert got == str(tmp_path) and after == before
    else:
        expected = os.path.join(os.path.realpath(ROOT), ".jax_cache")
        assert got == expected and after == expected
