"""The bring-up seams of ISSUE 21, all on CPU: where the compile cache
goes, and how `chip_smoke.py` behaves without a chip."""

import json
import os
import subprocess
import sys

import jax
import pytest

from singa_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


@pytest.fixture
def config_updates(monkeypatch):
    """Every `jax.config.update` our code makes, recorded not applied."""
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_cache_dir_placed_from_outside_leaves_jax_config_alone(
        monkeypatch, config_updates):
    monkeypatch.setenv(compile_cache.ENV_VAR, "/some/dir")
    assert compile_cache.enable() == "/some/dir"
    assert config_updates == []     # JAX reads the variable itself


def test_cache_dir_defaults_inside_the_checkout(monkeypatch,
                                                config_updates):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.enable() == want
    assert config_updates == [("jax_compilation_cache_dir", want)]


def test_cache_dir_is_the_same_from_any_process_and_cwd(tmp_path):
    code = ("import sys; sys.path.insert(0, sys.argv[1]); "
            "from singa_tpu.utils import compile_cache; "
            "print(compile_cache.enable()); "
            "import jax; print(jax.config.jax_compilation_cache_dir)")
    env = {k: v for k, v in os.environ.items()
           if k != compile_cache.ENV_VAR}
    seen = set()
    for cwd in (str(tmp_path), os.path.join(REPO, "tests")):
        out = subprocess.run([sys.executable, "-c", code, REPO], cwd=cwd,
                             env=env, capture_output=True, text=True,
                             timeout=120, check=True).stdout.split()
        assert out[0] == out[1]
        seen.add(out[0])
    assert seen == {os.path.join(REPO, ".jax_cache")}


def _smoke(*args, cwd=REPO, script=SMOKE):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, script, *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=600)


def test_chip_smoke_without_a_tpu_fails_and_prints_no_result():
    got = _smoke()
    assert got.returncode != 0
    assert got.stdout.strip() == ""     # no leg record, no device metric
    lines = [l for l in got.stderr.splitlines()
             if l.startswith("chip_smoke:")]
    assert len(lines) == 1 and "'cpu', not tpu" in lines[0]


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    alone = tmp_path / "chip_smoke.py"
    alone.write_text(open(SMOKE).read())
    got = _smoke(cwd=str(tmp_path), script=str(alone))
    assert got.returncode != 0 and got.stdout.strip() == ""


def test_chip_smoke_rehearsal_runs_every_leg_and_says_cpu():
    got = _smoke("--rehearsal")
    assert got.returncode == 0, got.stdout[-2000:] + got.stderr[-2000:]
    lines = got.stdout.strip().splitlines()
    final = json.loads(lines[-1])
    assert final["ok"] is True and final["rehearsal"] is True
    assert final["device"]["platform"] == "cpu"
    assert all(l.startswith("rehearsal platform=cpu | ")
               for l in lines[:-1])
    legs = [json.loads(l.split(" | ", 1)[1]) for l in lines[:-1]
            if l.split(" | ", 1)[1].startswith('{"leg": ')]
    assert [r["leg"] for r in legs] == ["train", "serve", "conv"]
    assert all(r["platform"] == "cpu" and r["rehearsal"] is True
               for r in legs)
