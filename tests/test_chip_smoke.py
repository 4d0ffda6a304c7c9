"""``chip_smoke.py`` off the chip: it must refuse, fast and by name.

The smoke itself only passes on a TPU (the driver runs it there).  What
tier-1 can hold it to is the other half of its contract: without an
accelerator it exits non-zero in seconds, says which platform it found,
prints no result line, and never pins the platform itself.
"""

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.monotonic()
    r = subprocess.run(
        [sys.executable, script], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=120,
    )
    return r, time.monotonic() - t0


def _no_result_line(stdout):
    for line in stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        assert not (isinstance(obj, dict) and "ok" in obj), line


def test_fails_fast_without_a_tpu_and_names_the_platform():
    r, wall = _run(REPO, SMOKE)
    assert r.returncode != 0
    assert wall < 60, f"took {wall:.0f}s to notice there is no chip"
    assert "phase=device FAILED" in r.stdout
    assert "platform='cpu'" in r.stdout
    assert "phase=train" not in r.stdout  # nothing ran after the refusal
    _no_result_line(r.stdout)


def test_fails_alone_in_a_directory_without_the_repo(tmp_path):
    import shutil

    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    r, _ = _run(str(tmp_path), str(tmp_path / "chip_smoke.py"))
    assert r.returncode != 0
    _no_result_line(r.stdout)


def test_imports_cleanly_and_never_sets_the_platform():
    import importlib.util

    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)  # no side effects at import
    assert mod.FULL["rows"] == 262_144 and mod.FULL["iters"] == 10
    with open(SMOKE) as f:
        src = f.read()
    assert "JAX_PLATFORMS" not in src.split('"""', 2)[2]
    assert "jax_platforms" not in src
