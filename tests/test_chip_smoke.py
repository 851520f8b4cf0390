"""`chip_smoke.py` without a chip: the no-fallback contract.

Under `JAX_PLATFORMS=cpu` the script must stop in its `device` phase —
before any kernel, model or cluster work — exit non-zero, name the
platform it found and end with `"ok": false`. It is run as the driver
runs it, with no size hook and without pretending to be a chip run.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _run(script, cwd, *argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, script, *argv], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


@pytest.fixture(scope="module")
def cpu_run():
    return _run(SCRIPT, REPO)


def test_fails_without_a_chip(cpu_run):
    assert cpu_run.returncode != 0
    last = json.loads(cpu_run.stdout.strip().splitlines()[-1])
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"
    assert set(last) == {"ok", "device"}


def test_stops_in_the_device_phase_and_names_the_platform(cpu_run):
    phases = {line.split("]")[0][1:] for line in cpu_run.stdout.splitlines()
              if line.startswith("[")}
    assert phases == {"device"}
    assert "JAX found platform 'cpu'" in cpu_run.stdout
    assert "not 'tpu'" in cpu_run.stdout


@pytest.mark.parametrize("argv", [(), ("--chips", "4")])
def test_fails_alone_in_a_directory(tmp_path, argv):
    """Without the rest of the repo beside it there is nothing to prove."""
    alone = shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    out = _run(str(alone), str(tmp_path), *argv)
    assert out.returncode != 0
    assert json.loads(out.stdout.strip().splitlines()[-1])["ok"] is False


def test_parent_never_imports_jax():
    """A process that has touched JAX holds the chip: the orchestrating
    parent must not, so every `import jax` sits inside a phase."""
    with open(SCRIPT) as f:
        top_level = [ln for ln in f if ln.startswith(("import ", "from "))]
    assert top_level and not any("jax" in ln or "ray_tpu" in ln
                                 for ln in top_level)
