"""chip_smoke.py off the chip (PR 21): it has no CPU mode, fails in
seconds naming the missing device, prints no result line, and its
parent process stays off jax — the stages themselves only ever run on
the TPU, through the chip tool."""

import ast
import json
import os
import shutil
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.monotonic()
    res = subprocess.run([sys.executable, script], cwd=cwd, env=env,
                         capture_output=True, text=True, timeout=120)
    return res, time.monotonic() - t0


def _no_result_line(stdout):
    for line in stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        assert not (isinstance(obj, dict) and "ok" in obj), line


def test_without_a_tpu_it_fails_in_seconds_naming_the_device():
    res, wall = _run(REPO, SMOKE)
    assert res.returncode != 0
    assert "FAILED: train-cold" in res.stdout
    # jax's own words for the missing device, relayed from the child
    assert "Unknown backend tpu" in res.stdout
    _no_result_line(res.stdout)
    # before any data generation: the synthetic set alone takes longer
    assert wall < 30, wall
    out = os.path.join(REPO, "chiprun_out", "chip_smoke")
    assert not os.path.exists(os.path.join(out, "train-warm"))
    assert not os.path.exists(os.path.join(out, "serve"))


def test_alone_in_a_directory_it_fails_too(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    res, _ = _run(str(tmp_path), str(tmp_path / "chip_smoke.py"))
    assert res.returncode != 0
    assert "No module named veles_tpu" in res.stdout
    _no_result_line(res.stdout)


def test_the_parent_imports_only_the_standard_library():
    """Statically (every import statement in the file) and at run time
    (importing it pulls in neither jax, numpy nor the package) — the
    script also re-checks sys.modules itself before every spawn."""
    with open(SMOKE) as f:
        tree = ast.parse(f.read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add((node.module or "").split(".")[0])
    assert imported <= set(sys.stdlib_module_names), imported
    res = subprocess.run(
        [sys.executable, "-c",
         "import sys, chip_smoke; "
         "bad = {'jax', 'jaxlib', 'numpy', 'veles_tpu'} "
         "& set(sys.modules); assert not bad, bad"],
        cwd=REPO, capture_output=True, text=True, timeout=60)
    assert res.returncode == 0, res.stderr
